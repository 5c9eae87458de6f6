"""Entry layer: the 95th percentile of the lag from a request's due time
to its ``ServeEngine.submit`` (host clock). The harness submits between
scheduler calls, so a ``step()`` that holds the loop for a whole
microbatch shows here. Open-loop cells only."""

from benchmarks.chip.stats import quantile


def read(run):
    lags = [r.submit - r.due for r in run.records if r.submit is not None]
    if run.events or not lags:
        return None
    return quantile(lags, 0.95)
