"""Scheduler, step scheduler: the 95th percentile over the window's
joins of the seconds a request queued, from its first enqueue to its
join into a lane (the ``queued_s`` argument of each ``serve.join``
span, host clock; ``stats.quantile``)."""

from benchmarks.chip import spans


def read(run):
    if run.trace is None:
        return None
    return spans.arg_quantile("serve.join", "queued_s", 0.95)
