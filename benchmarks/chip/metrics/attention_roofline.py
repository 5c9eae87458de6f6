"""Backbone, attention (``models/attention.py`` under the program's
``attention`` name scope): the S^2 FLOPs of the forwards the device
executed in the window, padded lanes included (the family's
``attention_flops`` at the latent's tokens; DiT's ``2 L 2 S^2 H hd``:
the scores and the weighted sum of values), over the device time of the
operations under that scope times the bf16 peak
(``spans.scope_seconds``)."""

from benchmarks.chip import spans


def read(run):
    if run.trace is None or not run.forwards:
        return None
    seconds = spans.scope_seconds("attention")
    if seconds <= 0:
        return None
    m = run.cell.config["model"]
    flops = run.cell.family.attention_flops(m, m["latent_tokens"])
    return 100.0 * run.forwards * flops / (
        seconds * run.peaks["bf16_flops"])
