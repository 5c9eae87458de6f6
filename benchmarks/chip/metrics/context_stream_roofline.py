"""Backbone, context stream (``models/mmdit.py`` under the program's
``context_stream`` name scope: the text stream's embedder, adaLN
modulation, projections and MLP): the family's ``context_flops`` of the
forwards the device executed in the window, padded lanes included, over
the device time of the operations under that scope times the bf16 peak
(``spans.scope_seconds``). FLOPs set the bound: a solve call runs 8
sequences (4 lanes under CFG) through each layer, so its 1.6 TFLOP
take 8.3 ms at peak and reading the stream's 0.99 B float32 weights
4.8 ms. A family without a text stream reads none."""

from benchmarks.chip import spans


def read(run):
    flops = getattr(run.cell.family, "context_flops", None)
    if run.trace is None or not run.forwards or flops is None:
        return None
    seconds = spans.scope_seconds("context_stream")
    if seconds <= 0:
        return None
    return 100.0 * run.forwards * flops(run.cell.config["model"]) / (
        seconds * run.peaks["bf16_flops"])
