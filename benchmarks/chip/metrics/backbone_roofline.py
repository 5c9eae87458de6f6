"""Backbone (``models/transformer.py`` under the program's ``backbone``
name scope): FLOPs of the forwards the device executed in the window,
padded lanes included, over the device time of the operations under
that scope times the bf16 peak. Compute-bound, so FLOPs set the bound."""


def read(run):
    t = run.trace
    if t is None or t["backbone_s"] <= 0 or not run.forwards:
        return None
    return 100.0 * run.forwards * run.forward_flops / (
        t["backbone_s"] * run.peaks["bf16_flops"])
