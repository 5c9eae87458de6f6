"""Compile-cache layer: XLA backend compiles inside the measured window
(a ``jax.monitoring`` listener). Every shape is warmed in set-up, so the
target is 0; a compile in the window is latency some request paid."""


def read(run):
    return run.window_compiles
