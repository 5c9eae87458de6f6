"""Executor and combine (``core/samplers/multistep.py``, ``kernels/``):
the least bytes of the multistep updates the window ran
(``counts.solver_step_bytes`` per lane-step, padded lanes included) over
the device time of the operations outside the ``backbone`` scope times
the HBM bandwidth. Bytes-bound; it counts the same work whichever
combine runs, and the time outside the backbone holds the guidance
combine, noise draws and conversions as well."""


def read(run):
    t = run.trace
    if t is None or not run.lane_steps:
        return None
    rest = t["op_s"] - t["backbone_s"]
    if rest <= 0:
        return None
    return 100.0 * run.lane_steps * run.solver_bytes / (
        rest * run.peaks["hbm_bytes_per_s"])
