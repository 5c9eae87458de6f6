"""Scheduler layer: the share of computed lane-steps that served a
request, ``active_lane_steps / lane_steps`` from ``ServeEngine.stats()``
summed over buckets, over the window (counts, not time)."""


def read(run):
    if run.lane_steps <= 0:
        return None
    return 100.0 * run.active_lane_steps / run.lane_steps
