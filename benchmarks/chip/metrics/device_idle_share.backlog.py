"""Device layer, backlog: the share of the traced window in which no
operation ran on the device. Work is always pending, so every idle
microsecond is the host's."""


def read(run):
    t = run.trace
    if t is None or not run.events or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
