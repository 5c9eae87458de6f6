"""Device layer, open loop: the share of the time in which a request was
pending or running that no operation ran on the device (profiler trace;
union of the ops on each chip's ``XLA Ops`` line, averaged over chips).
Time with no work offered is left out: it is the traffic's, not the
server's."""


def read(run):
    t = run.trace
    if t is None or run.events or not t.get("demand_s"):
        return None
    return 100.0 * (1.0 - t["demand_busy_s"] / t["demand_s"])
