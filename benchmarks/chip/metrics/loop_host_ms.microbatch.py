"""Engine loop, solve scheduler: the median over the window's
``serve.microbatch`` spans of the host's part of a microbatch, in ms:
its duration less its ``serve.sync`` child, where the host waits on the
device (profiler trace, ``spans.host_ms``). Key folding, the initial
noise, the solve's dispatch and the results."""

from benchmarks.chip import spans


def read(run):
    if run.trace is None:
        return None
    return spans.host_ms("serve.microbatch")
