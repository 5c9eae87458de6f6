"""Engine loop, step scheduler: the median over the window's
``serve.tick`` spans of the host's part of a tick, in ms: the tick's
duration less its ``serve.sync`` child, where the host waits on the
device (profiler trace, ``spans.host_ms``). Admission, joins, the step's
dispatch, the result Python and the merge; what the device idles on
between ticks."""

from benchmarks.chip import spans


def read(run):
    if run.trace is None:
        return None
    return spans.host_ms("serve.tick")
