"""Share of the timed window in which a chip ran no operation: 1 minus the
union of its operations' intervals in the profiler trace over the window's
length, averaged over the cell's chips."""

import trace_reduce


def read(run):
    if run.trace is None or not run.trace.chips():
        return None
    chips = run.trace.chips()
    busy = sum(trace_reduce.busy_ns(run.trace, c) for c in chips) / len(chips)
    return 1.0 - busy / 1e9 / run.seconds
