"""Milliseconds per window of the window's all-reduce during which no other
operation ran on the chip: the averaging's time on the critical path, the
most over the cell's chips.  The all-reduce is matched in any of the forms
it takes in the trace: jax names the shard_map's ``pmean`` all-reduce
``psum.N``; XLA may also give ``all-reduce``, an async start/done pair, or
reduce-scatter plus all-gather."""

import trace_reduce

COLLECTIVE = r".*(psum|all-reduce|reduce-scatter|all-gather)"


def read(run):
    if run.trace is None or run.windows == 0:
        return None
    ns = [trace_reduce.exposed_ns(run.trace, c, COLLECTIVE)
          for c in run.trace.chips()
          if trace_reduce.kernel_ns(run.trace, c, COLLECTIVE) > 0]
    if not ns:
        return None
    return max(ns) / 1e6 / run.windows
