"""Milliseconds per window in the caller's input layer: the benchmark's
host span around its ``sample_window`` callback (the NumPy draw, the gather
and the transfer to the device, until the batch is there), averaged over
the timed windows."""


def read(run):
    if not run.input_s:
        return None
    return 1e3 * sum(run.input_s) / len(run.input_s)
