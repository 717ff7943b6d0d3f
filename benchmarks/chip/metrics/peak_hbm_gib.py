"""The largest ``peak_bytes_in_use`` over the cell's chips, read from the
runtime's allocator right after the window (set-up included), in GiB."""


def read(run):
    return run.peak_bytes / 2 ** 30
