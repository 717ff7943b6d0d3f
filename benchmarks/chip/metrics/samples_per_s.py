"""Samples trained per second: every sample of every worker on every chip
in the timed window, over the window's whole wall time (host clock), which
holds every per-window sync and batch transfer."""


def read(run):
    return run.samples / run.seconds
