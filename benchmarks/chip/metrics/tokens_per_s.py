"""Tokens trained per second: every token of every sequence of every worker
on every chip in the timed window, over the window's whole wall time (host
clock), which holds every per-window sync and batch transfer."""


def read(run):
    return run.samples * run.cell.traffic["seq_len"] / run.seconds
