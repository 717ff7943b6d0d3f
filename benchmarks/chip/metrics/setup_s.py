"""Seconds from the start of the process to the start of the timed window:
imports, data, weights, the program's state and the set-up windows, which
compile the window's programs or load them from the cache."""


def read(run):
    return run.setup_s
