"""The median wall time of one timed CoDA window, in milliseconds: fit's
loop from one ``sample_window`` call to the next (draw, transfer, the
window's programs and fit's sync).  A run's rare stalls of the host move
the window's rate but not this median."""
import statistics


def read(run):
    if not run.window_s:
        return None
    return 1e3 * statistics.median(run.window_s)
