"""The collector's event-loop thread: its CPU over the window, % of one
core (100 * delta klogs_loop_cpu_seconds_total / window)."""

from _loop import busy


def read(run):
    return busy(run)
