"""% of the window the collector's event loop was ready to run but off
the CPU (the GIL, or preemption): its samples outside poll less its
CPU share, floored at 0."""

from _loop import stalled


def read(run):
    return stalled(run)
