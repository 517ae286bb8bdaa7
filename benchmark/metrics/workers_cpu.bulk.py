"""CPU of every collector thread but the event loop over the window, %
of one core: 100 * (delta klogs_process_cpu_seconds_total - delta
klogs_loop_cpu_seconds_total) / window."""

from _loop import workers_cpu


def read(run):
    return workers_cpu(run)
