"""% of the event-loop thread's CPU in the window spent in the kernel
(delta of klogs_loop_cpu_seconds_total{mode=sys} over both modes)."""

from _loop import sys_share


def read(run):
    return sys_share(run)
