"""Mean event-loop lag over the window: how late the deadline flusher
woke past the sleep it asked for, once per pass (delta _sum over delta
_count of klogs_loop_lag_seconds)."""

from _window import mean_ms


def read(run):
    return mean_ms(run, "klogs_loop_lag_seconds")
