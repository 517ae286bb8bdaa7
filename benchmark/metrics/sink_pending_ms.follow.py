"""Mean wait, per flush in the window, from the first pending line's
arrival in its sink to the flush request
(klogs_sink_pending_wait_seconds)."""

from _window import mean_ms


def read(run):
    return mean_ms(run, "klogs_sink_pending_wait_seconds")
