"""Mean wait, per flush in the window, from the flush request to taking
the sink's flush lock, held by its previous batch until that batch's
verdicts are back (klogs_sink_flush_lock_wait_seconds)."""

from _window import mean_ms


def read(run):
    return mean_ms(run, "klogs_sink_flush_lock_wait_seconds")
