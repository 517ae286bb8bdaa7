"""Mean time of one deadline-flusher pass over every live sink in the
window (klogs_sink_flusher_seconds)."""

from _window import mean_ms


def read(run):
    return mean_ms(run, "klogs_sink_flusher_seconds")
