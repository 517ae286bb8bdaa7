"""% of the event loop's samples in the window in stage flush
(klogs_loop_samples_total{stage=flush} over all stages)."""

from _loop import stage_share


def read(run):
    return stage_share(run, "flush")
