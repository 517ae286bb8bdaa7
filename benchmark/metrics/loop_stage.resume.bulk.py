"""% of the event loop's samples in the window in stage resume
(klogs_loop_samples_total{stage=resume} over all stages)."""

from _loop import stage_share


def read(run):
    return stage_share(run, "resume")
