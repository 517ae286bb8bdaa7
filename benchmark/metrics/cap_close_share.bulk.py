"""% of the coalesced groups dispatched in the window that closed
because the next caller batch would have carried them past the
coalescer's line ceiling: 100 * delta klogs_coalescer_cap_closes_total
over delta klogs_coalescer_groups_total. None where the collector has
no such counter or dispatched no group."""

CAP_CLOSES = "klogs_coalescer_cap_closes_total"


def read(run):
    if not any(name == CAP_CLOSES for name, _ in run.m1):
        return None
    groups = run.delta("klogs_coalescer_groups_total")
    if not groups:
        return None
    return 100.0 * run.delta(CAP_CLOSES) / groups
