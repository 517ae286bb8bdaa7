"""Arithmetic that the readers of the collector's own histograms and
host spans share. Each returns None where it finds nothing to read."""


def mean_ms(run, family):
    """Mean of one histogram's observations in the window, in ms:
    delta _sum over delta _count."""
    n = run.delta(family + "_count")
    if not n:
        return None
    return 1000.0 * run.delta(family + "_sum") / n


def idle_share_with(run, stage, inside):
    """% of the window in which the device idled while the host stage
    ``stage`` was open (``inside``) or not open, the mean over devices;
    None where the trace holds no such host span (a program that does
    not annotate it, or a trace reduced without the host plane)."""
    if run.trace is None:
        return None
    w = run.trace["window_ns"]
    shares = []
    for d in run.trace["devices"].values():
        if stage not in d.get("idle_host_ns", {}):
            return None
        ns = d["idle_host_ns"][stage]
        shares.append(ns if inside else w - d["busy_ns"] - ns)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares) / w
