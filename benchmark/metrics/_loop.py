"""Arithmetic that the readers of the collector's event-loop accounting
share (klogs_tpu/obs/loopprof.py: stage samples and thread CPU). Each
returns None on a collector without the counter it reads, and where
the window holds nothing to read."""

SAMPLES = "klogs_loop_samples_total"
LOOP_CPU = "klogs_loop_cpu_seconds_total"
PROCESS_CPU = "klogs_process_cpu_seconds_total"


def _has(run, family):
    return run.m1 is not None and any(n == family for n, _ in run.m1)


def stage_share(run, stage):
    """% of the window's samples of the loop that fell in ``stage``."""
    if not _has(run, SAMPLES):
        return None
    total = run.delta(SAMPLES)
    if not total:
        return None
    return 100.0 * run.delta(SAMPLES, stage=stage) / total


def busy(run):
    """The loop thread's CPU over the window, % of one core."""
    if not _has(run, LOOP_CPU) or not run.window_s:
        return None
    cpu = run.delta(LOOP_CPU)
    if not cpu:
        return None
    return 100.0 * cpu / run.window_s


def sys_share(run):
    """% of the loop thread's CPU in the window spent in the kernel."""
    if not _has(run, LOOP_CPU):
        return None
    cpu = run.delta(LOOP_CPU)
    if not cpu:
        return None
    return 100.0 * run.delta(LOOP_CPU, mode="sys") / cpu


def stalled(run):
    """% of the window the loop was ready to run but off the CPU: its
    samples outside poll less its CPU, floored at 0."""
    poll, cpu = stage_share(run, "poll"), busy(run)
    if poll is None or cpu is None:
        return None
    return max(0.0, 100.0 - poll - cpu)


def workers_cpu(run):
    """CPU of every thread but the loop over the window, % of one
    core."""
    cpu = busy(run)
    if cpu is None or not _has(run, PROCESS_CPU):
        return None
    return 100.0 * run.delta(PROCESS_CPU) / run.window_s - cpu
