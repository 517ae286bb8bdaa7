"""Mean bytes per socket read in the window, in KiB: delta
klogs_source_bytes_total over delta klogs_source_reads_total, both of
kind socket."""


def read(run):
    reads = run.delta("klogs_source_reads_total", kind="socket")
    if not reads:
        return None
    return run.delta("klogs_source_bytes_total", kind="socket") / reads / 1024
