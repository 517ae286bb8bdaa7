"""LogFilter interface and shared statistics.

This is the new layer the north star inserts at the reference's write
boundary (between the stream read at cmd/root.go:325 and the buffered
file write at cmd/root.go:366): lines go in, a keep/drop verdict per
line comes out, and only kept lines reach the sink.

Implementations:
- RegexFilter (klogs_tpu.filters.cpu): host-side ``re`` engine, the
  CPU baseline (≙ the Go ``regexp`` path in the north star).
- NFAEngineFilter (klogs_tpu.filters.tpu): bit-parallel batch NFA under
  JAX, with jnp and Pallas execution paths.

A line "matches" when ANY of the K patterns matches anywhere in the
line (re.search semantics, unanchored).
"""

import abc
import threading
import time


class FilterStats:
    """Aggregate pipeline statistics, for the --stats summary and the
    north-star metrics (lines/sec, matched %, batch latency).

    A VIEW over an obs.Registry — every number lives in a registered
    metric family (the same objects a /metrics scrape or --stats-json
    dump reads), so the summary and the instrument panel can never
    disagree. By default each FilterStats owns a private Registry
    (isolated pipelines/tests); the --metrics-port paths pass the
    process-global ``obs.REGISTRY`` so the sidecar scrapes live values.

    Three latency series are kept separate so saturation diagnosis is
    possible (the e2e number conflates them):
    - batch (e2e): sink-observed await, enqueue -> verdicts.
    - queue: enqueue -> device dispatch (coalescing + backpressure wait),
      recorded by AsyncFilterService.
    - device: dispatch -> verdicts fetched, recorded by
      AsyncFilterService.
    """

    def __init__(self, registry=None):
        from klogs_tpu.obs.metrics import Registry

        self.registry = registry if registry is not None else Registry()
        r = self.registry
        self._lines_in = r.family("klogs_sink_lines_total")
        self._lines_matched = r.family("klogs_sink_lines_matched_total")
        self._bytes_in = r.family("klogs_sink_bytes_in_total")
        self._bytes_out = r.family("klogs_sink_bytes_out_total")
        self._batches = r.family("klogs_sink_batches_total")
        self._deadline_flushes = r.family("klogs_sink_deadline_flush_total")
        self._batch = r.family("klogs_sink_batch_latency_seconds")
        self._queue = r.family("klogs_coalescer_queue_wait_seconds")
        self._device = r.family("klogs_engine_device_batch_seconds")
        # Two-phase (prefilter) visibility: without these a user cannot
        # tell whether gating is engaged, let alone winning.
        self._pf_lines = r.family("klogs_engine_prefilter_lines_total")
        self._pf_candidates = r.family(
            "klogs_engine_prefilter_candidates_total")
        self._pf_tiles = r.family("klogs_engine_prefilter_tiles_total")
        self._pf_tiles_live = r.family(
            "klogs_engine_prefilter_tiles_live_total")
        self._compiles = r.family("klogs_engine_compile_total")
        self._bucket_width = r.family("klogs_engine_bucket_width_bytes")
        self._pad_bytes = r.family("klogs_engine_pad_bytes_total")
        self._payload_bytes = r.family("klogs_engine_payload_bytes_total")
        # Device-sweep visibility (thousand-pattern fused path): which
        # narrowing stage ran, what it let through, and the degrade /
        # bypass events an operator needs to explain a throughput step.
        self._sweep_batches = r.family("klogs_sweep_batches_total")
        self._sweep_lines = r.family("klogs_sweep_lines_total")
        self._sweep_cand = r.family("klogs_sweep_candidate_lines_total")
        self._sweep_fallback = r.family("klogs_sweep_fallback_total")
        # Every kernel degrade (a Mosaic compile or execution failure
        # the engine survived on a plainer kernel), by the path that
        # gave way: a run that degraded is not a run of the kernel it
        # asked for, and chip_smoke.py fails on any of these.
        self._engine_degrade = r.family("klogs_engine_degrade_total")
        # Degrade-policy visibility (--on-filter-error, resilience):
        # batches/lines that bypassed or skipped filtering because the
        # filter service was unavailable.
        self._degraded_batches = r.family(
            "klogs_filter_degraded_batches_total")
        self._degraded_lines = r.family(
            "klogs_filter_degraded_lines_total")
        self.pf_disabled_reason: str | None = None
        # The device block (platform, kind, count, kernel) of the TPU
        # engine feeding these stats — set by NFAEngineFilter, read at
        # --stats-json dump time; None on the host (cpu) backend.
        self.device_doc = None
        self.started_at = time.perf_counter()
        # Warmup boundary: timestamp when the FIRST batch started
        # filtering. lines_per_sec measures from here, not from pipeline
        # construction — otherwise jit warmup deflates short runs
        # (VERDICT r1). Written by the dispatch loop AND by synchronous
        # record_batch fallbacks that benches drive from plain threads,
        # so the first-write race is settled under a lock (declared in
        # the lock-discipline table, tools/analysis).
        self._t_lock = threading.Lock()
        self.first_batch_started_at: float | None = None

    # -- counter views (the pre-registry attribute API) ---------------
    @property
    def lines_in(self) -> int:
        return int(self._lines_in.value)

    @property
    def lines_matched(self) -> int:
        return int(self._lines_matched.value)

    @property
    def degraded_lines(self) -> int:
        """Lines that took ANY degrade action (pass/drop), summed
        across actions — the --backfill "shed" accounting."""
        return int(sum(child.value
                       for _lv, child in self._degraded_lines.children()))

    @property
    def bytes_in(self) -> int:
        return int(self._bytes_in.value)

    @property
    def bytes_out(self) -> int:
        return int(self._bytes_out.value)

    @property
    def batches(self) -> int:
        return int(self._batches.value)

    @property
    def pf_lines(self) -> int:
        return int(self._pf_lines.value)

    @property
    def pf_candidates(self) -> int:
        return int(self._pf_candidates.value)

    @property
    def pf_tiles_total(self) -> int:
        return int(self._pf_tiles.value)

    @property
    def pf_tiles_live(self) -> int:
        return int(self._pf_tiles_live.value)

    def mark_batch_started(self, t: float | None = None) -> None:
        """Record the true start of the first filtered batch. Called at
        DISPATCH time (AsyncFilterService), so lines/sec on short runs
        is not overstated by back-computing the start from the first
        completion (which credits the whole first-batch latency as
        warmup)."""
        with self._t_lock:
            if self.first_batch_started_at is None:
                self.first_batch_started_at = (
                    t if t is not None else time.perf_counter())

    def record_batch(self, n_lines: int, n_matched: int, n_bytes_in: int,
                     n_bytes_out: int, latency_s: float) -> None:
        with self._t_lock:
            if self.first_batch_started_at is None:
                # Fallback for synchronous paths that never mark dispatch.
                self.first_batch_started_at = (
                    time.perf_counter() - latency_s)
        self._lines_in.inc(n_lines)
        self._lines_matched.inc(n_matched)
        self._bytes_in.inc(n_bytes_in)
        self._bytes_out.inc(n_bytes_out)
        self._batches.inc()
        # Exemplar: when a trace is recording this batch, the latency
        # sample links to it in the exposition — a p99 outlier points
        # straight at its hop-by-hop story.
        from klogs_tpu.obs.trace import TRACER

        self._batch.observe(latency_s, exemplar=TRACER.exemplar())

    def record_prefilter(self, n_lines: int, n_candidates: int,
                         n_tiles: int, n_tiles_live: int) -> None:
        self._pf_lines.inc(n_lines)
        self._pf_candidates.inc(n_candidates)
        self._pf_tiles.inc(n_tiles)
        self._pf_tiles_live.inc(n_tiles_live)

    def record_sweep(self, path: str, n_lines: int,
                     n_candidates: int) -> None:
        """One batch narrowed by the literal sweep: ``path`` is which
        stage ran (device = fused on-device sweep, host = host factor
        sweep)."""
        self._sweep_batches.labels(path=path).inc()
        self._sweep_lines.labels(path=path).inc(n_lines)
        self._sweep_cand.labels(path=path).inc(n_candidates)

    def record_degrade(self, path: str) -> None:
        """A device kernel gave way and the engine carried on with a
        plainer one: ``path`` names what failed (sweep_build,
        sweep_kernel, gated_kernel, chain, fetch). A device-sweep
        degrade also counts on klogs_sweep_fallback_total, which the
        host indexed engine's sweep shares."""
        self._engine_degrade.labels(path=path).inc()
        if path.startswith("sweep"):
            self._sweep_fallback.inc()
            from klogs_tpu.obs.trace import flight_trigger

            flight_trigger("sweep-fallback")

    def record_queue_wait(self, wait_s: float) -> None:
        self._queue.observe(wait_s)

    def record_device_batch(self, latency_s: float) -> None:
        from klogs_tpu.obs.trace import TRACER

        self._device.observe(latency_s, exemplar=TRACER.exemplar())

    def record_deadline_flush(self) -> None:
        """A flush forced by the follow-mode deadline (not batch size)
        — the signal that sinks are running latency-bound."""
        self._deadline_flushes.inc()

    def record_degraded(self, action: str, n_lines: int) -> None:
        """One sink flush handled by the --on-filter-error degrade
        policy instead of the filter (service unavailable): ``action``
        is what happened to its lines (pass = written unfiltered,
        drop = discarded)."""
        self._degraded_batches.labels(action=action).inc()
        self._degraded_lines.labels(action=action).inc(n_lines)

    def record_engine_batch(self, width: int, rows: int,
                            payload_bytes: int) -> None:
        """One width-bucketed sub-batch dispatched to the device:
        tracks the bucket-width distribution and padding waste
        (bucketed tensor area minus useful payload)."""
        self._bucket_width.observe(width)
        self._payload_bytes.inc(payload_bytes)
        self._pad_bytes.inc(max(0, width * rows - payload_bytes))

    def record_compile(self) -> None:
        """A (width, rows) batch geometry first seen by the engine —
        one jit trace/compile (the cold-start cost /readyz guards)."""
        self._compiles.inc()

    def percentile_latency_s(self, q: float) -> float:
        return self._batch.percentile(q)

    def percentile_queue_s(self, q: float) -> float:
        return self._queue.percentile(q)

    def percentile_device_s(self, q: float) -> float:
        return self._device.percentile(q)

    @property
    def has_service_latencies(self) -> bool:
        return self._device.count > 0

    def lines_per_sec(self) -> float:
        start = (self.first_batch_started_at
                 if self.first_batch_started_at is not None
                 else self.started_at)
        elapsed = time.perf_counter() - start
        return self.lines_in / elapsed if elapsed > 0 else 0.0

    def matched_pct(self) -> float:
        return 100.0 * self.lines_matched / self.lines_in if self.lines_in else 0.0


# Offsets ride int32 (device-friendly, half the index bandwidth of
# int64); batches past this must be split upstream, never silently
# wrapped into negative offsets.
_INT32_MAX = 2**31 - 1


def frame_lines(lines: list[bytes], strip_nl: bool = True):
    """list[bytes] -> (payload, offsets: int32[n+1], raw_total) — the
    framed-batch builder (one contiguous buffer + prefix sums instead of
    n PyBytes). Trailing-newline runs are stripped when strip_nl, the
    engine's rstrip(b"\\n") parity rule; raw_total is the UNstripped
    byte count (stats bytes-in). Native single-pass when built."""
    import numpy as np

    from klogs_tpu.native import hostops

    if hostops is not None and hasattr(hostops, "frame_lines"):
        payload, offs, raw = hostops.frame_lines(lines, int(strip_nl))
        return payload, np.frombuffer(offs, dtype=np.int32), raw
    raw = sum(len(ln) for ln in lines)
    bodies = [ln.rstrip(b"\n") for ln in lines] if strip_nl else lines
    # Stripping only shrinks, so raw bounds the payload: the second
    # (stripped) sum runs only for batches that could actually wrap.
    if raw > _INT32_MAX and sum(len(b) for b in bodies) > _INT32_MAX:
        # Parity with the native packer: int32 cumsum would silently
        # wrap into negative offsets (empty mis-sliced lines downstream)
        # — fail loudly instead.
        raise OverflowError(
            f"framed batch payload (> {_INT32_MAX} bytes) exceeds "
            "int32 offsets; split the batch")
    offsets = np.zeros(len(lines) + 1, dtype=np.int32)
    if bodies:
        offsets[1:] = np.cumsum(
            np.fromiter((len(b) for b in bodies), np.int32, len(bodies)))
    return b"".join(bodies), offsets, raw


def pack_framed_rows(payload: bytes, offsets, width: int,
                     rows: "int | None" = None, sel=None, lens=None):
    """Framed batch -> ([rows, width] u8 zero-padded row batch,
    [B] int64 lens): the vectorized ragged scatter that turns the
    collector's contiguous payload into the packed row layout device
    kernels consume (the inverse of frame_lines, minus the padding).
    Every payload byte's destination is its row stride minus the
    source line start — one fancy-indexed assignment, no per-line
    PyBytes. ``rows`` >= B pads extra zero rows (jit-cache row
    bucketing); rows beyond B and columns beyond each line stay zero.
    Callers must ensure every line fits ``width``.

    ``sel`` (int row indices) packs only those frame rows, in ``sel``
    order; ``lens`` overrides the per-row byte counts (selected rows
    when ``sel`` is given) — how the TPU engine's framed byte entry
    packs one width bucket with trailing newlines stripped. Shared by
    that entry, the IndexedFilter device-sweep path, and bench.py so
    the bench times the SAME packer production runs."""
    import numpy as np

    offsets = np.asarray(offsets)
    starts = offsets[:-1].astype(np.int64)
    contiguous = sel is None and lens is None
    if sel is not None:
        starts = starts[sel]
        if lens is None:
            lens = np.diff(offsets).astype(np.int64)[sel]
    if lens is None:
        lens = np.diff(offsets).astype(np.int64)
    lens = np.asarray(lens, dtype=np.int64)
    B = len(lens)
    if rows is None:
        rows = B
    batch = np.zeros((rows, width), dtype=np.uint8)
    total = int(lens.sum())
    if total:
        arr = np.frombuffer(payload, dtype=np.uint8)
        row_base = np.arange(B, dtype=np.int64) * width
        if contiguous:
            # Whole frame, unmodified lens: the source indices are one
            # arange over the payload span.
            shift = np.repeat(row_base - starts, lens)
            src = np.arange(int(offsets[0]), int(offsets[-1]),
                            dtype=np.int64)
            batch.reshape(-1)[src + shift] = arr[src]
        else:
            # General ragged gather/scatter (row subset and/or
            # stripped lens): absolute source index per byte via the
            # standard ragged-range trick.
            ends = np.cumsum(lens)
            intra = np.arange(total, dtype=np.int64) - np.repeat(
                ends - lens, lens)
            src = np.repeat(starts, lens) + intra
            batch.reshape(-1)[np.repeat(row_base, lens) + intra] = arr[src]
    return batch, lens


def split_frame(payload: bytes, offsets) -> list[bytes]:
    """Framed batch -> list[bytes] (line i = payload[offsets[i]:
    offsets[i+1]]) — the bridge for engines without a framed fast path.
    ``offsets`` is an int32 numpy array of n+1 exclusive prefix sums."""
    from klogs_tpu.native import hostops

    n = len(offsets) - 1
    if hostops is not None and hasattr(hostops, "split_frame"):
        import numpy as np

        return hostops.split_frame(
            payload, np.ascontiguousarray(offsets, dtype=np.int32), n)
    if not isinstance(payload, bytes):
        payload = bytes(payload)  # memoryview slab: slices must be bytes
    return [payload[offsets[i]:offsets[i + 1]] for i in range(n)]


class LogFilter(abc.ABC):
    """K-pattern any-match line filter."""

    @abc.abstractmethod
    def match_lines(self, lines: list[bytes]) -> list[bool]:
        """One verdict per line; True = keep. Lines include no trailing
        newline requirement — implementations must tolerate either."""

    # -- two-phase API for pipelined execution ------------------------
    # Device engines override these so a batch can be ENQUEUED without
    # blocking on its result: dispatch() returns an opaque handle after
    # (cheap, async) submission; fetch() blocks until the verdicts are
    # ready. The default degrades to synchronous matching, so every
    # filter is usable behind AsyncFilterService.

    def dispatch(self, lines: list[bytes]):
        return self.match_lines(lines)

    def fetch(self, handle) -> list[bool]:
        return handle

    # -- framed API ---------------------------------------------------
    # A "framed batch" is (payload: bytes, offsets: int32[n+1] prefix
    # sums): one contiguous buffer instead of n PyBytes. It is the
    # zero-per-line-object representation the service/wire path rides
    # (per-line msgpack objects measured ~1us/line of pure overhead on
    # the single-core loopback — SERVICE_BENCH.json round-4 rows).
    # Engines with a native framed packer override dispatch_framed;
    # the default bridges through the list path so every filter works.
    # fetch_framed returns a numpy bool array (callers count/slice it
    # without materializing per-line Python bools).

    def dispatch_framed(self, payload: bytes, offsets):
        return self.dispatch(split_frame(payload, offsets))

    def fetch_framed(self, handle):
        import numpy as np

        return np.asarray(self.fetch(handle), dtype=bool)

    def close(self) -> None:
        """Release engine resources (device buffers, transports)."""


class IncludeExcludeFilter(LogFilter):
    """keep = (no include set OR include matches) AND NOT exclude
    matches — the stern-style noise-suppression combinator. Both sides
    are independent LogFilters; dispatch() submits BOTH batches before
    either result is awaited, so on device engines the two automata
    pipeline instead of serializing round trips."""

    def __init__(self, include: "LogFilter | None", exclude: LogFilter):
        self.include = include
        self.exclude = exclude

    def match_lines(self, lines: list[bytes]) -> list[bool]:
        return self.fetch(self.dispatch(lines))

    def dispatch(self, lines: list[bytes]):
        hi = self.include.dispatch(lines) if self.include is not None else None
        he = self.exclude.dispatch(lines)
        return (hi, he)

    def fetch(self, handle) -> list[bool]:
        hi, he = handle
        ex = self.exclude.fetch(he)
        if hi is None:
            return [not e for e in ex]
        inc = self.include.fetch(hi)
        return [i and not e for i, e in zip(inc, ex)]

    def dispatch_framed(self, payload: bytes, offsets):
        # When NEITHER side has a native framed path, split once and
        # share the list — the per-side default bridge would run
        # split_frame twice over the same payload (2n allocations on
        # the flush hot path).
        def bridged(f):
            return (f is None
                    or type(f).dispatch_framed is LogFilter.dispatch_framed)

        if bridged(self.include) and bridged(self.exclude):
            return ("list", self.dispatch(split_frame(payload, offsets)))
        hi = (self.include.dispatch_framed(payload, offsets)
              if self.include is not None else None)
        he = self.exclude.dispatch_framed(payload, offsets)
        return ("framed", (hi, he))

    def fetch_framed(self, handle):
        import numpy as np

        kind, inner = handle
        if kind == "list":
            return np.asarray(self.fetch(inner), dtype=bool)
        hi, he = inner
        ex = self.exclude.fetch_framed(he)
        if hi is None:
            return ~ex
        return self.include.fetch_framed(hi) & ~ex

    def close(self) -> None:
        if self.include is not None:
            self.include.close()
        self.exclude.close()


def build_include_exclude(builder, patterns: list[str],
                          exclude: "list[str] | None") -> LogFilter:
    """Compose include/exclude pattern sets over a single-engine
    ``builder(pats) -> LogFilter`` — THE one place the combination
    logic lives (collector and filterd both call it, so they can never
    drift). Raises when both sets are empty: a pipeline with no
    patterns at all has nothing to decide."""
    exclude = exclude or []
    if not patterns and not exclude:
        raise ValueError("need at least one include or exclude pattern")
    include = builder(patterns) if patterns else None
    if exclude:
        return IncludeExcludeFilter(include, builder(exclude))
    return include
