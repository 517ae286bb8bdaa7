"""FilteredSink: the write-gating stage.

Sits exactly where the reference writes bytes to disk
(writeLogToDisk, cmd/root.go:359-374), but frames chunks into lines,
asks a LogFilter for a keep-mask, and writes only kept lines — in
the original per-file order (matching is batched, writes are ordered).

Batching policy: lines accumulate until ``batch_lines`` is reached, then
one filter call covers them (amortizing engine overhead — essential for
the TPU path). ``deadline_s`` bounds how long a pending line can wait in
follow mode; the deadline is enforced on the next write and by the
runner's periodic flush.
"""

import asyncio
import time
from dataclasses import dataclass
from dataclasses import field as dataclasses_field
from typing import Callable

from klogs_tpu.filters.base import FilterStats, LogFilter
from klogs_tpu.filters.framer import LineFramer
from klogs_tpu.obs import trace
from klogs_tpu.resilience import Unavailable
from klogs_tpu.runtime.fanout import StreamJob
from klogs_tpu.runtime.sink import FileSink, Sink
from klogs_tpu.ui import term


class FilteredSink(Sink):
    def __init__(
        self,
        inner: Sink,
        log_filter: LogFilter,
        stats: FilterStats,
        batch_lines: int = 1024,
        deadline_s: float = 0.05,
        on_close: "Callable[[FilteredSink], None] | None" = None,
        service: "AsyncFilterService | None" = None,
        on_filter_error: str = "abort",
    ):
        self._inner = inner
        self._filter = log_filter
        self._stats = stats
        self._pending_since: float | None = None
        self._batch_lines = batch_lines
        self._deadline_s = deadline_s
        self._on_close = on_close
        self._closed = False
        self._service = service
        # Degrade routing when the filter service is Unavailable
        # (retries exhausted / breaker open): "pass" writes the batch
        # unfiltered, "drop" discards it, "abort" (default) propagates
        # — one friendly fatal line, reference-style.
        self._on_filter_error = on_filter_error
        self._degrade_warned = False
        # Fully-framed hot path when the native module and a framed
        # service are both present: chunks accumulate in ONE contiguous
        # buffer (C newline sweep), the verdicts come back as a numpy
        # mask, and kept lines are span-gathered from the same buffer —
        # no per-line Python object anywhere between the HTTP read and
        # the file write. Otherwise the list path (LineFramer +
        # list[bytes]) keeps identical semantics.
        self._batcher = None
        if (service is not None and hasattr(service, "match_framed")) or (
                service is None and log_filter is not None):
            from klogs_tpu.filters.framer import FramedBatcher

            try:
                self._batcher = FramedBatcher()
            except RuntimeError:
                pass
        self._framer = LineFramer() if self._batcher is None else None
        self._pending: list[bytes] = []
        # Held across match+write so concurrent flushes (write vs the
        # deadline flusher) cannot reorder this file's lines while a
        # batch is in flight on the async service. Created lazily on
        # first flush: on Py3.10 an asyncio primitive binds the loop
        # that exists at CONSTRUCTION, and sinks are built by
        # make_pipeline before asyncio.run() starts the real one.
        self._flush_lock: "asyncio.Lock | None" = None

    def _pending_count(self) -> int:
        if self._batcher is not None:
            return self._batcher.pending_lines
        return len(self._pending)

    async def write(self, chunk: bytes) -> None:
        if self._batcher is not None:
            had = self._batcher.pending_lines
            n = self._batcher.feed(chunk)
            if n and not had:
                self._pending_since = time.perf_counter()
        else:
            lines = self._framer.feed(chunk)
            if lines:
                if not self._pending:
                    self._pending_since = time.perf_counter()
                self._pending.extend(lines)
            n = len(self._pending)
        if n >= self._batch_lines or (
            n
            and self._pending_since is not None
            and time.perf_counter() - self._pending_since >= self._deadline_s
        ):
            await self._flush_pending()

    async def _flush_pending(self, final: bool = False) -> None:
        # One span per flush: the batch's first hop when no fanout span
        # is active (deadline flusher, close), otherwise a child of the
        # chunk's fanout.read span — either way the root of everything
        # downstream (coalescer/shard/RPC/device/write).
        if self._flush_lock is None:
            self._flush_lock = asyncio.Lock()
        t_request = time.perf_counter()
        with trace.TRACER.span("sink.flush",
                               pending=self._pending_count()):
            async with self._flush_lock:
                # Read after the lock: another flush may have taken the
                # lines that were pending at the request.
                since = self._pending_since
                if since is not None and self._pending_count():
                    self._stats.record_flush_wait(
                        max(0.0, t_request - since),
                        time.perf_counter() - t_request)
                await self._flush_pending_locked(final=final)

    async def _flush_pending_locked(self, final: bool = False) -> None:
        if self._batcher is not None:
            await self._flush_framed(final)
            return
        pending, self._pending = self._pending, []
        self._pending_since = None
        if not pending:
            return
        t0 = time.perf_counter()
        from klogs_tpu.native import hostops

        if self._service is not None and hasattr(self._service,
                                                 "match_framed"):
            # Framed flush over list pending (native module absent or
            # arrived late): one pass builds (payload, offsets), the
            # verdicts come back as a numpy array.
            import numpy as np

            from klogs_tpu.filters.base import frame_lines

            payload, offsets, bytes_in = frame_lines(pending)
            try:
                mask_arr = await self._service.match_framed(payload, offsets)
            except Unavailable as e:
                await self._degrade(e, n_lines=len(pending), payload=payload)
                return
            self._note_recovered()
            latency = time.perf_counter() - t0
            n_kept = int(np.count_nonzero(mask_arr))
            mask_b = np.ascontiguousarray(mask_arr, dtype=np.uint8).tobytes()
            if hostops is not None:
                out = hostops.join_kept(pending, mask_b)
            else:
                out = b"".join(
                    ln for ln, keep in zip(pending, mask_b) if keep)
        else:
            if self._service is not None:
                try:
                    mask = await self._service.match(pending)
                except Unavailable as e:
                    await self._degrade(e, n_lines=len(pending),
                                        payload=b"".join(pending))
                    return
                self._note_recovered()
            else:
                mask = self._filter.match_lines(pending)
            latency = time.perf_counter() - t0
            n_kept = sum(mask)
            if hostops is not None:
                out = hostops.join_kept(pending, bytes(bytearray(mask)))
            else:
                out = b"".join(ln for ln, keep in zip(pending, mask) if keep)
            bytes_in = sum(len(ln) for ln in pending)
        if out:
            with trace.TRACER.span("sink.write", bytes=len(out)):
                await self._inner.write(out)
        self._stats.record_batch(
            n_lines=len(pending),
            n_matched=n_kept,
            n_bytes_in=bytes_in,
            n_bytes_out=len(out),
            latency_s=latency,
        )

    async def _flush_framed(self, final: bool) -> None:
        """The zero-per-line flush: framed batch in, span-gathered
        kept bytes out."""
        import numpy as np

        payload, offsets, n = self._batcher.take(final=final)
        self._pending_since = None
        if n == 0:
            return
        t0 = time.perf_counter()
        if self._service is not None:
            try:
                mask_arr = await self._service.match_framed(payload, offsets)
            except Unavailable as e:
                await self._degrade(e, n_lines=n, payload=payload)
                return
            self._note_recovered()
        else:
            # Direct sync engine (--backend=cpu): the DFA scan releases
            # the GIL and runs at millions of lines/s — no service hop.
            mask_arr = self._filter.fetch_framed(
                self._filter.dispatch_framed(payload, offsets))
        latency = time.perf_counter() - t0
        n_kept = int(np.count_nonzero(mask_arr))
        out = self._batcher._hostops.join_kept_framed(
            payload, np.ascontiguousarray(offsets), n,
            np.ascontiguousarray(mask_arr, dtype=np.uint8).tobytes())
        if out:
            with trace.TRACER.span("sink.write", bytes=len(out)):
                await self._inner.write(out)
        self._stats.record_batch(
            n_lines=n,
            n_matched=n_kept,
            n_bytes_in=len(payload),
            n_bytes_out=len(out),
            latency_s=latency,
        )

    async def _degrade(self, e: Unavailable, *, n_lines: int,
                       payload: bytes) -> None:
        """Route a batch whose filter service is Unavailable per
        --on-filter-error: pass = write unfiltered, drop = discard,
        abort = propagate (the run ends with one friendly line). The
        choice is counted per action so a scrape shows exactly how many
        lines rode each degrade path. Against a sharded --remote fleet
        the service only raises Unavailable after every endpoint has
        failed (partial-fleet failure is rerouted upstream, never
        degraded), so this path still means 'filtering is truly
        gone'."""
        # Flight recorder: a degraded batch is exactly the event an
        # operator reconstructs after the fact — arm a dump carrying
        # this batch's hop story (trace event rides the sink.flush
        # span; the trigger writes when the trace completes).
        trace.TRACER.event("sink.degrade",
                           action=self._on_filter_error, error=str(e))
        trace.flight_trigger("filter-degrade",
                             action=self._on_filter_error, error=str(e))
        if self._on_filter_error == "abort":
            raise e
        if not self._degrade_warned:
            self._degrade_warned = True
            term.warning(
                "filter service unavailable (%s); --on-filter-error=%s: "
                "%s lines until it recovers", e, self._on_filter_error,
                "writing UNFILTERED" if self._on_filter_error == "pass"
                else "DROPPING")
        if self._on_filter_error == "pass" and payload:
            await self._inner.write(payload)
        self._stats.record_degraded(self._on_filter_error, n_lines)

    def _note_recovered(self) -> None:
        # One line when filtering resumes after a degraded stretch —
        # the operator bookend to the degrade warning.
        if self._degrade_warned:
            self._degrade_warned = False
            term.info("filter service recovered; filtering resumed")

    async def flush_if_stale(self) -> None:
        """Flush pending lines whose deadline has passed (called by the
        pipeline's periodic follow-mode flusher)."""
        if (
            self._pending_count()
            and self._pending_since is not None
            and time.perf_counter() - self._pending_since >= self._deadline_s
        ):
            # Deadline-forced (not size-triggered) flushes are the
            # latency-bound signal operators size batch_lines by.
            self._stats.record_deadline_flush()
            await self._flush_pending()
            # Live tailing: matched lines must reach the file, not sit in
            # the inner sink's write buffer.
            await self._inner.flush()

    async def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._on_close is not None:
            self._on_close(self)
        try:
            if self._batcher is None:
                rest = self._framer.flush()
                if rest is not None:
                    self._pending.append(rest)
            await self._flush_pending(final=True)
        finally:
            # The inner sink (file fd) is released even when the final
            # flush dies on an unavailable service or a full disk.
            await self._inner.close()

    @property
    def bytes_written(self) -> int:
        return self._inner.bytes_written


@dataclass
class FilterPipeline:
    """Shared engine + stats across all per-container sinks.

    ``log_filter`` may be None when ``service`` is a remote client (the
    engine lives in the filterd process); sinks then always go through
    the service."""

    log_filter: LogFilter | None
    stats: FilterStats
    batch_lines: int = 1024
    deadline_s: float = 0.05
    service: "AsyncFilterService | None" = None
    patterns: list[str] | None = None
    ignore_case: bool = False
    exclude: list[str] | None = None
    # --on-filter-error degrade routing for every sink this pipeline
    # builds (pass|drop|abort; see FilteredSink).
    on_filter_error: str = "abort"
    # Where gated lines land; None = the reference behavior (a FileSink
    # on job.path). ``-o stdout|both`` injects console/tee factories.
    inner_factory: "Callable[[StreamJob], Sink] | None" = None
    _live_sinks: "set[FilteredSink]" = dataclasses_field(default_factory=set)

    def sink_factory(self, job: StreamJob) -> Sink:
        inner = (self.inner_factory(job) if self.inner_factory is not None
                 else FileSink(job.path))
        sink = FilteredSink(
            inner,
            self.log_filter,
            self.stats,
            batch_lines=self.batch_lines,
            deadline_s=self.deadline_s,
            on_close=self._live_sinks.discard,
            service=self.service,
            on_filter_error=self.on_filter_error,
        )
        self._live_sinks.add(sink)
        return sink

    async def run_deadline_flusher(self,
                                   stop: "asyncio.Event | None" = None
                                   ) -> None:
        """Follow-mode latency bound: periodically force pending lines in
        every live sink through the filter, so a matching line from a
        quiet container appears within ~deadline_s even if no further
        chunks arrive. Run as a background task; cancel to stop.

        ``--on-filter-error=abort`` escalation: an Unavailable raised by
        a stale flush means the documented "end the run with one clear
        error" — set ``stop`` (graceful stream teardown) and re-raise so
        the awaiter surfaces it, instead of quietly dropping the batch
        of an idle stream that will never write again."""
        period = self.deadline_s / 2
        while True:
            t_sleep = time.perf_counter()
            await asyncio.sleep(period)
            t_woke = time.perf_counter()
            # Concurrent: a serial sweep over N slow flushes would make
            # the sweep period N x the flush latency (observed: minutes
            # at 200 sinks). With the coalescing service these merge
            # into a handful of device batches anyway. Per-sink fault
            # isolation: one dead SINK (SinkError) must not kill the
            # flusher for every healthy stream — its own worker
            # surfaces that failure at the next write. The flushes
            # start outside the pass's span: each stays its own root.
            flushes = [asyncio.ensure_future(s.flush_if_stale())
                       for s in list(self._live_sinks)]
            with trace.TRACER.span("sink.flusher", sinks=len(flushes)):
                results = await asyncio.gather(*flushes,
                                               return_exceptions=True)
            self.stats.record_flusher_pass(
                max(0.0, t_woke - t_sleep - period),
                time.perf_counter() - t_woke)
            for r in results:
                if isinstance(r, Unavailable):
                    term.error("filter service unavailable and "
                               "--on-filter-error=abort: stopping (%s)", r)
                    # The run is ending on a degrade: flush the armed
                    # dump NOW — no further root span may ever finish.
                    trace.flight_trigger("abort-escalation", error=str(r))
                    trace.RECORDER.flush()
                    if stop is not None:
                        stop.set()
                    raise r
                if isinstance(r, Exception):
                    term.warning("deadline flush failed: %s", r)

    async def start(self) -> None:
        """Pre-flight: remote services verify the collector's pattern
        set against the server's before any line flows."""
        verify = getattr(self.service, "verify_patterns", None)
        if verify is not None and self.patterns is not None:
            await verify(self.patterns, self.ignore_case,
                         exclude=self.exclude or [])

    async def aclose(self) -> None:
        """Awaited teardown (run_async calls this): services that hold
        loop resources (grpc channel, in-flight batch tasks) shut down
        cleanly inside the loop instead of leaking fire-and-forget
        tasks into interpreter exit."""
        aclose = getattr(self.service, "aclose", None)
        if aclose is not None:
            await aclose()
        elif self.service is not None:
            self.service.close()
        elif self.log_filter is not None:
            self.log_filter.close()

    def close(self) -> None:
        if self.service is not None:
            self.service.close()  # in-process: also closes the filter
        elif self.log_filter is not None:
            self.log_filter.close()

    def print_summary(self) -> None:
        s = self.stats
        term.info(
            "Filter stats: %d lines in, %d matched (%.1f%%), %.0f lines/sec, "
            "batch latency p50=%.2fms p99=%.2fms (%d batches)",
            s.lines_in, s.lines_matched, s.matched_pct(), s.lines_per_sec(),
            s.percentile_latency_s(50) * 1e3, s.percentile_latency_s(99) * 1e3,
            s.batches,
        )
        if s.has_service_latencies:
            # Split so saturation is diagnosable: queue = coalesce +
            # backpressure wait before dispatch; device = engine time.
            term.info(
                "  queue p50=%.2fms p99=%.2fms | device p50=%.2fms p99=%.2fms",
                s.percentile_queue_s(50) * 1e3, s.percentile_queue_s(99) * 1e3,
                s.percentile_device_s(50) * 1e3,
                s.percentile_device_s(99) * 1e3,
            )
        if s.pf_lines:
            term.info(
                "  prefilter: %.1f%% candidates (%d/%d lines), "
                "%d/%d tiles skipped",
                100.0 * s.pf_candidates / s.pf_lines,
                s.pf_candidates, s.pf_lines,
                s.pf_tiles_total - s.pf_tiles_live, s.pf_tiles_total,
            )
        elif s.pf_disabled_reason:
            term.info("  %s", s.pf_disabled_reason)


def _build_filter(patterns: list[str], backend: str, stats,
                  ignore_case: bool) -> "LogFilter":
    """One engine for one pattern set (shared by the include and
    exclude sides so both always get the same backend treatment)."""
    if backend == "cpu":
        # Strongest host engine the set admits (native DFA scan ->
        # combined-re -> K-sequential re); KLOGS_CPU_ENGINE overrides.
        from klogs_tpu.filters.cpu import best_host_filter

        return best_host_filter(
            patterns, ignore_case=ignore_case,
            registry=stats.registry if stats is not None else None)[0]
    import jax

    from klogs_tpu.filters.tpu import NFAEngineFilter, resolve_kernel

    # Raises DeviceUnavailable when --backend tpu finds no TPU and the
    # run did not ask for the CPU.
    kernel = resolve_kernel()
    # Multi-chip: shard lines (data) x pattern groups over the mesh;
    # single chip: plain on-device batches, no collective overhead.
    engine = None
    if jax.device_count() > 1:
        from klogs_tpu.parallel.mesh import MeshEngine

        # The per-shard Pallas kernel on chips; GSPMD over the jnp path
        # on a CPU mesh.
        impl = {"pallas": "pallas", "interpret": "pallas_interpret",
                "jnp": "gspmd"}[kernel]
        engine = MeshEngine(
            patterns, ignore_case=ignore_case, impl=impl,
            on_degrade=stats.record_degrade if stats is not None else None)
    return NFAEngineFilter(patterns, ignore_case=ignore_case,
                           engine=engine, kernel=kernel, stats=stats)


def _env_positive_float(name: str, default: float) -> float:
    """Env-tunable positive float; zero/negative/nan/inf/garbage is
    rejected as ServiceConfigError naming the variable (a bad knob must
    not surface as a mystery timeout/latency downstream). The
    validation itself is the shared one in klogs_tpu.utils.env."""
    from klogs_tpu.service.client import ServiceConfigError
    from klogs_tpu.utils.env import positive_float

    return positive_float(name, default, exc=ServiceConfigError)


def make_pipeline(patterns: list[str], backend: str,
                  batch_lines: int | None = None,
                  deadline_s: float = 0.05,
                  remote: str | None = None,
                  ignore_case: bool = False,
                  exclude: list[str] | None = None,
                  registry=None,
                  on_filter_error: str = "abort",
                  shard_mode: str = "round-robin",
                  resolver: str | None = None,
                  kubeconfig: str | None = None) -> FilterPipeline:
    # ``registry`` (an obs.Registry) shares the stats backing store
    # with a /metrics sidecar or --stats-json dump; None keeps the
    # pipeline's numbers private (default, and what tests rely on).
    stats = FilterStats(registry=registry)
    service = None
    exclude = exclude or []
    if remote is not None or resolver is not None:
        from klogs_tpu.service.client import RemoteFilterClient
        from klogs_tpu.service.shard import (
            DEFAULT_HEDGE_S,
            DEFAULT_PROBE_INTERVAL_S,
            ShardedFilterClient,
            parse_endpoints,
            pattern_fingerprint,
        )

        # Transport security for the cross-node collector->filterd hop,
        # via env (a --remote deployment is configured by manifest, not
        # interactive flags): KLOGS_REMOTE_TLS_CA switches to TLS,
        # _TLS_CERT/_TLS_KEY add mTLS, _TOKEN_FILE attaches bearer auth
        # (passed as a path: the client re-reads it per RPC, so a
        # rotated mounted Secret keeps working mid-follow). A bad combo
        # raises ServiceConfigError, which the CLI maps to one friendly
        # line — no SystemExit from library code.
        # Per-RPC deadline: KLOGS_REMOTE_TIMEOUT_S bounds each attempt
        # (retry/backoff/breaker defaults live in the client; see
        # docs/RESILIENCE.md). Zero/negative would DEADLINE_EXCEED
        # every attempt with an error that never names the env var.
        rpc_timeout_s = _env_positive_float("KLOGS_REMOTE_TIMEOUT_S", 30.0)
        # --resolver: live membership (service/resolver.py). --remote
        # (when also given) is only the seed; the resolver's snapshots
        # take over from the first poll. A resolver alone may start
        # with an EMPTY seed — the first poll fills the fleet.
        live_resolver = None
        if resolver is not None:
            from klogs_tpu.service.resolver import make_resolver

            try:
                live_resolver = make_resolver(resolver,
                                              kubeconfig=kubeconfig)
            except ValueError as e:
                from klogs_tpu.service.client import ServiceConfigError

                raise ServiceConfigError(str(e)) from None
        targets = parse_endpoints(remote) if remote is not None else []
        from klogs_tpu.resilience import FAULTS

        stray = FAULTS.armed_targets() - set(targets)
        if stray and live_resolver is not None:
            # With live membership the fleet is open-ended: a targeted
            # clause naming a future joiner is legitimate chaos.
            stray = set()
        if stray:
            # A targeted chaos clause naming an endpoint outside the
            # fleet can never fire — one typoed digit and the chaos run
            # green-lights behavior it never exercised. Loud, like
            # every other bad-fault-spec path.
            term.warning(
                "KLOGS_FAULTS targets %s not in the --remote list %s — "
                "those clauses will never fire",
                ", ".join(sorted(stray)), ",".join(targets))
        from klogs_tpu.utils.env import read as env_read

        common = dict(
            tls_ca=env_read("KLOGS_REMOTE_TLS_CA"),
            tls_cert=env_read("KLOGS_REMOTE_TLS_CERT"),
            tls_key=env_read("KLOGS_REMOTE_TLS_KEY"),
            auth_token_file=env_read("KLOGS_REMOTE_TOKEN_FILE"),
            rpc_timeout_s=rpc_timeout_s,
            registry=registry)
        if len(targets) == 1 and live_resolver is None:
            # Single endpoint: the plain client, byte-identical to the
            # pre-shard behavior (no hedge tasks, no prober). With a
            # resolver even a single seed takes the sharded tier — the
            # fleet can grow past it.
            service = RemoteFilterClient(targets[0], **common)
        else:
            # A fleet: the sharded tier (docs/RESILIENCE.md, "Sharded
            # tier"). A batch raises Unavailable — and hence degrades
            # per --on-filter-error — only when EVERY endpoint is down.
            service = ShardedFilterClient(
                targets,
                shard_mode=shard_mode,
                fingerprint=pattern_fingerprint(patterns, exclude,
                                                ignore_case),
                hedge_s=_env_positive_float("KLOGS_HEDGE_S",
                                            DEFAULT_HEDGE_S),
                probe_interval_s=_env_positive_float(
                    "KLOGS_READYZ_INTERVAL_S", DEFAULT_PROBE_INTERVAL_S),
                resolver=live_resolver,
                **common)
        return FilterPipeline(
            log_filter=None,
            stats=stats,
            batch_lines=batch_lines or 8192,
            deadline_s=deadline_s,
            service=service,
            patterns=patterns,
            ignore_case=ignore_case,
            exclude=exclude,
            on_filter_error=on_filter_error,
        )
    if backend not in ("cpu", "tpu"):
        raise ValueError(f"unknown filter backend {backend!r}")
    from klogs_tpu.filters.base import build_include_exclude

    # Stats ride the include side only (or the combiner's inputs would
    # double-count); a both-empty call raises in the combinator instead
    # of building a pipeline that crashes on first use.
    made = []

    def builder(pats):
        f = _build_filter(pats, backend, stats if not made else None,
                          ignore_case)
        made.append(f)
        return f

    log_filter: LogFilter = build_include_exclude(builder, patterns, exclude)
    if backend == "cpu":
        batch_lines = batch_lines or 1024
    else:
        from klogs_tpu.filters.async_service import AsyncFilterService

        # Device batches are cheap per line but each round trip has fixed
        # latency: bigger batches + the async pipeline hide it.
        batch_lines = batch_lines or 8192
        service = AsyncFilterService(log_filter, stats=stats)
    return FilterPipeline(
        log_filter=log_filter,
        stats=stats,
        batch_lines=batch_lines,
        deadline_s=deadline_s,
        service=service,
        on_filter_error=on_filter_error,
    )
