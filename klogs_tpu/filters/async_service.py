"""AsyncFilterService — pipelined, coalescing batch execution for
device filters.

Two problems it solves:

1. **Round-trip latency.** A synchronous ``match_lines`` pays the full
   host<->device round trip per batch (tens of ms on a remote-attached
   TPU), serializing every sink's flush behind it. Device dispatch in
   jax is asynchronous, so dispatch happens on the event loop (cheap
   enqueue) and completion on a small thread pool, N batches in flight.

2. **Tiny-batch flood.** In follow mode, hundreds of rate-limited
   streams each flush a handful of lines every deadline tick; per-sink
   round trips would cap throughput at (workers / RTT) batches/s. The
   service therefore COALESCES concurrent match() calls into jumbo
   device batches — callers' lines are concatenated, one kernel runs,
   and verdict slices resolve each caller's future. The device sees
   large batches (its efficient regime) no matter how fragmented the
   callers are; p99 latency gains the coalesce window (few ms) and
   loses the queueing collapse.

   A group closes when the coalesce window ends or when it reaches
   ``coalesce_lines``, which is a CEILING: a caller whose lines would
   carry the pending group past it closes the group first and starts
   the next one (a single caller larger than the ceiling goes alone).
   The engine pads a group's rows up to a power of two
   (filters/tpu._bucket_batch), so the default ceiling is one such
   bucket, 16,384 rows: a full-size group fills the bucket it is
   padded to.

Per-sink write ordering is the sink's concern (FilteredSink holds its
flush lock across the await); cross-sink batches merge and overlap
freely. In-flight device work is bounded (backpressure).

The reference has no counterpart — its write path is synchronous
io.Copy per goroutine (/root/reference/cmd/root.go:359-374); this plays
the role the Go scheduler plays there, adapted to a device whose
dispatch has ms-scale fixed cost.
"""

import asyncio
import time
from concurrent.futures import ThreadPoolExecutor

from klogs_tpu.filters.base import FilterStats, LogFilter, frame_lines
from klogs_tpu.obs import trace

# Each in-flight fetch blocks one worker thread for a full host<->device
# round trip, so sustained batches/s caps at workers / RTT. On a remote
# attach (~74ms RTT) that cap binds well before the engine does; both
# knobs are env-tunable for such deployments. Malformed values warn and
# fall back rather than crashing module import (the shared
# warn-and-default dialect in klogs_tpu.utils.env).
from klogs_tpu.utils.env import warn_positive_int as _env_int


DEFAULT_MAX_IN_FLIGHT = _env_int("KLOGS_MAX_IN_FLIGHT", 16)
DEFAULT_FETCH_WORKERS = _env_int("KLOGS_FETCH_WORKERS", 8)
DEFAULT_COALESCE_LINES = _env_int("KLOGS_COALESCE_LINES", 16384)
DEFAULT_COALESCE_DELAY_S = 0.005

# Offsets ride int32: a coalesced group whose combined payload passes
# this would wrap member offset shifts into negative values (the C
# validators then fail the WHOLE group with an obscure range error).
# Groups are split below the limit instead. Module-level so tests can
# exercise the split without allocating 2 GiB.
GROUP_PAYLOAD_LIMIT = 2**31 - 1


class AsyncFilterService:
    def __init__(self, log_filter: LogFilter,
                 max_in_flight: int = DEFAULT_MAX_IN_FLIGHT,
                 fetch_workers: int = DEFAULT_FETCH_WORKERS,
                 coalesce_lines: int = DEFAULT_COALESCE_LINES,
                 coalesce_delay_s: float = DEFAULT_COALESCE_DELAY_S,
                 stats: FilterStats | None = None,
                 executor: "ThreadPoolExecutor | None" = None,
                 in_flight: "asyncio.Semaphore | None" = None):
        self._filter = log_filter
        # Optional split-latency recording (queue wait vs device time) so
        # --stats can tell saturation queueing from engine latency.
        self._stats = stats
        # Coalescer instrumentation rides the stats' registry (one
        # source of truth with the /metrics scrape); stats=None keeps
        # the zero-overhead path.
        self._m = None
        if stats is not None:
            r = stats.registry
            self._m = {
                "depth": r.family("klogs_coalescer_queue_depth"),
                "pending": r.family("klogs_coalescer_pending_lines"),
                "groups": r.family("klogs_coalescer_groups_total"),
                "cap_closes": r.family("klogs_coalescer_cap_closes_total"),
                "members": r.family("klogs_coalescer_group_members"),
                "lines": r.family("klogs_coalescer_group_lines"),
                "splits": r.family("klogs_coalescer_group_splits_total"),
                "bp_wait": r.family(
                    "klogs_coalescer_backpressure_wait_seconds"),
                "dispatch": r.family("klogs_coalescer_dispatch_seconds"),
            }
        # The multi-tenant registry (service/tenancy.py) injects ONE
        # shared fetch pool + ONE in-flight semaphore across every
        # set's service: the process owns one device, so the budget is
        # global. A service only shuts down a pool it created itself.
        # An owned semaphore is created lazily at first dispatch: on
        # Py3.10 it binds the loop alive at CONSTRUCTION, and services
        # are built by make_pipeline before asyncio.run() starts.
        self._sem: "asyncio.Semaphore | None" = in_flight
        self._max_in_flight = max_in_flight
        self._own_pool = executor is None
        self._pool = executor if executor is not None else ThreadPoolExecutor(
            max_workers=fetch_workers, thread_name_prefix="klogs-fetch"
        )
        self._coalesce_lines = coalesce_lines
        self._coalesce_delay_s = coalesce_delay_s
        # Utilization-profiler probes (obs/profiler.py): the live
        # queue-depth / in-flight / executor-saturation samples the
        # /profile snapshot carries. Registered only on instrumented
        # pipelines (stats present), dropped at close; name collisions
        # (multi-set registries build one service per set over the
        # SHARED pool) resolve last-writer-wins, which is the shared
        # budget's one true value anyway.
        self._probes: "dict[str, object]" = {}
        if stats is not None:
            from klogs_tpu.obs.profiler import PROFILER

            self._probes = {
                "coalescer.queue_depth":
                    lambda: float(len(self._pending)),
                "coalescer.pending_lines":
                    lambda: float(self._pending_lines),
                "device.in_flight_used": self._in_flight_used,
                "device.fetch_queue": self._fetch_queue_depth,
            }
            for name, fn in self._probes.items():
                PROFILER.add_probe(name, fn)
        # (payload, offsets, n_lines, future, enqueue_time) per caller.
        self._pending: list[tuple] = []
        self._pending_lines = 0
        self._kick_handle: asyncio.TimerHandle | None = None
        self._closed = False
        # Strong refs: the loop only weakly references tasks, so a
        # coalesced-batch task could be GC'd mid-flight, stranding every
        # caller future in its group.
        self._tasks: set[asyncio.Task] = set()
        self.batches_dispatched = 0  # for tests / stats

    @property
    def coalesce_lines(self) -> int:
        return self._coalesce_lines

    @property
    def max_in_flight(self) -> int:
        return self._max_in_flight

    def apply_tuning(self, coalesce_lines: "int | None" = None,
                     max_in_flight: "int | None" = None) -> None:
        """Adopt a new operating point (ops/tune.py AdaptiveController).
        Coalesce sizing applies from the next enqueue; in-flight depth
        resizes the semaphore LIVE — an increase releases fresh permits
        immediately, a decrease absorbs permits in the background as
        in-flight batches retire (work already dispatched is never
        cancelled). Values are trusted: the controller validates and
        bounds them against ops/tune.py OPERATING_SURFACE."""
        if coalesce_lines is not None:
            self._coalesce_lines = int(coalesce_lines)
        if max_in_flight is None:
            return
        new = int(max_in_flight)
        delta = new - self._max_in_flight
        if delta == 0:
            return
        self._max_in_flight = new
        sem = self._sem
        if sem is None:
            return  # not yet created: first dispatch builds it at `new`
        if delta > 0:
            for _ in range(delta):
                sem.release()
            return

        async def _absorb(n: int = -delta) -> None:
            # Permits always return as groups retire, so this settles
            # once the pipeline drains to the new depth; aclose gathers
            # it after the group tasks for the same reason. Acquire-
            # and-HOLD is the point (capacity shrinks for good), and
            # the semaphore dies with the service, so a cancelled
            # absorb strands nothing.
            for _ in range(n):
                await sem.acquire()  # klogs: ignore[cancel-safety] — hold is intentional, sem dies with service

        task = asyncio.get_running_loop().create_task(_absorb())
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)

    def _in_flight_used(self) -> float:
        """Occupied in-flight dispatch slots (0 before first dispatch
        creates the semaphore)."""
        sem = self._sem
        if sem is None:
            return 0.0
        return float(max(0, self._max_in_flight - sem._value))

    def _fetch_queue_depth(self) -> float:
        """Fetches waiting for a free executor worker — the executor-
        saturation sample (>0 means every fetch worker is mid-round-
        trip and dispatches queue behind them)."""
        q = getattr(self._pool, "_work_queue", None)
        return float(q.qsize()) if q is not None else 0.0

    def _drop_probes(self) -> None:
        if self._probes:
            from klogs_tpu.obs.profiler import PROFILER

            for name, fn in self._probes.items():
                PROFILER.remove_probe(name, fn)  # type: ignore[arg-type]
            self._probes = {}

    async def match(self, lines: list[bytes]) -> list[bool]:
        """Resolves with one verdict per line. Concurrent calls coalesce
        into shared device batches. Internally the batch is framed
        immediately (one contiguous payload + offsets, see
        filters.base.frame_lines) so coalescing and dispatch never touch
        per-line Python objects again."""
        if not lines:
            return []
        payload, offsets, _ = frame_lines(lines)
        arr = await self._enqueue(payload, offsets, len(lines))
        return arr.tolist()

    async def match_framed(self, payload: bytes, offsets):
        """Framed-batch entry: offsets is an int32[n+1] prefix-sum
        array. Resolves with a numpy bool verdict array (a view-slice of
        the coalesced group's verdicts — zero per-line work)."""
        n = len(offsets) - 1
        if n <= 0:  # includes the pathological empty-offsets array
            import numpy as np

            if n < 0:
                raise ValueError("framed batch: empty offsets array")
            return np.zeros(0, dtype=bool)
        return await self._enqueue(payload, offsets, n)

    async def _enqueue(self, payload: bytes, offsets, n: int):
        if self._closed:
            raise RuntimeError("AsyncFilterService is closed")
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        if self._pending and self._pending_lines + n > self._coalesce_lines:
            # Kick before overflow: the pending group goes as it is and
            # this caller opens the next one.
            if self._m is not None:
                self._m["cap_closes"].inc()
            self._kick(loop)
        # The caller's span context rides the pending entry: the
        # coalesced group's dispatch span parents under the FIRST
        # caller's trace (one trace carries the full downstream story)
        # and the other members are linked as events.
        ctx = trace.TRACER.current_context()
        if ctx is not None:
            trace.TRACER.event("coalescer.enqueue", lines=n,
                               queue_depth=len(self._pending))
        self._pending.append((payload, offsets, n, fut,
                              time.perf_counter(), ctx))
        self._pending_lines += n
        if self._m is not None:
            self._m["depth"].set(len(self._pending))
            self._m["pending"].set(self._pending_lines)
        if self._pending_lines >= self._coalesce_lines:
            self._kick(loop)
        elif self._kick_handle is None:
            self._kick_handle = loop.call_later(
                self._coalesce_delay_s, self._kick, loop
            )
        return await fut

    def _kick(self, loop) -> None:
        if self._kick_handle is not None:
            self._kick_handle.cancel()
            self._kick_handle = None
        if not self._pending:
            return
        group, self._pending = self._pending, []
        self._pending_lines = 0
        if self._m is not None:
            self._m["depth"].set(0)
            self._m["pending"].set(0)
        task = loop.create_task(self._run_group(group))
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)

    async def _run_group(self, group) -> None:
        import numpy as np

        loop = asyncio.get_running_loop()
        if len(group) > 1 and (
                sum(len(e[0]) for e in group) > GROUP_PAYLOAD_LIMIT):
            # A concatenated payload past int32 would wrap the member
            # offset shifts below into negative values. Split into
            # subgroups under the limit (each member is itself bounded:
            # frame_lines and the framed-wire decode both reject >int32
            # single batches) and run them sequentially — correctness
            # over peak batch size in this pathological regime.
            subs, sub, size = [], [], 0
            for e in group:
                if sub and size + len(e[0]) > GROUP_PAYLOAD_LIMIT:
                    subs.append(sub)
                    sub, size = [], 0
                sub.append(e)
                size += len(e[0])
            subs.append(sub)
            if self._m is not None:
                self._m["splits"].inc(len(subs) - 1)
            for sub in subs:
                await self._run_group(sub)
            return
        if len(group) == 1:
            payload, offsets = group[0][0], group[0][1]
        else:
            # Concatenate framed batches: payloads join; each offsets
            # array shifts by the cumulative payload base. All
            # vectorized over the (few) group members, never per line.
            payload = b"".join(e[0] for e in group)
            parts = []
            base = 0
            for e in group:
                parts.append(e[1][:-1] + base)
                base += len(e[0])
            parts.append(np.asarray([base], dtype=np.int32))
            offsets = np.concatenate(parts)
        # One trace carries the group's downstream story: the first
        # member with a recording context parents the dispatch span;
        # the other members' traces are linked as events (a span cannot
        # have N parents, but the flight recorder can still connect
        # them through the link events).
        parent = next(
            (e[5] for e in group
             if e[5] is not None and e[5].sampled),
            next((e[5] for e in group if e[5] is not None), None))
        with trace.TRACER.span("coalescer.dispatch", parent=parent,
                               members=len(group),
                               lines=len(offsets) - 1) as sp:
            for e in group:
                ctx = e[5]
                if (ctx is not None and ctx is not parent
                        and getattr(ctx, "sampled", False)):
                    sp.add_event("coalescer.link",
                                 trace_id=f"{ctx.trace_id:032x}",
                                 span_id=f"{ctx.span_id:016x}")
            try:
                t_sem = time.perf_counter()
                if self._sem is None:
                    self._sem = asyncio.Semaphore(self._max_in_flight)
                async with self._sem:
                    t_dispatch = time.perf_counter()
                    if self._stats is not None:
                        self._stats.mark_batch_started(t_dispatch)
                        for e in group:
                            self._stats.record_queue_wait(t_dispatch - e[4])
                    if self._m is not None:
                        self._m["bp_wait"].observe(t_dispatch - t_sem)
                        self._m["groups"].inc()
                        self._m["members"].observe(len(group))
                        self._m["lines"].observe(len(offsets) - 1)
                    sp.add_event("coalescer.dispatching",
                                 backpressure_wait_s=t_dispatch - t_sem)
                    handle = self._filter.dispatch_framed(payload, offsets)
                    self.batches_dispatched += 1
                    if self._m is not None:
                        self._m["dispatch"].observe(
                            time.perf_counter() - t_dispatch)
                    # The fetch blocks an executor thread for the full
                    # device round trip; the span wraps the AWAIT (the
                    # context var does not cross into the thread — the
                    # await site owns the timing).
                    with trace.TRACER.span("device.fetch"):
                        verdicts = await loop.run_in_executor(
                            self._pool, self._filter.fetch_framed, handle
                        )
                    if self._stats is not None:
                        self._stats.record_device_batch(
                            time.perf_counter() - t_dispatch)
            except Exception as e:
                # The exception is consumed here (routed to the member
                # futures), so __exit__ would record status=ok — mark
                # the span explicitly or the flight dump shows a
                # clean-looking dispatch for the batch that failed.
                sp.set_status("error")
                sp.set_attr("error", f"{type(e).__name__}: {e}")
                for _, _, _, fut, *_ in group:
                    if not fut.done():
                        fut.set_exception(e)
                return
        off = 0
        for _, _, n, fut, *_ in group:
            if not fut.done():
                fut.set_result(verdicts[off : off + n])
            off += n

    async def aclose(self) -> None:
        """Graceful shutdown: dispatch any coalescing (un-kicked) lines,
        then drain in-flight batch tasks, so no caller future is
        stranded and no task dies with the loop."""
        self._closed = True
        self._drop_probes()
        if self._pending:
            self._kick(asyncio.get_running_loop())
        elif self._kick_handle is not None:
            self._kick_handle.cancel()
            self._kick_handle = None
        if self._tasks:
            await asyncio.gather(*list(self._tasks), return_exceptions=True)
        # All in-flight fetches were just gathered, so the join is
        # near-instant — but it still joins threads, which must not
        # happen on the event loop (every other stream's flush would
        # stall behind it). An injected (shared) pool outlives this
        # service: its owner shuts it down.
        if self._own_pool:
            await asyncio.to_thread(self._pool.shutdown)
        self._filter.close()

    def close(self) -> None:
        self._closed = True
        self._drop_probes()
        if self._kick_handle is not None:
            self._kick_handle.cancel()
            self._kick_handle = None
        if self._own_pool:
            self._pool.shutdown(wait=True)
        self._filter.close()
