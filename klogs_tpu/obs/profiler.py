"""Continuous pipeline utilization profiler + the fleet capacity signal.

Spans (obs.trace) say where one batch went; metrics (obs.metrics) say
how often things happened. Neither answers the operating question the
ROADMAP's elasticity items need answered continuously: *which stage is
the bottleneck right now, and how much headroom does this process
have?* This module closes that gap with two cooperating pieces:

- ``PipelineProfiler`` — folds every finished span whose name is in
  the pipeline stage catalog (PR 9's spans: fanout.read ->
  coalescer.dispatch -> device.sweep/groupscan/kernel/fetch ->
  sink.write -> rpc.client/server ...) into per-stage busy-seconds,
  and on a cheap periodic tick derives rolling per-stage utilization
  (busy-seconds per wall-second over the tick window, unbiased by the
  trace sampling rate), samples registered probes (queue depth,
  in-flight slots, executor saturation), and serves the result as the
  ``/profile`` JSON endpoint on the obs sidecar plus an optional
  ``--profile-json`` rolling JSONL file. Off by default: until
  ``enable()`` runs, the tracer sink is never installed, so the
  per-span cost of a disabled profiler is exactly zero.

- ``FleetCapacity`` — the filterd-side capacity accountant: offered vs
  admitted lines (offered = entered a match RPC; admitted = passed
  tenancy admission and produced verdicts), rolling rates over a short
  window, and a headroom estimate in [0, 1] combining the profiler's
  observed peak stage utilization with the admitted-rate-vs-envelope
  ratio (``KLOGS_FLEET_CAPACITY_LPS``). The server
  advertises all three through Hello so ``ShardedFilterClient``
  re-exports them per endpoint (``klogs_fleet_endpoint_*``) — the
  scrape an HPA consumes.

Design rules (the obs budget discipline):

- Folding rides the span stream — per-BATCH, never per-line — and is
  one dict lookup + two float adds per span. The <2% overhead budget
  on the K=1024 bench path is measured and recorded by
  ``tools/bench_fleet.py`` (BENCH_FLEET.json ``overhead`` row).
- Utilization is windowed at tick time, not per span; gauges and the
  JSONL line update once per ``KLOGS_PROFILE_INTERVAL_S``.
- Everything is bounded: the stage catalog is a fixed enum, probes are
  a small named dict, the capacity history is a pruned deque.
"""

import json
import os
import threading
import time
from collections import deque
from typing import TYPE_CHECKING, Any, Callable

from klogs_tpu.obs import trace as _trace

if TYPE_CHECKING:
    import asyncio

    from klogs_tpu.obs.metrics import Registry

# The pipeline stage catalog: the span names (docs/OBSERVABILITY.md
# "Span catalog") the profiler folds. A fixed enum — the `stage` label
# on the klogs_profile_* families is bounded by this tuple.
STAGES: "tuple[str, ...]" = (
    "source.read",
    "fanout.read",
    "sink.flush",
    "sink.write",
    "coalescer.dispatch",
    "shard.dispatch",
    "rpc.client",
    "rpc.server",
    "tenant.admit",
    "device.frame",
    "device.sweep",
    "device.groupscan",
    "device.kernel",
    "device.fetch",
    "mesh.dispatch",
)
_STAGE_SET = frozenset(STAGES)

DEFAULT_INTERVAL_S = 1.0
# Rolling window for the offered/admitted rate estimate.
_CAPACITY_WINDOW_S = 30.0
# Minimum spacing between capacity history samples.
_CAPACITY_SAMPLE_S = 0.5

# Fallback zero point for process uptime when /proc is unreadable.
_T0 = time.monotonic()


def _profile_sample_from_env(default: float) -> float:
    """KLOGS_PROFILE_SAMPLE: the trace-sampling rate the profiler
    requests when enabled (0..1; 0 = profiling stays off even when
    --profile-json asks for it). Malformed values raise naming the
    variable — a typo'd knob silently profiling nothing is exactly the
    blind spot this subsystem exists to remove."""
    from klogs_tpu.utils.env import read as env_read

    raw = env_read("KLOGS_PROFILE_SAMPLE")
    if raw is None:
        return default
    try:
        val = float(raw)
    except ValueError:
        raise ValueError(
            f"KLOGS_PROFILE_SAMPLE={raw!r}: expected a number in [0, 1]"
        ) from None
    if not 0.0 <= val <= 1.0:
        raise ValueError(
            f"KLOGS_PROFILE_SAMPLE={raw!r}: expected a number in [0, 1]")
    return val


def process_uptime_s() -> float:
    """Seconds since THIS process started (not since module import):
    /proc/self/stat field 22 is the start time in clock ticks since
    boot, /proc/uptime the seconds since boot. Falls back to the
    module-load zero point where /proc is unavailable."""
    try:
        with open("/proc/self/stat", "rb") as f:
            stat = f.read()
        with open("/proc/uptime", "rb") as f:
            boot_uptime = float(f.read().split()[0])
        # Fields after the parenthesized comm (which may contain
        # spaces): field 22 (1-based) = starttime, i.e. index 19 after
        # the closing paren.
        after = stat.rsplit(b")", 1)[1].split()
        start_ticks = int(after[19])
        hz = os.sysconf("SC_CLK_TCK")
        return max(0.0, boot_uptime - start_ticks / float(hz))
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _T0


def process_rss_bytes() -> int:
    """Current resident set size in bytes (/proc/self/statm field 2 x
    page size); 0 where /proc is unavailable."""
    try:
        with open("/proc/self/statm", "rb") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


def refresh_process_metrics(registry: "Registry | None") -> None:
    """Update the process-level gauges (klogs_process_uptime_seconds /
    klogs_process_rss_bytes) so headroom math and dashboards need no
    node exporter. Called before each /metrics render (off the event
    loop), at --stats-json dump time, and on every profiler tick."""
    if registry is None:
        return
    registry.family("klogs_process_uptime_seconds").set(process_uptime_s())
    registry.family("klogs_process_rss_bytes").set(process_rss_bytes())


class PipelineProfiler:
    """Per-stage busy-seconds accounting over the finished-span stream.

    ``PROFILER`` below is the process-global instance (one pipeline
    story per process, like the tracer); private instances isolate
    tests. Until ``enable()`` runs, ``on_span`` is never installed as a
    tracer sink — a disabled profiler costs literally nothing per span.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._enabled = False
        self._sample = 0.0
        self._interval_s = DEFAULT_INTERVAL_S
        self._t_enabled: "float | None" = None
        # stage -> [busy_s, span_count]; mutated under _lock (the span
        # stream arrives from loop and executor threads alike).
        self._stages: "dict[str, list[float]]" = {}
        # parent span_id -> folded-child duration accumulated so far:
        # stages nest (shard.dispatch wraps rpc.client wraps the wire),
        # so each span folds its SELF time — duration minus folded
        # children — or the outermost wrapper would always "win" the
        # bottleneck. Bounded: entries whose parent never folds (e.g.
        # an unfolded ancestor) are evicted oldest-first past the cap.
        self._child_busy: "dict[str, float]" = {}
        self._util: "dict[str, float]" = {}
        self._last_tick: "tuple[float, dict[str, float]] | None" = None
        self._last_doc: "dict[str, Any] | None" = None
        self._probes: "dict[str, Callable[[], float]]" = {}
        self._capacity: "FleetCapacity | None" = None
        self._device: "Callable[[], dict | None] | None" = None
        self._json_lock = threading.Lock()
        self._json_path: "str | None" = None
        self._registry: "Registry | None" = None
        # Already-synced (busy_s, spans) per stage, so counter families
        # advance by tick-time deltas (counters cannot be set).
        self._synced: "dict[str, tuple[float, int]]" = {}

    # -- configuration ------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, sample: "float | None" = None) -> bool:
        """Turn the profiler on: install the span-fold sink and make
        sure spans actually flow (raises the tracer's sampling rate to
        the profile sample unless KLOGS_TRACE_SAMPLE explicitly pins
        one). ``sample`` defaults to KLOGS_PROFILE_SAMPLE, else 1.0 —
        asking for a profile means you want the profile. Returns
        whether the profiler is enabled (KLOGS_PROFILE_SAMPLE=0 keeps
        it off even against an explicit --profile-json)."""
        rate = sample if sample is not None else _profile_sample_from_env(1.0)
        if rate <= 0.0:
            return self._enabled
        from klogs_tpu.utils.env import positive_float

        # Validated HERE, on the main enablement path: a malformed
        # interval raising later inside the background ticker task
        # would kill profiling silently — exactly the typo'd-knob
        # blind spot this subsystem exists to remove.
        interval = positive_float("KLOGS_PROFILE_INTERVAL_S",
                                  DEFAULT_INTERVAL_S)
        with self._lock:
            self._enabled = True
            self._sample = rate
            self._interval_s = interval
            if self._t_enabled is None:
                self._t_enabled = time.perf_counter()
        _trace.TRACER.ensure_sample(rate)
        # Idempotent install (trace.reset() in tests drops all sinks).
        _trace.TRACER.remove_sink(self.on_span)
        _trace.TRACER.add_sink(self.on_span)
        return True

    def maybe_enable(self) -> bool:
        """Env-driven enablement: on iff KLOGS_PROFILE_SAMPLE > 0."""
        rate = _profile_sample_from_env(0.0)
        if rate > 0.0:
            return self.enable(rate)
        return self._enabled

    def bind_registry(self, registry: "Registry | None") -> None:
        with self._lock:
            self._registry = registry
            self._synced = {}

    def attach_capacity(self, capacity: "FleetCapacity | None") -> None:
        """Attach the filterd's capacity accountant so /profile and the
        JSONL stream carry the offered/admitted/headroom block (a later
        server instance in the same process rebinds, like the tracer's
        registry binding)."""
        self._capacity = capacity

    def attach_device(self,
                      device: "Callable[[], dict | None] | None") -> None:
        """Attach filterd's device block (platform, kind, count,
        kernel) so /profile says what the stages ran on."""
        self._device = device

    def set_json_path(self, path: "str | None") -> None:
        """--profile-json PATH: append one JSON line per tick."""
        with self._json_lock:
            self._json_path = path

    def add_probe(self, name: str, fn: "Callable[[], float]") -> None:
        """Register a named point-in-time sampler (queue depth,
        in-flight slots, executor saturation) read on each tick. A
        re-registration under the same name replaces the probe (one
        live pipeline per process owns each name)."""
        with self._lock:
            self._probes[name] = fn

    def remove_probe(self, name: str,
                     fn: "Callable[[], float] | None" = None) -> None:
        """Drop a probe; with ``fn`` given, only when it is still the
        registered one (a replaced probe belongs to its new owner)."""
        with self._lock:
            if fn is None or self._probes.get(name) is fn:
                self._probes.pop(name, None)

    def reset(self) -> None:
        """Test hook: disable, uninstall the sink, wipe all state."""
        _trace.TRACER.remove_sink(self.on_span)
        with self._lock:
            self._enabled = False
            self._sample = 0.0
            self._interval_s = DEFAULT_INTERVAL_S
            self._t_enabled = None
            self._stages = {}
            self._child_busy = {}
            self._util = {}
            self._last_tick = None
            self._last_doc = None
            self._probes = {}
            self._registry = None
            self._synced = {}
        self._device = None
        with self._json_lock:
            self._json_path = None
        self._capacity = None

    # -- the span fold (tracer sink) ----------------------------------

    def on_span(self, doc: "dict[str, Any]") -> None:
        """Fold one finished span into its stage's SELF busy-seconds
        (duration minus already-folded children — children finish
        before their parent, so their durations are waiting in
        ``_child_busy`` when the parent arrives). A few dict ops +
        float adds under a lock — the whole per-span cost of an
        enabled profiler."""
        name = doc.get("name")
        if not self._enabled or name not in _STAGE_SET:
            return
        dur = doc.get("duration_s")
        if not isinstance(dur, (int, float)):
            return
        span_id = doc.get("span_id")
        parent_id = doc.get("parent_id")
        with self._lock:
            child = (self._child_busy.pop(span_id, 0.0)
                     if isinstance(span_id, str) else 0.0)
            if isinstance(parent_id, str):
                if len(self._child_busy) >= 4096:
                    # Orphaned accumulators (parent ended unfolded or
                    # was cancelled before its children): drop the
                    # oldest half rather than growing forever.
                    for key in list(self._child_busy)[:2048]:
                        del self._child_busy[key]
                self._child_busy[parent_id] = (
                    self._child_busy.get(parent_id, 0.0) + float(dur))
            acc = self._stages.get(name)  # type: ignore[arg-type]
            if acc is None:
                acc = self._stages[name] = [0.0, 0]  # type: ignore[index]
            acc[0] += max(0.0, float(dur) - child)
            acc[1] += 1

    def max_utilization(self) -> "float | None":
        """Peak per-stage utilization over the last completed tick
        window — the saturation signal FleetCapacity.headroom folds
        in. None before the first full window (or when disabled)."""
        with self._lock:
            if not self._enabled or not self._util:
                return None
            return max(self._util.values())

    # -- ticking ------------------------------------------------------

    def tick(self, io: bool = True) -> "dict[str, Any] | None":
        """One profiler tick: derive windowed utilization, sample the
        probes, sync metric families, store (and append, with
        --profile-json) the snapshot doc. Returns the doc, or None
        when disabled. Runs off the event loop (run_ticker hops it
        through a thread; the JSONL append and the /proc refresh are
        file I/O). ``io=False`` (profile_doc's on-demand path, which
        CAN run on the loop) skips both."""
        if not self._enabled:
            return None
        now = time.perf_counter()
        with self._lock:
            stages = {k: (v[0], int(v[1])) for k, v in self._stages.items()}
            last = self._last_tick
            self._last_tick = (now, {k: b for k, (b, _) in stages.items()})
            t_enabled = self._t_enabled if self._t_enabled is not None else now
            probes = list(self._probes.items())
            registry = self._registry
        # Unbias by the LIVE trace-sampling rate: at sample=s only a
        # fraction s of batches carry spans, so observed busy-seconds
        # underestimate true occupancy by that factor.
        rate = _trace.TRACER.sample_rate()
        util: "dict[str, float]" = {}
        if last is not None and now - last[0] > 0:
            dt = now - last[0]
            for k, (busy, _) in stages.items():
                util[k] = (busy - last[1].get(k, 0.0)) / dt / max(rate, 1e-9)
        with self._lock:
            self._util = util
        if registry is not None:
            self._sync_metrics(registry, stages, util)
            if io:
                refresh_process_metrics(registry)
        samples: "dict[str, float]" = {}
        for name, fn in probes:
            try:
                v = fn()
            except Exception:
                continue  # a broken probe must never kill the tick
            if isinstance(v, (int, float)):
                samples[name] = float(v)
        bottleneck = (max(util, key=lambda k: util[k])
                      if any(v > 0 for v in util.values()) else None)
        doc: "dict[str, Any]" = {
            "t": time.time(),
            "enabled": True,
            "sample": rate,
            "wall_s": round(now - t_enabled, 6),
            "stages": {
                k: {"busy_s": round(b, 6), "spans": n,
                    "utilization": round(util.get(k, 0.0), 6)}
                for k, (b, n) in sorted(stages.items())},
            "samples": samples,
            "bottleneck": bottleneck,
        }
        cap = self._capacity
        if cap is not None:
            doc["capacity"] = cap.doc()
        if self._device is not None:
            doc["device"] = self._device()
        with self._lock:
            self._last_doc = doc
        if io:
            with self._json_lock:
                path = self._json_path
                if path is not None:
                    try:
                        with open(path, "a", encoding="utf-8") as f:
                            f.write(json.dumps(doc) + "\n")
                    except OSError:
                        pass  # best-effort; the pipeline owns the run
        return doc

    def _sync_metrics(self, registry: "Registry",
                      stages: "dict[str, tuple[float, int]]",
                      util: "dict[str, float]") -> None:
        busy = registry.family("klogs_profile_stage_busy_seconds_total")
        spans = registry.family("klogs_profile_stage_spans_total")
        gauge = registry.family("klogs_profile_stage_utilization")
        with self._lock:
            synced = dict(self._synced)
            self._synced = {k: (b, n) for k, (b, n) in stages.items()}
        for k, (b, n) in stages.items():
            last_b, last_n = synced.get(k, (0.0, 0))
            if b > last_b:
                busy.labels(stage=k).inc(b - last_b)
            if n > last_n:
                spans.labels(stage=k).inc(n - last_n)
        for k, u in util.items():
            gauge.labels(stage=k).set(u)

    def profile_doc(self) -> "dict[str, Any]":
        """What GET /profile serves: the last ticked snapshot verbatim
        (so the endpoint and the --profile-json stream can never
        disagree — the /traces parity discipline), computing one on
        demand only when no tick has run yet."""
        with self._lock:
            doc = self._last_doc
            enabled = self._enabled
        if doc is not None:
            return doc
        if not enabled:
            doc = {"enabled": False, "stages": {}, "samples": {},
                   "bottleneck": None}
            if self._device is not None:
                doc["device"] = self._device()
            return doc
        # On-demand (no tick has run yet): this path serves the HTTP
        # handler ON the event loop — no JSONL append, no /proc reads.
        return self.tick(io=False) or {"enabled": False}

    async def run_ticker(self, stop: "asyncio.Event",
                         interval_s: "float | None" = None) -> None:
        """Periodic tick driver (a background task on the collector or
        filterd loop). Stop-aware wait (the blessed poller idiom); one
        final tick at teardown so the JSONL stream always ends with
        the complete picture. The tick itself (probe sampling + file
        append) hops through a worker thread."""
        import asyncio

        # The env interval was validated (loudly) at enable time.
        period = (interval_s if interval_s is not None
                  else self._interval_s)
        while not stop.is_set():
            try:
                await asyncio.wait_for(stop.wait(), timeout=period)
                break
            except asyncio.TimeoutError:
                pass
            await asyncio.to_thread(self.tick)
        await asyncio.to_thread(self.tick)


class FleetCapacity:
    """Offered vs admitted lines + the headroom estimate a filterd
    advertises through Hello (and exports as klogs_fleet_* when a
    registry is bound).

    - *offered*: lines that entered a match RPC (before tenancy
      admission) — the demand signal.
    - *admitted*: lines that produced verdicts (past quota shed and
      the fair gate) — the served signal. offered - admitted over a
      window is the shed pressure an autoscaler should add capacity
      for.
    - *headroom*: in [0, 1], by signal trust (see ``headroom()``):
      1 - admitted_rate / envelope when the operator calibrated one
      (KLOGS_FLEET_CAPACITY_LPS), else 1 - peak stage utilization
      from the live profiler, else None (profiler off and no
      envelope) — an advertised guess would be worse than silence.
    """

    def __init__(self, registry: "Registry | None" = None,
                 envelope_lps: "float | None" = None,
                 profiler: "PipelineProfiler | None" = None) -> None:
        self._lock = threading.Lock()
        self._offered = 0
        self._admitted = 0
        # Baseline sample at construction: the first rate read measures
        # against process start, not against its own first call.
        self._hist: "deque[tuple[float, int, int]]" = deque(
            [(time.monotonic(), 0, 0)])
        self._envelope = envelope_lps
        self._profiler = profiler
        self._m_offered: Any = None
        self._m_admitted: Any = None
        self._m_headroom: Any = None
        if registry is not None:
            self._m_offered = registry.family(
                "klogs_fleet_offered_lines_total")
            self._m_admitted = registry.family(
                "klogs_fleet_admitted_lines_total")
            self._m_headroom = registry.family("klogs_fleet_headroom")

    # -- accounting ---------------------------------------------------

    def note_offered(self, n: int) -> None:
        if n <= 0:
            return
        with self._lock:
            self._offered += n
        if self._m_offered is not None:
            self._m_offered.inc(n)

    def note_admitted(self, n: int) -> None:
        if n <= 0:
            return
        with self._lock:
            self._admitted += n
        if self._m_admitted is not None:
            self._m_admitted.inc(n)

    @property
    def offered(self) -> int:
        with self._lock:
            return self._offered

    @property
    def admitted(self) -> int:
        with self._lock:
            return self._admitted

    def _roll(self, now: float) -> None:
        with self._lock:
            if (not self._hist
                    or now - self._hist[-1][0] >= _CAPACITY_SAMPLE_S):
                self._hist.append((now, self._offered, self._admitted))
            while (len(self._hist) > 1
                   and now - self._hist[0][0] > _CAPACITY_WINDOW_S):
                self._hist.popleft()

    def rates(self) -> "tuple[float | None, float | None]":
        """(offered lines/s, admitted lines/s) over the rolling window:
        LIVE totals against the oldest retained sample, so the rate is
        current at read time (a Hello between history samples must not
        advertise a stale rate). (None, None) until a baseline sample
        has aged past the minimum spacing."""
        now = time.monotonic()
        self._roll(now)
        with self._lock:
            if not self._hist:
                return None, None
            t0, off0, adm0 = self._hist[0]
            off1, adm1 = self._offered, self._admitted
        dt = now - t0
        if dt < _CAPACITY_SAMPLE_S / 2:
            return None, None
        return (off1 - off0) / dt, (adm1 - adm0) / dt

    # -- the signal ---------------------------------------------------

    def envelope_lps(self) -> "float | None":
        """The rate envelope: KLOGS_FLEET_CAPACITY_LPS when set (the
        deployment's own calibration), else the constructor's, else
        None. No rate is ever assumed from a record of another run."""
        from klogs_tpu.utils.env import is_set, positive_float

        if is_set("KLOGS_FLEET_CAPACITY_LPS"):
            return positive_float("KLOGS_FLEET_CAPACITY_LPS", 0.0)
        return self._envelope

    def headroom(self) -> "float | None":
        """1 - saturation, clamped to [0, 1], by signal trust:

        1. An explicit envelope (KLOGS_FLEET_CAPACITY_LPS, or one
           passed to the constructor): 1 - admitted_rate / envelope.
           Concurrency-free, directly HPA-consumable, and the
           operator calibrated it for THIS deployment.
        2. Else the profiler's peak stage utilization, clamped at 1
           (utilization is concurrency-inclusive: 16 in-flight RPCs
           legitimately read >1, which means 'saturated', not '16x').
        3. None when neither exists — an advertised guess would be
           worse than silence."""
        cap = self.envelope_lps()
        if cap:
            # Before the rolling window has aged (process just
            # started) the observed rate is ~0 by definition — a
            # fresh server advertises full rate-headroom.
            _, admitted_lps = self.rates()
            return max(0.0, min(1.0, 1.0 - (admitted_lps or 0.0) / cap))
        prof = self._profiler if self._profiler is not None else PROFILER
        util = prof.max_utilization()
        if util is not None:
            return max(0.0, 1.0 - min(1.0, util))
        return None

    def doc(self) -> "dict[str, Any]":
        """The capacity block Hello (and /profile) carries."""
        offered_lps, admitted_lps = self.rates()
        head = self.headroom()
        if self._m_headroom is not None and head is not None:
            self._m_headroom.set(head)
        with self._lock:
            offered, admitted = self._offered, self._admitted
        return {
            "offered_lines": offered,
            "admitted_lines": admitted,
            "offered_lps": (round(offered_lps, 1)
                            if offered_lps is not None else None),
            "admitted_lps": (round(admitted_lps, 1)
                             if admitted_lps is not None else None),
            "headroom": head,
        }


# Process-global profiler: what --profile-json, the /profile endpoint,
# and the pipeline layers' probes use by default (one pipeline story
# per process, mirroring obs.trace.TRACER).
PROFILER = PipelineProfiler()
