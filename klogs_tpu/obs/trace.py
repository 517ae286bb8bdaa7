"""Distributed tracing + the degrade flight recorder.

Aggregate metrics (obs.metrics) say how OFTEN the pipeline hedged,
rerouted, or fell back; they cannot say where one specific batch went
and why. This module adds the per-batch story: a dependency-free span
core instrumenting one batch's full life — fanout read -> sink flush ->
shard routing (hedge/reroute/failover as events) -> RPC client/server
(context propagated in gRPC metadata) -> server coalescer -> device
frame/sweep/enqueue/fetch -> sink write — plus a flight recorder that
turns every degrade event into a self-contained JSON artifact.

Design rules (same budget discipline as obs.metrics):

- **Head-based sampling, off by default.** ``KLOGS_TRACE_SAMPLE`` is
  the fraction of traces recorded (0..1); the decision is made ONCE at
  the trace root and rides the context (and the wire), so a trace is
  always complete or absent. At 0 (default) ``span()`` is a float
  compare returning a no-op singleton — nothing on the framed hot path
  regresses.
- **Spans ride per-batch code, never per-line.** The busiest span site
  is one per fanout chunk / sink flush.
- **Task-safe context.** The current span lives in a ``contextvars``
  ContextVar: asyncio tasks inherit it at creation, so a hedge attempt
  task is automatically parented under the shard dispatch span.
  Executor threads do NOT inherit it — by convention the await site
  owns the span (``device.fetch`` wraps the ``run_in_executor`` await),
  and the span-discipline analysis pass (tools/analysis) keeps spans
  out of fire-and-forget tasks.
- **Bounded everything.** Attributes, events, the finished-span ring,
  and the recorder ring all have fixed caps; a runaway trace cannot
  grow process memory.

The device clock (``Tracer.device_clock``): while ``--profile`` captures
a device trace, every span named in ``CLOCK_STAGES`` (sampled or not)
and every sampled span also opens a ``jax.profiler.TraceAnnotation``
for its lifetime, so the capture holds the host stage beside the device
ops on one clock. An unsampled stage span is then a bare annotation
(``_ClockSpan``): no ids, no context, nothing recorded here.

The flight recorder (``FlightRecorder``) keeps a fixed ring of recent
finished spans. ``trigger(reason)`` — fired on breaker open,
``--on-filter-error`` degrade, sweep/prefilter fallback, and abort
escalation — arms a dump that is written when the CURRENT trace's root
span finishes, so the artifact contains the triggering batch's complete
hop sequence with per-stage durations (a dump at trigger time would cut
the story mid-batch).
"""

import contextvars
import json
import os
import random
import threading
import time
from collections import deque
from typing import TYPE_CHECKING, Any, Callable, Iterable

if TYPE_CHECKING:
    from klogs_tpu.obs.metrics import Registry

# gRPC metadata key carrying the W3C-style traceparent
# (00-<32hex trace>-<16hex span>-<2hex flags>); lowercase as gRPC
# requires. service/transport.py re-exports it as the wire contract.
TRACEPARENT_KEY = "klogs-traceparent"

# Bounds: per-span attribute count / value length, events per span,
# finished-span ring (feeds /traces and the recorder).
MAX_ATTRS = 32
MAX_ATTR_LEN = 256
MAX_EVENTS = 64
DEFAULT_RING = 4096

_SENTINEL = object()  # "parent not given" marker for start_span

# The per-group host stages annotated on the device trace's clock while
# ``Tracer.device_clock`` is on. Each occurs once per coalesced group,
# width bucket or flusher pass (hundreds a second), never per chunk.
CLOCK_STAGES: "tuple[str, ...]" = (
    "coalescer.dispatch",
    "device.frame",
    "device.enqueue",
    "device.fetch",
    "device.sweep",
    "mesh.dispatch",
    "sink.flusher",
)
_CLOCK_SET = frozenset(CLOCK_STAGES)

# Trace/span ids come from a private PRNG (seeded from the OS) so tests
# that seed the global `random` module cannot collide trace identities.
_IDS = random.Random()


def _sample_from_env() -> float:
    """KLOGS_TRACE_SAMPLE: fraction of traces to record (0..1).
    Malformed values raise naming the variable — a typo'd knob
    silently tracing nothing (or everything) is undebuggable."""
    from klogs_tpu.utils.env import read as env_read

    raw = env_read("KLOGS_TRACE_SAMPLE")
    if raw is None:
        return 0.0
    try:
        val = float(raw)
    except ValueError:
        raise ValueError(
            f"KLOGS_TRACE_SAMPLE={raw!r}: expected a number in [0, 1]"
        ) from None
    if not 0.0 <= val <= 1.0:
        raise ValueError(
            f"KLOGS_TRACE_SAMPLE={raw!r}: expected a number in [0, 1]")
    return val


class SpanContext:
    """The propagatable identity of a span: what a child (local or
    across the gRPC hop) needs to parent itself. ``remote`` marks a
    context that crossed a process boundary (extracted from wire
    metadata): a span parented under one is this PROCESS's root of the
    trace — the flight recorder treats it as a story-completion point,
    since the true root lives in another process."""

    __slots__ = ("trace_id", "span_id", "sampled", "remote")

    def __init__(self, trace_id: int, span_id: int, sampled: bool,
                 remote: bool = False) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled
        self.remote = remote

    def traceparent(self) -> str:
        return (f"00-{self.trace_id:032x}-{self.span_id:016x}-"
                f"{'01' if self.sampled else '00'}")

    @classmethod
    def from_traceparent(cls, value: str) -> "SpanContext | None":
        parts = value.split("-")
        if len(parts) != 4:
            return None
        try:
            trace_id = int(parts[1], 16)
            span_id = int(parts[2], 16)
            flags = int(parts[3], 16)
        except ValueError:
            return None
        if len(parts[1]) != 32 or len(parts[2]) != 16:
            return None
        return cls(trace_id, span_id, bool(flags & 1))


def _clip(value: object) -> object:
    if isinstance(value, (int, float, bool)) or value is None:
        return value
    s = str(value)
    return s if len(s) <= MAX_ATTR_LEN else s[:MAX_ATTR_LEN] + "…"


class Span:
    """One timed operation. A context manager: ``with tracer.span(...)``
    is THE way to hold one open (the span-discipline analysis pass
    enforces it in the plumbing scope); ``__exit__`` records an escaping
    exception as status=error (CancelledError as status=cancelled — the
    hedge-loser signature) and reports to the tracer.

    Unsampled spans still enter the context (so the head decision
    propagates to children and across the wire) but record nothing."""

    __slots__ = ("_tracer", "name", "trace_id", "span_id", "parent_id",
                 "sampled", "local_root", "root_span_id", "start_unix",
                 "_t0", "duration_s", "status", "attrs", "events",
                 "_token", "_ended", "_ann")

    def __init__(self, tracer: "Tracer", name: str, trace_id: int,
                 span_id: int, parent_id: "int | None", sampled: bool,
                 attrs: "dict[str, object] | None" = None,
                 local_root: bool = False,
                 root_span_id: "int | None" = None) -> None:
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.sampled = sampled
        self.local_root = local_root or parent_id is None
        # The span whose end completes THIS process's part of the
        # story (the flight recorder waits for it): self when a local
        # root, else inherited down the local chain.
        self.root_span_id = (span_id if self.local_root
                             else (root_span_id if root_span_id is not None
                                   else parent_id))
        self.start_unix = time.time() if sampled else 0.0
        self._t0 = time.perf_counter()
        self.duration_s: "float | None" = None
        self.status = "ok"
        self.attrs: "dict[str, object]" = {}
        self.events: "list[dict[str, object]]" = []
        self._token: "contextvars.Token[object] | None" = None
        self._ended = False
        self._ann: Any = None
        if sampled and attrs:
            for k, v in attrs.items():
                self.set_attr(k, v)

    # -- recording ----------------------------------------------------

    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id, self.sampled)

    def set_attr(self, key: str, value: object) -> None:
        if self.sampled and len(self.attrs) < MAX_ATTRS:
            self.attrs[key] = _clip(value)

    def add_event(self, name: str, **attrs: object) -> None:
        if self.sampled and len(self.events) < MAX_EVENTS:
            ev: "dict[str, object]" = {
                "name": name, "t_s": time.perf_counter() - self._t0}
            for k, v in attrs.items():
                ev[k] = _clip(v)
            self.events.append(ev)

    def set_status(self, status: str) -> None:
        if self.sampled:
            self.status = status

    def end(self) -> None:
        """Finish the span and report it. Idempotent (the with-block and
        a manual finally may both call it)."""
        if self._ended:
            return
        self._ended = True
        self.duration_s = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self.sampled:
            self._tracer._finish(self)

    # -- context management -------------------------------------------

    def __enter__(self) -> "Span":
        self._token = _CURRENT.set(self)
        annotate = self._tracer._annotate
        if annotate is not None and (self.sampled
                                     or self.name in _CLOCK_SET):
            self._ann = annotate(self.name)
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type: "type[BaseException] | None",
                 exc: "BaseException | None", tb: object) -> None:
        if self._token is not None:
            _CURRENT.reset(self._token)
            self._token = None
        if exc is not None and self.sampled:
            import asyncio

            if isinstance(exc, asyncio.CancelledError):
                self.status = "cancelled"
            else:
                self.status = "error"
                self.set_attr("error", f"{type(exc).__name__}: {exc}")
        self.end()

    def to_dict(self) -> "dict[str, object]":
        return {
            "name": self.name,
            "trace_id": f"{self.trace_id:032x}",
            "span_id": f"{self.span_id:016x}",
            "parent_id": (None if self.parent_id is None
                          else f"{self.parent_id:016x}"),
            "local_root": self.local_root,
            "start_unix": self.start_unix,
            "duration_s": self.duration_s,
            "status": self.status,
            "attrs": dict(self.attrs),
            "events": list(self.events),
        }


class _NoopSpan:
    """The zero-cost span when tracing is off: every method is a no-op
    and the context var is never touched (nothing downstream can
    sample, because the rate is 0)."""

    __slots__ = ()
    sampled = False
    name = ""

    def context(self) -> None:
        return None

    def set_attr(self, key: str, value: object) -> None:
        pass

    def add_event(self, name: str, **attrs: object) -> None:
        pass

    def set_status(self, status: str) -> None:
        pass

    def end(self) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: object) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class _ClockSpan(_NoopSpan):
    """An unsampled ``CLOCK_STAGES`` span while the device clock is on:
    a no-op span that holds one profiler annotation for its lifetime.
    It never enters the context var, so it parents nothing."""

    __slots__ = ("_ann",)

    def __init__(self, annotation: Any) -> None:
        self._ann = annotation

    def end(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self) -> "_ClockSpan":
        self._ann.__enter__()
        return self

    def __exit__(self, *exc: object) -> None:
        self.end()


# The active span for the current task/thread. Module-level (contextvars
# must be created once); shared by every Tracer in the process — in
# practice one process runs one TRACER, and tests that build private
# tracers run their spans inside their own with-blocks.
_CURRENT: "contextvars.ContextVar[object]" = contextvars.ContextVar(
    "klogs_trace_current", default=None)


class Tracer:
    """Span factory + finished-span ring.

    ``TRACER`` below is the process-global instance every instrumented
    module uses (collector and filterd share one process-wide trace
    story each); private instances isolate tests. The sample rate comes
    from ``KLOGS_TRACE_SAMPLE`` unless ``configure()`` overrides it."""

    def __init__(self, sample: "float | None" = None,
                 ring: int = DEFAULT_RING) -> None:
        self._lock = threading.Lock()
        self._sample = sample
        self._ring: "deque[dict[str, object]]" = deque(maxlen=ring)
        self._sinks: "list[Callable[[dict[str, object]], None]]" = []
        self._json_lock = threading.Lock()
        self._json_path: "str | None" = None
        self._m_spans: Any = None
        # jax.profiler.TraceAnnotation while the device clock is on.
        self._annotate: Any = None

    # -- configuration ------------------------------------------------

    def device_clock(self, on: bool) -> None:
        """Annotate spans on the device trace's clock (module
        docstring). ``--profile`` turns it on right after
        ``jax.profiler.start_trace`` and off right before
        ``stop_trace``; JAX is imported only here."""
        if on:
            from jax.profiler import TraceAnnotation

            self._annotate = TraceAnnotation
        else:
            self._annotate = None

    def _clock_span(self, name: str) -> "_NoopSpan":
        """What a span that records nothing is while the clock is on."""
        if name not in _CLOCK_SET:
            return NOOP_SPAN
        return _ClockSpan(self._annotate(name))

    def _rate(self) -> float:
        if self._sample is None:
            self._sample = _sample_from_env()
        return self._sample

    @property
    def enabled(self) -> bool:
        return self._rate() > 0.0

    def configure(self, sample: "float | None" = None) -> None:
        """Override the sample rate (None = re-read the env on next
        use). ``--trace-json`` calls ``enable_default()`` instead so an
        explicit KLOGS_TRACE_SAMPLE still wins."""
        self._sample = sample

    def sample_rate(self) -> float:
        """The effective head-sampling rate (env-resolved) — what the
        profiler divides observed busy-seconds by to unbias stage
        utilization."""
        return self._rate()

    def ensure_sample(self, rate: float) -> None:
        """Raise the sampling rate to at least ``rate`` — the
        profiler's enablement path (profiling needs spans to fold) —
        UNLESS KLOGS_TRACE_SAMPLE explicitly pins one: an operator's
        explicit rate, including 0, always wins."""
        from klogs_tpu.utils.env import is_set

        if is_set("KLOGS_TRACE_SAMPLE"):
            return
        if rate > self._rate():
            self._sample = rate

    def enable_default(self) -> None:
        """Turn sampling fully on UNLESS KLOGS_TRACE_SAMPLE is set —
        the --trace-json ergonomics: asking for a trace file means you
        want traces, but an explicit rate (including 0) is respected."""
        from klogs_tpu.utils.env import is_set

        if not is_set("KLOGS_TRACE_SAMPLE"):
            self._sample = 1.0

    def bind_registry(self, registry: "Registry | None") -> None:
        self._m_spans = (registry.family("klogs_trace_spans_total")
                         if registry is not None else None)

    def reset(self, sample: "float | None" = None) -> None:
        """Test hook: drop every finished span, sink, and file sink,
        then set the rate (None = env)."""
        with self._lock:
            self._ring.clear()
            self._sinks = []
        with self._json_lock:
            self._json_path = None
        self._sample = sample
        self._m_spans = None
        self._annotate = None

    # -- span creation ------------------------------------------------

    def start_span(self, name: str, parent: object = _SENTINEL,
                   **attrs: object) -> "Span | _NoopSpan":
        """Create a span. ``parent`` defaults to the current span (the
        contextvar); pass an explicit ``SpanContext`` (e.g. extracted
        from gRPC metadata, or a coalesced group's carrying member) or
        ``None`` to force a new root. Returns the no-op singleton when
        nothing samples (a ``_ClockSpan`` for a ``CLOCK_STAGES`` name
        while the device clock is on) — callers never branch."""
        if parent is _SENTINEL:
            parent = _CURRENT.get()
        if parent is None:
            rate = self._rate()
            if rate <= 0.0:
                if self._annotate is None:
                    return NOOP_SPAN
                return self._clock_span(name)
            sampled = rate >= 1.0 or _IDS.random() < rate
            return Span(self, name, _IDS.getrandbits(128),
                        _IDS.getrandbits(64), None, sampled, attrs or None)
        root_id: "int | None" = None
        if isinstance(parent, Span):
            root_id = parent.root_span_id
            ctx: "SpanContext | None" = parent.context()
        elif isinstance(parent, _NoopSpan):
            ctx = None
        else:
            ctx = parent
        if ctx is None:
            if self._annotate is None:
                return NOOP_SPAN
            return self._clock_span(name)
        assert isinstance(ctx, SpanContext)
        return Span(self, name, ctx.trace_id, _IDS.getrandbits(64),
                    ctx.span_id, ctx.sampled, attrs or None,
                    local_root=ctx.remote, root_span_id=root_id)

    # The idiomatic entry (`with tracer.span("name"):`).
    span = start_span

    def current_span(self) -> "Span | None":
        cur = _CURRENT.get()
        return cur if isinstance(cur, Span) else None

    def current_context(self) -> "SpanContext | None":
        cur = _CURRENT.get()
        return cur.context() if isinstance(cur, Span) else None

    def event(self, name: str, **attrs: object) -> None:
        """Add an event to the current span, if one is recording — the
        convenience for deep helpers (routing demotions, degrades) that
        should annotate whatever batch is in flight."""
        cur = _CURRENT.get()
        if isinstance(cur, Span):
            cur.add_event(name, **attrs)

    def exemplar(self) -> "dict[str, str] | None":
        """Exemplar labels ({trace_id, span_id}) for the current
        sampled span, linking a histogram observation to its trace in
        the Prometheus exposition (OpenMetrics exemplar syntax)."""
        cur = _CURRENT.get()
        if isinstance(cur, Span) and cur.sampled:
            return {"trace_id": f"{cur.trace_id:032x}",
                    "span_id": f"{cur.span_id:016x}"}
        return None

    # -- wire propagation ---------------------------------------------

    def inject(self) -> "tuple[tuple[str, str], ...]":
        """gRPC metadata entries carrying the current span context
        (empty when nothing is recording)."""
        cur = _CURRENT.get()
        if isinstance(cur, Span) and cur.sampled:
            return ((TRACEPARENT_KEY, cur.context().traceparent()),)
        return ()

    def extract(self, metadata: "Iterable[tuple[str, str]] | None"
                ) -> "SpanContext | None":
        """Parse a traceparent out of gRPC invocation metadata; None
        when absent/malformed (the RPC then roots its own trace under
        local sampling)."""
        if not metadata:
            return None
        for key, value in metadata:
            if key == TRACEPARENT_KEY and isinstance(value, str):
                ctx = SpanContext.from_traceparent(value)
                if ctx is not None:
                    # Crossed a process boundary: spans parented under
                    # this are THIS process's roots of the trace.
                    ctx.remote = True
                return ctx
        return None

    # -- finished spans -----------------------------------------------

    def _finish(self, span: Span) -> None:
        doc = span.to_dict()
        with self._lock:
            self._ring.append(doc)
            sinks = list(self._sinks)
        if self._m_spans is not None:
            self._m_spans.inc()
        path = self._json_path
        if path is not None:
            self._write_json(path, doc)
        for sink in sinks:
            try:
                sink(doc)
            except Exception:
                pass  # a broken sink must never take the pipeline down

    def _write_json(self, path: str, doc: "dict[str, object]") -> None:
        try:
            with self._json_lock:
                with open(path, "a", encoding="utf-8") as f:
                    f.write(json.dumps(doc) + "\n")
        except OSError:
            pass  # tracing is best-effort; the pipeline owns the run

    def add_sink(self, fn: "Callable[[dict[str, object]], None]") -> None:
        with self._lock:
            self._sinks.append(fn)

    def remove_sink(self, fn: "Callable[[dict[str, object]], None]"
                    ) -> None:
        with self._lock:
            if fn in self._sinks:
                self._sinks.remove(fn)

    def set_json_path(self, path: "str | None") -> None:
        """--trace-json PATH: append every finished span as one JSON
        line (JSONL; the file-sink twin of the /traces endpoint)."""
        with self._json_lock:
            self._json_path = path

    def finished_spans(self) -> "list[dict[str, object]]":
        with self._lock:
            return list(self._ring)

    def traces_doc(self) -> "dict[str, object]":
        """Finished spans grouped by trace for the /traces endpoint:
        {"traces": [{"trace_id", "spans": [...]}, ...]}, spans in start
        order, traces in first-seen order."""
        groups: "dict[str, list[dict[str, object]]]" = {}
        for doc in self.finished_spans():
            groups.setdefault(str(doc["trace_id"]), []).append(doc)
        traces = []
        for tid, spans in groups.items():
            spans.sort(key=lambda d: (d.get("start_unix") or 0.0))
            traces.append({"trace_id": tid, "spans": spans})
        return {"traces": traces}


class FlightRecorder:
    """Fixed ring of recent spans, dumped as one JSON artifact when a
    degrade event fires.

    Registered as a tracer sink; ``trigger(reason)`` arms a dump that
    is written when the next ROOT span finishes — so the artifact
    contains the triggering batch's complete hop sequence, not a story
    cut off mid-dispatch. Per-reason rate limiting keeps a flapping
    breaker from writing a dump per flap; ``flush()`` writes an armed
    dump immediately (pipeline teardown, tests)."""

    def __init__(self, capacity: int = 1024,
                 dir_path: "str | None" = None,
                 min_interval_s: float = 30.0) -> None:
        self._lock = threading.Lock()
        self._ring: "deque[dict[str, object]]" = deque(maxlen=capacity)
        self._dir = dir_path
        self._min_interval_s = min_interval_s
        self._last: "dict[str, float]" = {}
        self._pending: "list[dict[str, object]]" = []
        self._seq = 0
        self._writers: "list[threading.Thread]" = []
        self.dumps: "list[str]" = []
        self._m_dumps: Any = None

    def configure(self, dir_path: "str | None" = None,
                  min_interval_s: "float | None" = None) -> None:
        with self._lock:
            if dir_path is not None:
                self._dir = dir_path
            if min_interval_s is not None:
                self._min_interval_s = min_interval_s

    def bind_registry(self, registry: "Registry | None") -> None:
        self._m_dumps = (registry.family("klogs_flight_dumps_total")
                         if registry is not None else None)

    def reset(self) -> None:
        self.join_writes()
        with self._lock:
            self._ring.clear()
            self._pending = []
            self._last = {}
            self._writers = []
            self.dumps = []
        self._m_dumps = None

    def _dump_dir(self) -> str:
        if self._dir is not None:
            return self._dir
        from klogs_tpu.utils.env import read as env_read

        env = env_read("KLOGS_FLIGHT_DIR")
        if env:
            return env
        import tempfile

        return tempfile.gettempdir()

    # -- span stream (tracer sink) ------------------------------------

    def record(self, doc: "dict[str, object]") -> None:
        pending = None
        with self._lock:
            self._ring.append(doc)
            if self._pending:
                # Write when the span whose end completes the
                # TRIGGERING chain's story finishes: the exact root
                # span recorded at trigger time (true root on a
                # collector; the remote-parented rpc.server on a
                # filterd — a propagated trace has no local parentless
                # span there). A trigger armed outside any trace
                # flushes on the next local root. Matching the exact
                # span — not just the trace — matters when one process
                # hosts both ends (tests): the server-side local root
                # of the SAME trace ends first and must not cut the
                # collector-side story out of the artifact.
                wanted = {t.get("root_span_id") for t in self._pending}
                if ((None in wanted and doc.get("local_root"))
                        or doc.get("span_id") in wanted):
                    pending, self._pending = self._pending, []
        if pending is not None:
            self._write(pending)

    # -- triggers -----------------------------------------------------

    def trigger(self, reason: str, **attrs: object) -> None:
        """Arm a dump for ``reason`` (breaker-open, filter-degrade,
        sweep-fallback, abort-escalation). No-op when there is no story
        to dump (tracing off: no recording trace AND an empty ring) or
        inside the per-reason rate-limit window."""
        now = time.monotonic()
        # WHICH chain tripped the trigger: the dump waits for that
        # chain's local root span (the failed batch's full story in
        # this process), not whichever concurrent trace finishes
        # first.
        cur = TRACER.current_span()
        if cur is not None and not cur.sampled:
            cur = None
        with self._lock:
            if cur is None and not self._ring and not self._pending:
                return
            last = self._last.get(reason)
            if last is not None and now - last < self._min_interval_s:
                return
            self._last[reason] = now
            entry: "dict[str, object]" = {"reason": reason,
                                          "wall": time.time()}
            entry["trace_id"] = (f"{cur.trace_id:032x}"
                                 if cur is not None else None)
            entry["root_span_id"] = (
                f"{cur.root_span_id:016x}"
                if cur is not None and cur.root_span_id is not None
                else None)
            for k, v in attrs.items():
                entry[k] = _clip(v)
            self._pending.append(entry)
            # Bounded: a trigger whose trace never completes (process
            # shutting down, span dropped) must not accumulate for the
            # life of a daemon.
            if len(self._pending) > 32:
                del self._pending[0]

    def flush(self) -> "str | None":
        """Write an armed dump immediately (no root may ever end after
        teardown), waiting for the file to land. Returns the path, or
        None when nothing was armed."""
        with self._lock:
            pending, self._pending = self._pending, []
        if not pending:
            self.join_writes()
            return None
        return self._write(pending, wait=True)

    def join_writes(self, timeout_s: float = 5.0) -> None:
        """Wait for in-flight background dump writes (teardown/tests)."""
        with self._lock:
            writers = list(self._writers)
        for w in writers:
            w.join(timeout_s)

    def _write(self, triggers: "list[dict[str, object]]",
               wait: bool = False) -> "str | None":
        with self._lock:
            spans = list(self._ring)
            self._seq += 1
            seq = self._seq
        path = os.path.join(self._dump_dir(),
                            f"klogs-flight-{os.getpid()}-{seq}.json")
        # Serialization + disk I/O off the caller: record() runs on the
        # event loop (a span just ended there), and a full ring is
        # hundreds of KB — stalling the loop at the exact moment the
        # pipeline is degrading would worsen the incident being
        # recorded. ``wait`` (teardown/tests) joins before returning.
        worker = threading.Thread(
            target=self._write_blob, args=(triggers, spans, path),
            name="klogs-flight-dump", daemon=True)
        with self._lock:
            self._writers.append(worker)
            if len(self._writers) > 8:
                self._writers = [w for w in self._writers
                                 if w.is_alive()][-8:]
        worker.start()
        if wait:
            worker.join(5.0)
        return path

    def _write_blob(self, triggers: "list[dict[str, object]]",
                    spans: "list[dict[str, object]]", path: str) -> None:
        doc = {
            "reasons": triggers,
            "wall": time.time(),
            "pid": os.getpid(),
            "spans": spans,
        }
        try:
            with open(path, "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=1)
        except OSError as e:
            from klogs_tpu.ui import term

            term.warning("cannot write flight-recorder dump %s: %s",
                         path, e)
            return
        with self._lock:
            self.dumps.append(path)
        if self._m_dumps is not None:
            for t in triggers:
                self._m_dumps.labels(reason=t["reason"]).inc()
        from klogs_tpu.ui import term

        term.info("flight recorder dump (%s) written to %s",
                  ", ".join(str(t["reason"]) for t in triggers), path)


# Process-global tracer + recorder: what every instrumented module and
# the /traces endpoint use by default. The recorder rides the tracer's
# span stream as a sink.
TRACER = Tracer()
RECORDER = FlightRecorder()
TRACER.add_sink(RECORDER.record)


def flight_trigger(reason: str, **attrs: object) -> None:
    """Module-level trigger hook for the degrade call sites (breaker
    open, --on-filter-error degrade, sweep fallback, abort escalation).
    Cheap no-op when tracing is off."""
    RECORDER.trigger(reason, **attrs)


def reset(sample: "float | None" = None) -> None:
    """Test hook: wipe the global tracer AND recorder, re-wire the
    recorder sink, set the sample rate (None = env-driven again)."""
    TRACER.reset(sample)
    RECORDER.reset()
    TRACER.add_sink(RECORDER.record)
