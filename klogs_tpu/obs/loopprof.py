"""The collector's event-loop thread, accounted without timing its path.

The loop reads every socket, frames every line, flushes every sink and
resumes every device fetch, so it sets the pace of a collector. What it
spends its time on is read here with no timing call on its own path:

- **Stage samples.** A native thread that never takes the GIL
  (``native/_loopsampler.c``) wakes ``SAMPLE_HZ`` times a second (a
  prime rate, so it does not beat with the 25 ms deadline flusher or
  the 5 ms coalesce timer) and queues a call for the loop thread. The
  interpreter runs it at the loop's next bytecode boundary, with the
  frame the loop was executing, and it counts the ticks since the last
  call for the stage of the innermost frame that ``TABLE`` names,
  ``other`` where none does (``klogs_loop_samples_total{stage}``).
  Ticks that fall while the loop is in a system call, a long C call
  or waiting for the GIL are counted when it comes back, for the
  function that made the call. No signal is sent: no system call is
  interrupted.
- **Thread CPU.** Once a second, and on every refresh of the process
  metrics (each ``/metrics`` scrape and the ``--stats-json`` dump), the
  kernel's own accounting: the loop thread's user and system time from
  ``/proc/self/task/<tid>/stat`` and the whole process's from
  ``getrusage`` (``klogs_loop_cpu_seconds_total{mode}``,
  ``klogs_process_cpu_seconds_total{mode}``).

The share of samples outside ``poll``, less the loop thread's CPU over
the same wall time, is the time the loop was ready to run but off the
CPU: waiting for the GIL or preempted. A select that does not block
(callbacks or timers are already due) is the loop's own bookkeeping
and samples as ``other``; a loop that waits for the GIL on its way out
of a blocking select still samples as ``poll``.

Why not a Python sampler thread reading ``sys._current_frames()``: it
must take the GIL first, so it reads the loop where the loop next lets
go of the GIL (a socket read, a large numpy copy, the selector), not
where it spends its time. On a CPU run of the fake cluster such a
sampler put 66 of 69 samples on one numpy copy inside
``FramedBatcher.take`` that took about 1% of the run.

Stage samples need the native module and a loop on the main thread
(only it runs the queued calls); elsewhere only the CPU counters move.
"""

from __future__ import annotations

import atexit
import dis
import os
import resource
import sys
import threading
from types import CodeType, FrameType
from typing import TYPE_CHECKING, Any

if TYPE_CHECKING:
    from klogs_tpu.obs.metrics import Registry

SAMPLE_HZ = 97
CPU_READ_S = 1.0

STAGES = ("read", "frame", "flush", "dispatch", "resume", "flusher",
          "poll", "other")

# (module, qualified name, stage) or, for a function whose work splits
# at one await, (module, qualified name, stage before it, (the callable
# awaited, stage after it)). A sample takes the stage of the innermost
# frame listed here, so a listed caller covers every unlisted callee
# (the JAX frames below a dispatch, the trace spans around a read).
# Functions written in C have no frame: the native framer and
# join_kept_framed count for the Python function that calls them.
TABLE: tuple[tuple[Any, ...], ...] = (
    ("klogs_tpu.sources.socket", "SocketStream.__anext__", "read"),
    ("asyncio.streams", "StreamReader.read", "read"),
    ("asyncio.streams", "StreamReader.feed_data", "read"),
    ("asyncio.streams", "StreamReaderProtocol.data_received", "read"),
    ("asyncio.selector_events",
     "_SelectorSocketTransport._read_ready__data_received", "read"),
    ("asyncio.selector_events",
     "_SelectorSocketTransport._read_ready__get_buffer", "read"),
    ("klogs_tpu.filters.framer", "LineFramer.feed", "frame"),
    ("klogs_tpu.filters.framer", "LineFramer.flush", "frame"),
    ("klogs_tpu.filters.framer", "FramedBatcher.feed", "frame"),
    ("klogs_tpu.filters.framer", "FramedBatcher.take", "frame"),
    ("klogs_tpu.filters.base", "frame_lines", "frame"),
    ("klogs_tpu.filters.sink", "FilteredSink.write", "frame"),
    ("klogs_tpu.filters.sink", "FilteredSink._flush_pending", "flush"),
    ("klogs_tpu.filters.sink", "FilteredSink._flush_pending_locked",
     "flush"),
    ("klogs_tpu.filters.sink", "FilteredSink._flush_framed", "flush"),
    ("klogs_tpu.runtime.sink", "FileSink.write", "flush"),
    ("klogs_tpu.runtime.sink", "FileSink.flush", "flush"),
    ("klogs_tpu.runtime.stdout", "_ConsoleSink.write", "flush"),
    ("klogs_tpu.runtime.stdout", "_ConsoleSink.flush", "flush"),
    ("klogs_tpu.runtime.stdout", "TeeSink.write", "flush"),
    ("klogs_tpu.runtime.stdout", "TeeSink.flush", "flush"),
    ("klogs_tpu.filters.async_service", "AsyncFilterService.match",
     "dispatch"),
    ("klogs_tpu.filters.async_service", "AsyncFilterService.match_framed",
     "dispatch"),
    ("klogs_tpu.filters.async_service", "AsyncFilterService._enqueue",
     "dispatch"),
    ("klogs_tpu.filters.async_service", "AsyncFilterService._kick",
     "dispatch"),
    ("klogs_tpu.filters.async_service", "AsyncFilterService._run_group",
     "dispatch", ("run_in_executor", "resume")),
    ("klogs_tpu.filters.tpu", "NFAEngineFilter.dispatch_framed",
     "dispatch"),
    ("klogs_tpu.filters.sink", "FilterPipeline.run_deadline_flusher",
     "flusher"),
    ("klogs_tpu.filters.sink", "FilteredSink.flush_if_stale", "flusher"),
    ("selectors", "DefaultSelector.select", "poll"),
)

# code object -> (stage, bytecode offset of the split await or -1,
# stage after it)
Resolved = dict[CodeType, tuple[str, int, str]]


def _code_of(module: str, qualname: str) -> CodeType:
    obj: Any = sys.modules[module]
    for part in qualname.split("."):
        obj = getattr(obj, part)
    return obj.__code__  # type: ignore[no-any-return]


def _await_offset(code: CodeType, callee: str) -> int:
    """Bytecode offset at which ``code`` suspends awaiting its first
    call of ``callee``: a frame past it has resumed from that await."""
    seen = False
    for ins in dis.get_instructions(code):
        if ins.argval == callee and ins.opname.startswith("LOAD_"):
            seen = True
        elif seen and ins.opname == "YIELD_VALUE":
            return ins.offset
    raise LookupError(f"{code.co_qualname} awaits no {callee}")


def resolve(table: "tuple[tuple[Any, ...], ...]" = TABLE,
            ) -> "tuple[Resolved, list[tuple[Any, ...]]]":
    """(resolved entries, entries not resolved). An entry whose module
    is not imported yet stays unresolved: none of its frames can be on
    a stack."""
    out: Resolved = {}
    missing: list[tuple[Any, ...]] = []
    for entry in table:
        module, qualname, stage = entry[:3]
        try:
            code = _code_of(module, qualname)
            split, after = -1, stage
            if len(entry) > 3:
                split, after = _await_offset(code, entry[3][0]), entry[3][1]
        except (KeyError, AttributeError, LookupError):
            missing.append(entry)
            continue
        out[code] = (stage, split, after)
    return out, missing


def stage_of(frame: "FrameType | None", codes: Resolved) -> str:
    """The stage of the innermost listed frame of a stack."""
    while frame is not None:
        hit = codes.get(frame.f_code)
        if hit is not None:
            stage, split, after = hit
            if stage == "poll":
                # A select with timeout 0 does not wait: work is due.
                return ("other" if frame.f_locals.get("timeout") == 0
                        else "poll")
            return after if 0 <= split < frame.f_lasti else stage
        frame = frame.f_back
    return "other"


def _thread_cpu_s(tid: int) -> "tuple[float, float] | None":
    """(user, system) CPU seconds of one thread of this process, from
    the kernel's accounting; None where /proc has no such thread."""
    try:
        with open(f"/proc/self/task/{tid}/stat", "rb") as f:
            stat = f.read()
        # Fields after the parenthesized comm: utime and stime are
        # fields 14 and 15 (1-based), indexes 11 and 12 after it.
        after = stat.rsplit(b")", 1)[1].split()
        hz = os.sysconf("SC_CLK_TCK")
        return int(after[11]) / hz, int(after[12]) / hz
    except (OSError, ValueError, IndexError):
        return None


def _process_cpu_s() -> "tuple[float, float]":
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime, ru.ru_stime


class LoopProfiler:
    """Accounts one event-loop thread into ``registry``. ``start`` must
    be called on that thread; ``stop`` ends the accounting."""

    def __init__(self, registry: "Registry") -> None:
        self.registry = registry
        samples = registry.family("klogs_loop_samples_total")
        self._samples = {s: samples.labels(stage=s) for s in STAGES}
        loop_cpu = registry.family("klogs_loop_cpu_seconds_total")
        proc_cpu = registry.family("klogs_process_cpu_seconds_total")
        self._cpu = {(k, m): fam.labels(mode=m)
                     for k, fam in (("loop", loop_cpu),
                                    ("process", proc_cpu))
                     for m in ("user", "sys")}
        # Ticks by stage, written only by the sampler's call on the loop
        # thread, which must take no lock: it runs inside whatever the
        # loop was doing. sync() carries them into the counters.
        self.ticks = dict.fromkeys(STAGES, 0)
        self._synced = dict.fromkeys(STAGES, 0)
        # Last kernel readings, so the CPU counters advance by what the
        # kernel added since (from 0: they carry its totals). The
        # accounting thread and a scrape's refresh both sync.
        self._sync_lock = threading.Lock()
        self._last: dict[tuple[str, str], float] = {}
        self._codes: Resolved = {}
        self._unresolved = TABLE
        self._tid = 0
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None

    def start(self) -> "LoopProfiler":
        global _SAMPLED
        self._tid = threading.get_native_id()
        self._resolve()
        self.sync()
        if _SAMPLED is None and _start_sampler():
            _SAMPLED = self
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="klogs-loopprof")
        self._thread.start()
        with _ACTIVE_LOCK:
            _ACTIVE.append(self)
        return self

    def stop(self) -> None:
        global _SAMPLED
        with _ACTIVE_LOCK:
            if self in _ACTIVE:
                _ACTIVE.remove(self)
        if _SAMPLED is self:
            from klogs_tpu import native

            native.loopsampler.stop()
            _SAMPLED = None
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        self.sync()

    def count(self, frame: "FrameType | None", n: int) -> None:
        """``n`` ticks for the stage of ``frame``'s stack."""
        try:
            stage = stage_of(frame, self._codes)
        except Exception:  # noqa: BLE001 — must never raise into the loop
            stage = "other"
        self.ticks[stage] += n

    def _resolve(self) -> None:
        if not self._unresolved:
            return
        codes, missing = resolve(self._unresolved)
        if codes:
            # Copy, then swap: the sampler's call reads it unlocked.
            self._codes = {**self._codes, **codes}
        self._unresolved = tuple(missing)

    def sync(self) -> None:
        """Advance the counters: ticks counted so far, and the kernel's
        current CPU totals."""
        now: dict[tuple[str, str], float] = {}
        loop = _thread_cpu_s(self._tid) if self._tid else None
        if loop is not None:
            now[("loop", "user")], now[("loop", "sys")] = loop
        now[("process", "user")], now[("process", "sys")] = _process_cpu_s()
        with self._sync_lock:
            for key, value in now.items():
                last = self._last.get(key, 0.0)
                if value > last:
                    self._cpu[key].inc(value - last)
                    self._last[key] = value
            for stage in STAGES:
                n = self.ticks[stage] - self._synced[stage]
                if n:
                    self._samples[stage].inc(n)
                    self._synced[stage] += n

    def _run(self) -> None:
        while not self._stop.wait(CPU_READ_S):
            self._resolve()
            self.sync()


# Process-wide, as the interpreter's main thread is: the profilers
# running, for refresh(), and the one whose loop the native sampler
# interrupts (one at a time).
_ACTIVE: list[LoopProfiler] = []
_ACTIVE_LOCK = threading.Lock()
_SAMPLED: "LoopProfiler | None" = None


def _on_tick(frame: "FrameType | None", n: int) -> None:
    prof = _SAMPLED
    if prof is not None:
        prof.count(frame, n)


def _start_sampler() -> bool:
    """Start the native sampler; False where it cannot run (no native
    module, or a loop that is not the main thread, the only one that
    runs the sampler's calls)."""
    from klogs_tpu import native

    if threading.current_thread() is not threading.main_thread():
        return False
    sampler = native.load_loopsampler()
    if sampler is None:
        return False
    try:
        sampler.start(_on_tick, SAMPLE_HZ)
    except (RuntimeError, ValueError, OSError):
        return False
    # No call may be queued while the interpreter finalizes.
    atexit.register(sampler.stop)
    return True


def refresh(registry: "Registry | None") -> None:
    """Bring the counters of the loops accounted into ``registry`` up to
    date (before a scrape or a dump)."""
    with _ACTIVE_LOCK:
        profs = [p for p in _ACTIVE if p.registry is registry]
    for p in profs:
        p.sync()
