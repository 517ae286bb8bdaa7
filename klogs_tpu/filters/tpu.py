"""NFAEngineFilter — the ``--backend=tpu`` LogFilter.

Host-side half of the TPU path: frames incoming lines into fixed-width
``[batch, max_line_bytes]`` uint8 tensors (the LineBatcher role from
SURVEY.md §2), ships them to the JAX engine (klogs_tpu.ops.nfa), and
returns the per-line keep-mask that gates file writes — the stage the
north star inserts at the reference's write boundary
(/root/reference/cmd/root.go:359-374).

Static-shape discipline (XLA traces once per shape): lines are padded
into power-of-two length buckets so the jit cache stays tiny; lines
longer than ``chunk_bytes`` run through the carried-state chunk path
(klogs_tpu.ops.nfa.match_chunk) instead of forcing a giant pad width —
the long-context design from SURVEY.md §5.

Trailing-newline handling matches RegexFilter: trailing "\\n" bytes are
stripped before matching, so ``$`` sees the logical end of line.
"""

import threading

import numpy as np

from klogs_tpu.filters.base import LogFilter
from klogs_tpu.filters.compiler.glushkov import compile_patterns
from klogs_tpu.utils.env import read as env_read

# Smallest pad width; also the bucket floor. 128 matches the TPU lane.
MIN_BUCKET = 128
# Smallest batch-dimension bucket. Both axes are padded to power-of-two
# buckets so XLA traces O(log) distinct shapes, not one per flush size.
MIN_BATCH_BUCKET = 8


def _bucket_len(n: int, chunk_bytes: int) -> int:
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return min(b, chunk_bytes)


def _bucket_batch(n: int) -> int:
    b = MIN_BATCH_BUCKET
    while b < n:
        b *= 2
    return b


def classify_batch(batch: np.ndarray, lengths: np.ndarray, table: np.ndarray,
                   begin_c: int, end_c: int, pad_c: int) -> np.ndarray:
    """Vectorized host classification of an ALREADY-packed [B, L] u8
    batch into the [B, L+3] sentinel cls layout (see pack_classify) —
    exactly the first=True/final=True case of the chunk protocol, so
    the sentinel layout lives in one place (classify_chunk_host).
    Shared by the numpy pack_classify fallback and MeshEngine's
    batch->cls adapter."""
    return classify_chunk_host(batch, lengths, table, begin_c, end_c, pad_c,
                               first=True, final=True)


def classify_chunk_host(chunk: np.ndarray, rem: np.ndarray, table: np.ndarray,
                        begin_c: int, end_c: int, pad_c: int,
                        first: bool, final: bool) -> np.ndarray:
    """Host mirror of ops.nfa.classify_chunk (+ the final accept-latch
    column) for the carried-state long-line protocol: [B, L] u8 chunk +
    remaining-lengths -> [B, T] class ids. Same END-deferral semantics:
    END is emitted at chunk-local position ``rem`` when it falls inside
    this chunk's window (the final chunk gets one extra column so END
    can land at L), positions past END are PAD."""
    B, L = chunk.shape
    Lb = L + (1 if final else 0)
    T = Lb + (1 if first else 0) + (1 if final else 0)
    off = 1 if first else 0
    from klogs_tpu.native import hostops

    if (hostops is not None and hasattr(hostops, "classify_chunk")
            and table.dtype == np.int8 and chunk.dtype == np.uint8
            and chunk.flags.c_contiguous):
        buf = hostops.classify_chunk(
            chunk, B, L, rem.astype(np.int32).tobytes(), table.tobytes(),
            begin_c, end_c, pad_c, int(first), int(final))
        return np.frombuffer(buf, dtype=np.int8).reshape(B, T)
    cls = np.empty((B, T), dtype=table.dtype)
    if first:
        cls[:, 0] = begin_c
    if final:
        cls[:, off + L :] = pad_c  # extra END window col + latch col
    body = cls[:, off : off + L]
    # All-i8 operations (a nested where promotes to int64 and triples
    # the passes — measured 70 MB/s vs GB/s for this form).
    pos = np.arange(L, dtype=np.int32)[None, :]
    remc = rem.astype(np.int32)
    body[:] = table[chunk]
    body[pos >= remc[:, None]] = pad_c
    # END lands at chunk-local position rem when inside this chunk's
    # window (the final chunk's window includes position L).
    inside = (remc >= 0) & (remc < Lb)
    rows = np.nonzero(inside)[0]
    cls[rows, off + remc[rows]] = end_c
    return cls


def pack_classify(lines: list[bytes], width: int, table: np.ndarray,
                  begin_c: int, end_c: int, pad_c: int) -> np.ndarray:
    """[B] bytes -> [B', width+3] i8 class ids (B' batch-bucketed):
    col 0 BEGIN, cols 1..len table[byte], col len+1 END, rest PAD (the
    accept-latch column included). Fused pack + classification on the
    host — the device-side classify gather measured as ~85% of hot-path
    device time (BENCH_DEVICE.json "host_classify" probe), so the
    byte->class mapping happens here, in the native packer when built,
    else via vectorized numpy."""
    B = len(lines)
    rows = _bucket_batch(B)
    from klogs_tpu.native import hostops

    if hostops is not None and hasattr(hostops, "pack_classify"):
        buf, _lens = hostops.pack_classify(
            lines, width, rows, table.tobytes(), begin_c, end_c, pad_c)
        return np.frombuffer(buf, dtype=np.int8).reshape(rows, width + 3)
    batch, lengths = pack_lines(lines, width)
    return classify_batch(batch, lengths, table, begin_c, end_c, pad_c)


def pack_lines(lines: list[bytes], width: int) -> tuple[np.ndarray, np.ndarray]:
    """[B] bytes -> ([B', width] u8 zero-padded, [B'] i32 lengths) with
    B' = B rounded up to a batch bucket; pad rows are empty lines whose
    verdicts the caller slices off.

    Zero-padding bytes are ignored by the engine (positions >= length
    classify as pad_class), so the fill value is arbitrary. Uses the
    native packer (klogs_tpu.native) when available — the pure-Python
    per-line loop is the host-side bottleneck otherwise.
    """
    B = len(lines)
    rows = _bucket_batch(B)
    from klogs_tpu.native import hostops

    if hostops is not None:
        buf, lens = hostops.pack_lines(lines, width, rows)
        batch = np.frombuffer(buf, dtype=np.uint8).reshape(rows, width)
        return batch, np.frombuffer(lens, dtype=np.int32)
    batch = np.zeros((rows, width), dtype=np.uint8)
    lengths = np.zeros((rows,), dtype=np.int32)  # pad rows: empty lines
    for i, ln in enumerate(lines):
        lengths[i] = len(ln)
        batch[i, : len(ln)] = np.frombuffer(ln, dtype=np.uint8)
    return batch, lengths


KERNELS = ("pallas", "interpret", "jnp")


class DeviceUnavailable(RuntimeError):
    """--backend tpu found no TPU, and JAX_PLATFORMS did not ask for
    the CPU."""


def resolve_kernel(kernel: "str | None" = None) -> str:
    """The kernel the TPU engine runs: ``kernel``, else KLOGS_TPU_KERNEL,
    else ``auto`` — the Pallas kernel on an accelerator and the jnp
    path on the CPU. The CPU is only ever a deliberate choice: when JAX
    finds no accelerator and JAX_PLATFORMS does not name the CPU alone,
    this raises DeviceUnavailable instead of carrying on at jnp speed."""
    import jax

    from klogs_tpu.utils.env import jax_cpu_requested

    kernel = kernel or env_read("KLOGS_TPU_KERNEL", "auto")
    if kernel != "auto" and kernel not in KERNELS:
        raise ValueError(f"KLOGS_TPU_KERNEL={kernel!r}: expected auto, "
                         + ", ".join(KERNELS))
    platform = jax.default_backend()
    if platform == "cpu" and not jax_cpu_requested():
        raise DeviceUnavailable(
            "--backend tpu needs a TPU, but JAX found none (its devices: "
            f"{', '.join(str(d) for d in jax.devices())}); set "
            "JAX_PLATFORMS=cpu to run the engine on the CPU on purpose")
    if kernel == "auto":
        kernel = "jnp" if platform == "cpu" else "pallas"
    return kernel


def device_doc(kernel: str) -> dict:
    """The device block --stats-json, Hello and /profile carry: the
    device as JAX reports it, how many, the kernel in use, whether the
    native host packer loaded, and the compile clock once started."""
    import jax

    from klogs_tpu.native import hostops
    from klogs_tpu.utils.cache import COMPILES

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "count": jax.device_count(), "kernel": kernel,
            "native": hostops is not None, **COMPILES.doc()}


class NFAEngineFilter(LogFilter):
    """Batch-NFA filter on the JAX engine: the Pallas kernel on a TPU,
    the jnp path on a CPU run that asked for it (identical semantics,
    per conftest's hermetic setup)."""

    # Above this, a single line routes to the sequence-parallel scan
    # (ops/seqscan): the chunked vector path costs len/chunk_bytes
    # SEQUENTIAL device dispatches, which for one huge line is pure
    # latency; the transfer-matrix tree turns it into batched matmuls.
    SEQ_SCAN_BYTES = 128 * 1024

    def __init__(self, patterns: list[str], ignore_case: bool = False,
                 chunk_bytes: int = 4096, engine=None, kernel: str | None = None,
                 stats=None):
        from klogs_tpu.ops import nfa  # deferred: --backend=cpu must not need jax

        self._nfa = nfa
        self._patterns = list(patterns)
        self._ignore_case = ignore_case
        # The union automaton of all K patterns (_union) and its
        # augmented long-line form (_long_program): built on first use,
        # because at thousand-pattern K the union exceeds the position
        # cap while the grouped kernel, which short lines run, does not.
        self._prog = None
        self._dp = None
        self._dp_aug = None
        self._chunk_bytes = chunk_bytes
        self._engine = engine  # optional parallel engine (klogs_tpu.parallel)
        self._stats = stats  # optional FilterStats for engine visibility
        # Degrade flags and the jit-shape set are written by fetch-time
        # retry closures running in AsyncFilterService's executor
        # threads while the loop thread dispatches — mutations go under
        # this lock (declared in the lock-discipline table,
        # tools/analysis). Reads stay lock-free: a stale read of a
        # monotonic degrade flag only delays the fallback one batch.
        self._state_lock = threading.Lock()
        # Batch geometries already traced: a new (width, rows) pair is
        # one jit compile — surfaced as a compile-event counter so an
        # operator can see shape churn (each event is a latency cliff).
        self._shapes_seen: set[tuple[int, int]] = set()

        # Execution path for the hot op (resolve_kernel): the Pallas
        # kernel on a TPU, jnp on a deliberate CPU run; "interpret"
        # exercises the kernel code hermetically (tests).
        kernel = resolve_kernel(kernel)
        self._kernel = kernel
        if stats is not None:
            stats.device_doc = self.device_doc
        if kernel in ("pallas", "interpret"):
            from klogs_tpu.ops import pallas_nfa

            self._pallas = pallas_nfa
            # Full-line batches run the grouped kernel (patterns binned
            # into 128-state automata: MXU cost linear, not quadratic,
            # in total positions); the long-line chunk path uses the
            # single augmented union automaton (state carry across
            # chunks needs one uniform state space).
            self._dp_grouped, self._g_live, self._g_acc = nfa.compile_grouped(
                patterns, ignore_case=ignore_case
            )
            self._match_all = self._dp_grouped.match_all
            # Host-side classification table for the grouped hot path
            # (pack_classify). Class ids ride int8, so a pattern set
            # whose shared classifier exceeds 127 classes (hundreds of
            # byte-set-diverse patterns) falls back to device-side
            # classification rather than overflowing.
            if self._dp_grouped.n_classes <= 127:
                self._cls_table = np.asarray(
                    self._dp_grouped.byte_class).astype(np.int8)
            else:
                self._cls_table = None
            # Degrade memory for the DEFAULTED chain variant
            # (mask_block=4 on hardware): chain restructurings are
            # compile-fragile on unproven backends (mask_block=8/16
            # fail Mosaic on v5e), so a default-variant failure flips
            # this and the engine continues on the plain chain. An
            # env-forced variant stays loud.
            self._chain_fallback = False
            # Two-phase filter: a mandatory-pair candidate mask gates
            # which kernel tiles run (ops/pallas_nfa skip-tiles path).
            # Default OFF: the 2026-07-29 device A/B (BENCH_DEVICE.json)
            # measured the byte-LUT candidate mask at ~684k lines/s —
            # nearly the full NFA kernel's cost — so gating was a net
            # loss (413k gated vs 641k plain). KLOGS_TPU_PREFILTER=1
            # opts in; requires every pattern to yield clauses.
            self._pf_tables = None
            if env_read("KLOGS_TPU_PREFILTER", "0") == "1":
                from klogs_tpu.filters.compiler.prefilter import compile_prefilter
                from klogs_tpu.ops.prefilter import class_tables, device_tables

                pf = compile_prefilter(patterns, ignore_case=ignore_case)
                if pf.usable:
                    # Class-domain tables (MXU matmul mask over the
                    # kernel's cls array); byte-LUT fallback only if the
                    # classifier were ever non-uniform w.r.t. the LUTs.
                    self._pf_tables = (
                        class_tables(pf, self._dp_grouped.byte_class,
                                     self._dp_grouped.n_classes)
                        or device_tables(pf)
                    )
                else:
                    # One clause-less pattern disables gating for the
                    # whole set (its candidate mask would be all-True);
                    # say so instead of failing silently.
                    from klogs_tpu.ui import term

                    culprits = [p for p, n in zip(patterns,
                                                  pf.clause_counts or [])
                                if n == 0]
                    if culprits:
                        reason = ("prefilter disabled: no mandatory byte "
                                  "pairs for pattern(s) %s" %
                                  ", ".join(repr(p) for p in culprits[:4]))
                    else:
                        # Every pattern HAS clauses; the shared slot
                        # table filled up before some pattern got one.
                        reason = ("prefilter disabled: clause slot table "
                                  "exhausted (pattern set too diverse)")
                    term.info("%s", reason)
                    if self._stats is not None:
                        self._stats.pf_disabled_reason = reason
            # Thousand-pattern fused path: the device literal sweep
            # (ops/sweep.py) gates (tile, group) kernel grid cells with
            # the factor-index candidate mask, computed ON DEVICE in
            # the same dispatch (frame -> sweep -> gated match, no host
            # round-trip). Auto at the same K threshold that flips
            # best_host_filter to the indexed engine, and only on a
            # real accelerator — on the CPU backend the dense sweep is
            # gather-bound and loses to the host sweep (BENCH_SWEEP).
            self._sweep_tables = None
            if engine is None:
                self._init_sweep(patterns, ignore_case)
        else:
            # The jnp path runs every line on the union automaton.
            self._match_all = self._union().match_all
            self._sweep_tables = None
            from klogs_tpu.filters.cpu import device_sweep_env

            if engine is None and device_sweep_env() == "1":
                # The fused sweep only exists for the pallas/interpret
                # kernels; a forced knob silently doing nothing here
                # would be the exact unexplained-~10x the validation
                # exists to prevent.
                from klogs_tpu.ui import term

                term.info(
                    "KLOGS_TPU_SWEEP=1 ignored: the fused sweep needs "
                    "the pallas/interpret kernel (running %s)",
                    kernel)

    def _union(self):
        """The union automaton (and its packed form, self._dp)."""
        if self._prog is None:
            prog = compile_patterns(self._patterns,
                                    ignore_case=self._ignore_case)
            dp = self._nfa.pack_program(prog)
            with self._state_lock:
                self._prog, self._dp = prog, dp
        return self._prog

    def _long_program(self):
        """The augmented union program that lines over chunk_bytes run
        on (the chunk kernel and the seq-scan: state carried across
        chunks needs one state space), with its live/accept state ids
        and host classification table (None past int8 class ids)."""
        if self._dp_aug is None:
            import jax.numpy as jnp

            from klogs_tpu.filters.compiler.parser import RegexSyntaxError

            try:
                prog = self._union()
            except RegexSyntaxError as e:
                raise RegexSyntaxError(
                    f"a line over {self._chunk_bytes} bytes runs on the "
                    f"union automaton of all {len(self._patterns)} "
                    f"patterns, which this set cannot build: {e}") from e
            dp_aug = self._nfa.pack_program(self._nfa.augment(prog),
                                            dtype=jnp.int8)
            table = (np.asarray(dp_aug.byte_class).astype(np.int8)
                     if dp_aug.n_classes <= 127 else None)
            with self._state_lock:
                self._live, self._acc = prog.n_states, prog.n_states + 1
                self._aug_cls_table = table
                self._dp_aug = dp_aug
        return self._dp_aug

    def _init_sweep(self, patterns: list[str], ignore_case: bool) -> None:
        """Build the device sweep tables when the auto rule (or
        KLOGS_TPU_SWEEP=1) selects the fused path. Any build failure
        degrades LOUDLY to the plain kernel — same contract as the
        indexed-engine auto fallback in best_host_filter. The
        sweep-vs-prefilter precedence itself lives in ONE place shared
        with the mesh (cpu.device_gate_choice): the kernel accepts one
        gate only, an explicit prefilter opt-in beats the auto sweep,
        a forced sweep beats the prefilter — but the working prefilter
        is only discarded AFTER the tables actually build (a failed
        build must not leave the engine with neither gate)."""
        from klogs_tpu.filters.cpu import device_gate_choice
        from klogs_tpu.ui import term

        choice = device_gate_choice(
            len(patterns), have_prefilter=self._pf_tables is not None,
            interpret=self._kernel == "interpret")
        if choice != "sweep":
            return
        pg = self._dp_grouped.pattern_group
        if not pg:
            term.warning(
                "device sweep unavailable: grouped program carries no "
                "pattern_group map; running the plain kernel")
            return
        try:
            from klogs_tpu.filters.compiler.groups import analyze, plan_groups
            from klogs_tpu.filters.compiler.index import FactorIndex
            from klogs_tpu.ops.sweep import device_sweep_tables

            infos = analyze(patterns, ignore_case=ignore_case)
            index = FactorIndex(infos, plan_groups(infos))
            prog = index.sweep_program(
                group_of=np.asarray(pg, dtype=np.int32),
                n_groups=int(self._dp_grouped.follow.shape[0]))
            tables = device_sweep_tables(prog)
            if self._pf_tables is not None:
                from klogs_tpu.filters.cpu import note_sweep_supersedes

                note_sweep_supersedes()
            with self._state_lock:
                self._pf_tables = None
                self._sweep_tables = tables
        except Exception as e:
            # Auto/forced sweep failing to BUILD must not kill the
            # engine: the plain kernel is always correct — but say so,
            # a silent fallback at this K is an unexplained ~10x.
            term.warning(
                "device sweep build failed for this %d-pattern set "
                "(%s: %s); running the plain kernel",
                len(patterns), type(e).__name__, e)
            self._degraded("sweep_build")

    def device_doc(self) -> dict:
        """device_doc() for this engine's kernel, plus the mesh a
        multi-chip engine spans."""
        doc = device_doc(self._kernel)
        if self._engine is not None:
            doc["mesh"] = self._engine.placement()
        return doc

    def _degraded(self, path: str) -> None:
        """Count a kernel that gave way (FilterStats.record_degrade)."""
        if self._stats is not None:
            self._stats.record_degrade(path)

    def match_lines(self, lines: list[bytes]) -> list[bool]:
        return self.fetch(self.dispatch(lines))

    def _record_sub_batch(self, width: int, rows: int,
                          payload_bytes: int) -> None:
        """Engine-layer instrumentation per width-bucketed sub-batch:
        bucket-width distribution, padding waste, and first-seen shape
        (≈ jit compile) events. No-op without a stats object."""
        if self._stats is None:
            return
        self._stats.record_engine_batch(width, rows, payload_bytes)
        key = (width, rows)
        with self._state_lock:
            first_seen = key not in self._shapes_seen
            self._shapes_seen.add(key)
        if first_seen:
            self._stats.record_compile()

    def _cls_args(self):
        """(table, begin, end, pad) for the active host-classify path."""
        if self._engine is not None:
            eng = self._engine
            return (eng.cls_table, eng.begin_class, eng.end_class,
                    eng.pad_class)
        dpg = self._dp_grouped
        return (self._cls_table, dpg.begin_class, dpg.end_class,
                dpg.pad_class)

    def _use_cls(self) -> bool:
        if self._engine is not None:
            # A mesh engine running the fused sweep consumes raw bytes.
            return (getattr(self._engine, "cls_table", None) is not None
                    and not getattr(self._engine, "swept", False))
        if getattr(self, "_sweep_tables", None) is not None:
            # The fused sweep consumes raw bytes (the cls hot path
            # never ships them to the device); short lines take the
            # byte-consuming grouped entry instead.
            return False
        return (self._kernel in ("pallas", "interpret")
                and getattr(self, "_cls_table", None) is not None)

    def dispatch_framed(self, payload: bytes, offsets):
        """Framed-batch dispatch: no per-line PyBytes on the hot path.
        Rows are width-bucketed vectorized (numpy over the offsets), each
        bucket packs straight out of the contiguous payload — via the C
        framed packer on the cls hot path, via the shared
        ``pack_framed_rows`` ragged scatter on the byte path (active
        device sweep, which consumes raw bytes; deferred from PR 8 —
        this entry used to detour through split_frame's per-line
        PyBytes there). Long/huge rows (rare) bridge to the chunked /
        seq-scan paths via slicing."""
        import numpy as np

        from klogs_tpu.native import hostops

        offsets = np.ascontiguousarray(offsets, dtype=np.int32)
        n = len(offsets) - 1
        if n == 0:
            return (0, [])
        if self._match_all:
            return (n, None)
        if (hostops is not None
                and hasattr(hostops, "pack_classify_framed")
                and self._use_cls()):
            return self._dispatch_framed_cls(payload, offsets, n)
        if self._frames_bytes():
            return self._dispatch_framed_bytes(payload, offsets, n)
        from klogs_tpu.filters.base import split_frame

        return self.dispatch(split_frame(payload, offsets))

    def _frames_bytes(self) -> bool:
        """True when the active execution path consumes raw byte
        batches AND the framed byte packer should feed it directly:
        the fused device sweep (single-chip tables or a swept mesh
        engine) — its kernel takes bytes, so the cls packer cannot
        serve it and split_frame would cost n PyBytes per flush."""
        if getattr(self, "_sweep_tables", None) is not None:
            return True
        eng = self._engine
        return eng is not None and getattr(eng, "swept", False)

    def _framed_width_buckets(self, lens, short, n: int):
        """Power-of-two width bucket per row (jit-cache discipline,
        same buckets as the list path: every assignment clamps to
        chunk_bytes exactly like _bucket_len, or a non-power-of-two
        chunk_bytes would mint an EXTRA jit shape above it and pad
        every top-bucket row past the chunk width)."""
        import numpy as np

        chunk = self._chunk_bytes
        width_of = np.full(n, min(MIN_BUCKET, chunk), dtype=np.int64)
        w = MIN_BUCKET
        while w < chunk and bool((short & (lens > w)).any()):
            w *= 2
            width_of[lens > w // 2] = min(w, chunk)
        return width_of

    def _dispatch_framed_cls(self, payload: bytes, offsets, n: int):
        """The cls hot path: C framed packer -> class ids -> kernel.
        Raw lengths may include a trailing newline the C packer strips
        — the only effect is an occasional one-bucket-up pad, never a
        wrong width."""
        import numpy as np

        from klogs_tpu.native import hostops
        from klogs_tpu.obs import trace

        lens = np.diff(offsets)
        parts = []
        short = lens <= self._chunk_bytes
        if short.any():
            width_of = self._framed_width_buckets(lens, short, n)
            tab, bc, ec, pc = self._cls_args()
            tab_b = tab.tobytes()
            for w in np.unique(width_of[short]):
                sel = np.nonzero(short & (width_of == w))[0].astype(np.int32)
                rows = _bucket_batch(len(sel))
                with trace.TRACER.span("device.frame", width=int(w),
                                       rows=rows, path="cls"):
                    buf, _ = hostops.pack_classify_framed(
                        payload, offsets, n, sel.tobytes(), int(w),
                        rows, tab_b, bc, ec, pc)
                    cls = np.frombuffer(buf, dtype=np.int8).reshape(
                        -1, int(w) + 3)
                self._record_sub_batch(int(w), rows, int(lens[sel].sum()))
                # device.enqueue times the (asynchronous) dispatch
                # enqueue; the round-trip completion is device.fetch.
                with trace.TRACER.span("device.enqueue", width=int(w),
                                       rows=rows):
                    parts.append((sel, *self._match_cls_device(cls)))
        if not bool(short.all()):
            rest = np.nonzero(~short)[0]
            bodies = {int(i): payload[offsets[i]:offsets[i + 1]]
                      .rstrip(b"\n") for i in rest}
            self._dispatch_framed_rest(rest, bodies, parts)
        return (n, parts)

    def _dispatch_framed_bytes(self, payload: bytes, offsets, n: int):
        """The byte path (fused device sweep): width-bucketed [B, W] u8
        batches packed straight from the contiguous payload by the
        shared ``pack_framed_rows`` ragged scatter (filters/base), so
        the sweep path pays no per-line PyBytes either. Trailing
        newlines are peeled vectorized (rstrip parity with dispatch)."""
        import numpy as np

        from klogs_tpu.filters.base import pack_framed_rows
        from klogs_tpu.obs import trace

        starts = offsets[:-1].astype(np.int64)
        ends = offsets[1:].astype(np.int64).copy()
        if len(payload):
            arr = np.frombuffer(payload, dtype=np.uint8)
            while True:
                # Loop count = the longest trailing-newline run
                # (almost always 1); each pass is one vectorized scan.
                m = (ends > starts) & (arr[np.maximum(ends, 1) - 1] == 0x0A)
                if not bool(m.any()):
                    break
                ends[m] -= 1
        lens = ends - starts
        parts = []
        short = lens <= self._chunk_bytes
        if bool(short.any()):
            width_of = self._framed_width_buckets(lens, short, n)
            for w in np.unique(width_of[short]):
                sel = np.nonzero(short & (width_of == w))[0]
                rows = _bucket_batch(len(sel))
                with trace.TRACER.span("device.frame", width=int(w),
                                       rows=rows, path="bytes"):
                    batch, sub_lens = pack_framed_rows(
                        payload, offsets, int(w), rows=rows, sel=sel,
                        lens=lens[sel])
                lengths = np.zeros(rows, dtype=np.int32)
                lengths[:len(sel)] = sub_lens
                self._record_sub_batch(int(w), rows, int(lens[sel].sum()))
                with trace.TRACER.span("device.enqueue", width=int(w),
                                       rows=rows, swept=True):
                    parts.append((sel, *self._match_full(batch, lengths)))
        if not bool(short.all()):
            rest = np.nonzero(~short)[0]
            bodies = {int(i): payload[int(starts[i]):int(ends[i])]
                      for i in rest}
            self._dispatch_framed_rest(rest, bodies, parts)
        return (n, parts)

    def _dispatch_framed_rest(self, rest, bodies: dict, parts: list) -> None:
        """Long/huge rows shared by both framed paths: bridge to the
        carried-state chunk path / seq-scan via the (already stripped)
        body slices."""
        long_idx = [int(i) for i in rest
                    if len(bodies[int(i)]) <= self.SEQ_SCAN_BYTES]
        huge_idx = [int(i) for i in rest
                    if len(bodies[int(i)]) > self.SEQ_SCAN_BYTES]
        if long_idx:
            parts.append((long_idx, self._match_long(
                [bodies[i] for i in long_idx]), None, None))
        if huge_idx:
            parts.append((huge_idx, self._match_huge(
                [bodies[i] for i in huge_idx]), None, None))

    def dispatch(self, lines: list[bytes]):
        """Enqueue device work for a batch WITHOUT blocking on results
        (jax dispatch is asynchronous). Returns a handle for fetch()."""
        if not lines:
            return (0, [])
        if self._match_all:
            return (len(lines), None)  # all-match shortcut
        bodies = [ln.rstrip(b"\n") for ln in lines]  # parity with RegexFilter
        parts = []  # (index_list, device_mask_or_ndarray)

        short_idx = [i for i, b in enumerate(bodies) if len(b) <= self._chunk_bytes]
        long_idx = [i for i, b in enumerate(bodies)
                    if self._chunk_bytes < len(b) <= self.SEQ_SCAN_BYTES]
        huge_idx = [i for i, b in enumerate(bodies) if len(b) > self.SEQ_SCAN_BYTES]

        # Bucket short lines by padded width to bound jit-cache churn.
        buckets: dict[int, list[int]] = {}
        for i in short_idx:
            buckets.setdefault(
                _bucket_len(len(bodies[i]), self._chunk_bytes), []
            ).append(i)
        # MeshEngine exposes its global classifier when class ids fit
        # int8 — the multi-chip hot path takes cls directly; an active
        # device sweep forces the byte path instead (_use_cls).
        use_cls = self._use_cls()
        for width, idxs in buckets.items():
            sub = [bodies[i] for i in idxs]
            self._record_sub_batch(width, _bucket_batch(len(sub)),
                                   sum(len(b) for b in sub))
            if use_cls:
                parts.append((idxs, *self._match_cls_dispatch(sub, width)))
            else:
                batch, lengths = pack_lines(sub, width)
                parts.append((idxs, *self._match_full(batch, lengths)))
        if long_idx:
            parts.append(
                (long_idx, self._match_long([bodies[i] for i in long_idx]),
                 None, None))
        if huge_idx:
            parts.append(
                (huge_idx, self._match_huge([bodies[i] for i in huge_idx]),
                 None, None))
        return (len(lines), parts)

    def fetch(self, handle) -> list[bool]:
        return self._fetch_array(handle).tolist()

    def fetch_framed(self, handle) -> np.ndarray:
        return self._fetch_array(handle)

    def _fetch_array(self, handle) -> np.ndarray:
        """Block until the dispatched batch's verdicts are on host.

        An asynchronously-failing device batch (e.g. OOM at execution)
        surfaces HERE, not at dispatch — when the failing part carries a
        retry closure (the gated-kernel path), the failure degrades to
        the plain kernel instead of killing the streaming run."""
        n, parts = handle
        if parts is None:
            return np.ones(n, dtype=bool)
        out = np.zeros(n, dtype=bool)
        for idxs, mask, retry, pf in parts:
            try:
                vals = np.asarray(mask)
            except Exception as e:
                if retry is None:
                    raise
                from klogs_tpu.ui import term

                term.warning(
                    "device kernel failed at fetch (%s); "
                    "retrying on the plain path", str(e)[:120])
                self._degraded("fetch")
                vals = np.asarray(retry())
                pf = None
            out[idxs] = vals[: len(idxs)]
            if pf is not None and self._stats is not None:
                swept = isinstance(pf, tuple) and pf and pf[0] == "sweep"
                if swept:
                    pf = pf[1]
                n_cand, n_live, n_tiles = (int(np.asarray(x)) for x in pf)
                self._stats.record_prefilter(
                    len(idxs), min(n_cand, len(idxs)), n_tiles, n_live)
                if swept:
                    self._stats.record_sweep(
                        "device", len(idxs), min(n_cand, len(idxs)))
        return out

    def _match_cls_dispatch(self, bodies: list[bytes], width: int):
        """Hot path: host-side fused pack+classify, device kernel on
        class ids (no classify gather on device). Returns
        (device_mask, retry_closure_or_None, pf_stats_or_None)."""
        tab, bc, ec, pc = self._cls_args()
        cls = pack_classify(bodies, width, tab, bc, ec, pc)
        return self._match_cls_device(cls)

    def _match_cls_device(self, cls: np.ndarray):
        """Device half of the cls hot path — shared by the list and
        framed packers. Returns (device_mask, retry_or_None,
        pf_stats_or_None)."""
        if self._engine is not None:
            eng = self._engine
            retry = None
            if getattr(eng, "gated", False):
                # Degrade path for an opt-in gated kernel that fails
                # asynchronously: fetch() retries on the plain fn (whose
                # own sync chain-degrade then covers a chain fault).
                def retry(cls=cls):
                    self._degraded("gated_kernel")
                    eng.disable_prefilter()
                    return eng.match_cls(cls, plain=True)
            elif getattr(eng, "_chain_defaulted", False):
                # No gating, but the DEFAULTED chain variant can still
                # fail asynchronously at fetch: degrade and rerun.
                def retry(cls=cls):
                    self._degraded("chain")
                    eng.degrade_chain()
                    return eng.match_cls(cls)
            try:
                return eng.match_cls(cls), retry, None
            except Exception as e:
                if retry is None:
                    raise
                from klogs_tpu.ui import term

                term.warning(
                    "gated mesh kernel unavailable (%s); "
                    "falling back to plain NFA", str(e)[:120])
                return retry(), None, None
        dpg = self._dp_grouped
        interpret = self._kernel == "interpret"
        kw, chain_defaulted = self._chain_kwargs(interpret)

        def run_plain(run_kw):
            return self._pallas.match_cls_grouped_pallas(
                dpg, self._g_live, self._g_acc, cls,
                interpret=interpret, **run_kw)

        def chain_retry():
            # Rerun without the chain restructure ONLY if the chain was
            # a default — an env-forced variant is kept even here (the
            # operator asked to measure exactly that kernel; if it is
            # the async fault the rerun fails again and raises loudly).
            if chain_defaulted:
                with self._state_lock:
                    self._chain_fallback = True
                self._degraded("chain")
            return run_plain(dict(kw, mask_block=1) if chain_defaulted
                             else kw)

        def pf_retry():
            # Fetch-time failure of the PREFILTERED kernel: degrade one
            # cause at a time (ADVICE r4) — drop gating but KEEP the
            # defaulted chain variant (its +13% win is independent of
            # the prefilter); only degrade the chain if the plain rerun
            # also fails. np.asarray forces the rerun synchronous so a
            # second async fault surfaces here, not at the caller.
            with self._state_lock:
                self._pf_tables = None
            self._degraded("gated_kernel")
            try:
                return np.asarray(run_plain(kw))
            except Exception as e:
                if not chain_defaulted:
                    raise
                from klogs_tpu.ui import term

                term.warning(
                    "plain chain rerun also failed (%s); degrading to "
                    "mask_block=1", str(e)[:120])
                return chain_retry()

        if self._pf_tables is not None and len(self._pf_tables) == 4:
            want_stats = self._stats is not None
            try:
                res = self._pallas.match_cls_grouped_pallas(
                    dpg, self._g_live, self._g_acc, cls,
                    interpret=interpret,
                    prefilter_tables=self._pf_tables,
                    return_stats=want_stats, **kw)
                mask, pf = res if want_stats else (res, None)
                return mask, pf_retry, pf
            except Exception as e:
                # Gated-kernel compile trouble (Mosaic) must degrade to
                # the plain NFA, not kill the streaming run.
                from klogs_tpu.ui import term

                term.warning(
                    "prefiltered kernel unavailable (%s); "
                    "falling back to plain NFA", str(e)[:120])
                with self._state_lock:
                    self._pf_tables = None
                self._degraded("gated_kernel")
        try:
            mask = run_plain(kw)
        except Exception as e:
            if not chain_defaulted:
                raise
            from klogs_tpu.ui import term

            term.warning(
                "default mask_block=%d chain failed on this backend (%s); "
                "continuing on the plain chain",
                kw.get("mask_block"), str(e)[:120])
            return chain_retry(), None, None
        # A defaulted chain variant can also fail ASYNCHRONOUSLY (device
        # execution surfaces at fetch); hand fetch() the same retry.
        return mask, (chain_retry if chain_defaulted else None), None

    def _chain_kwargs(self, interpret: bool):
        """(kernel kwargs, chain_defaulted): tune.chain_selection plus
        the degrade memory — after a default-variant failure every later
        batch runs the plain chain directly."""
        from klogs_tpu.ops.tune import chain_selection

        kw, defaulted, _ = chain_selection(on_hardware=not interpret)
        if self._chain_fallback and defaulted:
            kw["mask_block"] = 1
            defaulted = False
        return kw, defaulted

    def _match_full(self, batch: np.ndarray, lengths: np.ndarray):
        """Byte-consuming full-line path (device-side classify).
        Returns (device_mask, retry_or_None, sweep_stats_or_None) — the
        retry covers an ASYNC failure (defaulted chain variant or the
        fused sweep kernel) surfacing at fetch(), mirroring
        _match_cls_dispatch."""
        if self._engine is not None:
            eng = self._engine
            retry = None
            if getattr(eng, "swept", False):
                # Async failure of the fused sweep fn surfaces at
                # fetch: drop the sweep, count the degrade, rerun on
                # the classify path (whose own gated/chain degrades
                # then apply).
                def retry(batch=batch, lengths=lengths):
                    self._degraded("sweep_kernel")
                    eng.disable_sweep()
                    return eng.match_batch(batch, lengths)
            elif getattr(eng, "gated", False):
                def retry(batch=batch, lengths=lengths):
                    self._degraded("gated_kernel")
                    eng.disable_prefilter()
                    return eng.match_batch(batch, lengths)
            elif getattr(eng, "_chain_defaulted", False):
                def retry(batch=batch, lengths=lengths):
                    self._degraded("chain")
                    eng.degrade_chain()
                    return eng.match_batch(batch, lengths)
            # A dispatch-time degrade inside the mesh counts through
            # its own on_degrade hook (filters/sink.py wires it).
            return eng.match_batch(batch, lengths), retry, None
        if self._kernel in ("pallas", "interpret"):
            interpret = self._kernel == "interpret"
            kw, chain_defaulted = self._chain_kwargs(interpret)

            def plain_retry():
                with self._state_lock:
                    self._chain_fallback = True
                self._degraded("chain")
                return self._pallas.match_batch_grouped_pallas(
                    self._dp_grouped, self._g_live, self._g_acc,
                    batch, lengths, interpret=interpret,
                    **dict(kw, mask_block=1))

            def run_plain(run_kw):
                return self._pallas.match_batch_grouped_pallas(
                    self._dp_grouped, self._g_live, self._g_acc,
                    batch, lengths, interpret=interpret, **run_kw)

            sweep = getattr(self, "_sweep_tables", None)
            if sweep is not None:

                def sweep_retry(record: bool = True):
                    # Fetch-time failure of the FUSED sweep kernel:
                    # drop the sweep gate (one cause at a time — the
                    # chain variant is independent), record the
                    # degrade, rerun plain. np.asarray forces the rerun
                    # synchronous so a second async fault surfaces
                    # here.
                    with self._state_lock:
                        self._sweep_tables = None
                    self._degraded("sweep_kernel")
                    try:
                        return np.asarray(run_plain(kw))
                    except Exception:
                        if not chain_defaulted:
                            raise
                        return plain_retry()

                want_stats = self._stats is not None
                try:
                    res = self._pallas.match_batch_grouped_pallas(
                        self._dp_grouped, self._g_live, self._g_acc,
                        batch, lengths, interpret=interpret,
                        sweep_tables=sweep, return_stats=want_stats,
                        **kw)
                    mask, sw = res if want_stats else (res, None)
                    return (mask, sweep_retry,
                            None if sw is None else ("sweep", sw))
                except Exception as e:
                    from klogs_tpu.ui import term

                    term.warning(
                        "fused sweep kernel unavailable (%s); "
                        "falling back to plain NFA", str(e)[:120])
                    with self._state_lock:
                        self._sweep_tables = None
                    self._degraded("sweep_kernel")
            try:
                mask = run_plain(kw)
            except Exception as e:
                if not chain_defaulted:
                    raise
                from klogs_tpu.ui import term

                term.warning(
                    "default mask_block=%d chain failed on this backend "
                    "(%s); continuing on the plain chain",
                    kw.get("mask_block"), str(e)[:120])
                return plain_retry(), None, None
            return mask, (plain_retry if chain_defaulted else None), None
        return self._nfa.match_batch(self._dp, batch, lengths), None, None

    def _match_long(self, bodies: list[bytes]) -> np.ndarray:
        """Carried-state chunked matching: all long lines advance in
        lockstep, the NFA state vector threaded across chunks."""
        L = self._chunk_bytes
        B = _bucket_batch(len(bodies))
        total = np.zeros(B, dtype=np.int32)
        total[: len(bodies)] = [len(b) for b in bodies]
        pad_rows = B - len(bodies)
        n_chunks = int(np.ceil(total.max() / L))
        use_pallas = self._kernel in ("pallas", "interpret")
        if use_pallas:
            v = self._pallas.initial_state_kernel(self._long_program(),
                                                  self._live, B)
        else:
            self._union()
            v, matched = self._nfa.initial_state(self._dp, B)
        host_cls = use_pallas and self._aug_cls_table is not None
        for k in range(n_chunks):
            seg = [b[k * L : (k + 1) * L].ljust(L, b"\0") for b in bodies]
            seg += [b"\0" * L] * pad_rows
            chunk = np.frombuffer(b"".join(seg), dtype=np.uint8).reshape(B, L)
            rem = total - k * L
            first, final = (k == 0), (k == n_chunks - 1)
            if host_cls:
                # Host-side classification, like the full-line hot path
                # (the device classify gather is ~85% of device time).
                dpa = self._dp_aug
                cls = classify_chunk_host(
                    chunk, rem, self._aug_cls_table,
                    dpa.begin_class, dpa.end_class, dpa.pad_class,
                    first=first, final=final)
                v, matched = self._pallas.match_chunk_cls_pallas(
                    dpa, self._acc, cls, v, final=final,
                    interpret=(self._kernel == "interpret"),
                )
            elif use_pallas:
                v, matched = self._pallas.match_chunk_pallas(
                    self._dp_aug, self._acc, chunk, rem, v,
                    first=first, final=final,
                    interpret=(self._kernel == "interpret"),
                )
            else:
                v, matched = self._nfa.match_chunk(
                    self._dp, chunk, rem, v, matched,
                    first=first, final=final,
                )
        return matched  # device array (padded); fetch() slices on host

    def _match_huge(self, bodies: list[bytes]) -> np.ndarray:
        """Sequence-parallel scan (ops/seqscan): log-depth batched
        transfer-matrix composition instead of len/chunk sequential
        dispatches. Concurrent jumbo lines advance together in one
        vmapped program per chunk-count bucket — no per-line dispatch
        or recompilation."""
        from klogs_tpu.ops import seqscan

        dp = self._long_program()
        return np.array(
            seqscan.match_lines_scan(dp, self._live, self._acc, bodies),
            dtype=bool)

    def close(self) -> None:
        if self._engine is not None:
            self._engine.close()
