"""Device-mesh execution of the batch-NFA filter.

SPMD layout (SURVEY.md §2 "Mesh/sharding layer", §5 "Distributed
communication backend"): a 2-D ``Mesh`` with axes

- ``data``    — lines (DP): the [B, L] byte batch is row-sharded.
- ``pattern`` — pattern groups (the TP analog): the K patterns are
  split into G groups, each compiled to its own automaton; the stacked
  [G, ...] program arrays are sharded one group per mesh column.

The per-line any-match reduce across pattern shards is expressed as a
plain ``jnp.any`` over the group axis; GSPMD lowers it to an all-reduce
over ICI. No hand-written collectives — shardings are annotated and XLA
inserts the comms (the reference's only comm stack is REST to the
apiserver, cmd/root.go:322-325; this is its on-mesh equivalent).

Multi-host: under ``jax.distributed`` the same Mesh spans hosts over
DCN transparently; nothing here is host-count-aware.
"""

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from klogs_tpu.filters.compiler.glushkov import compile_patterns
from klogs_tpu.ops import nfa


def choose_grid(n_devices: int, n_patterns: int) -> tuple[int, int]:
    """(data, pattern) mesh shape: give the pattern axis at most as many
    shards as there are patterns, keep it a divisor of the device count,
    and spend the rest on data parallelism. Batch is the throughput axis,
    so data gets the benefit of the doubt on ties."""
    g = 1
    for cand in range(min(n_devices, n_patterns), 0, -1):
        if n_devices % cand == 0:
            g = cand
            break
    d = n_devices // g
    # Prefer data-major splits: if the pattern axis ended up bigger than
    # data for a small pattern count, rebalance toward data.
    while g >= 2 * d and g % 2 == 0:
        g //= 2
        d *= 2
    return d, g


def split_patterns(patterns: list[str], g: int) -> list[list[str]]:
    """Round-robin so group automaton sizes stay balanced."""
    groups = [patterns[i::g] for i in range(g)]
    return [grp for grp in groups if grp]


class MeshEngine:
    """Pattern-sharded, data-parallel match engine over a jax Mesh.

    Drop-in ``engine`` for NFAEngineFilter: exposes match_batch over
    numpy arrays, returning a device mask handle.

    Two SPMD implementations with identical semantics:

    - ``impl="gspmd"`` (default): sharding annotations on a vmapped
      any-match; XLA's partitioner inserts the cross-shard all-reduce.
    - ``impl="shard_map"``: per-shard code with an EXPLICIT collective —
      each pattern shard evaluates its own automaton on its data rows,
      then ``jax.lax.pmax`` ORs the bitmask across the ``pattern`` axis
      over ICI. Same collective XLA would insert, written out so the
      comm pattern is visible/auditable (SURVEY.md §5 "Distributed
      communication backend").
    - ``impl="pallas"`` (and ``"pallas_interpret"`` for hermetic tests):
      shard_map with the grouped Pallas kernel as the per-shard compute —
      the production multi-chip hot path (VMEM-resident kernel per chip,
      pmax OR across pattern shards over ICI). Pattern groups are
      bin-packed per shard via compile_grouped.
    """

    def __init__(self, patterns: list[str], ignore_case: bool = False,
                 devices=None, grid: tuple[int, int] | None = None,
                 impl: str = "gspmd", on_degrade=None):
        devices = devices if devices is not None else jax.devices()
        # on_degrade(path): called when a kernel gives way and the
        # engine carries on with a plainer one (FilterStats.
        # record_degrade's paths); None counts nothing.
        self._on_degrade = on_degrade or (lambda path: None)
        # Devices holding the last verdict array (placement()).
        self._verdict_devices = 0
        if grid is None:
            grid = choose_grid(len(devices), len(patterns))
        d, g = grid
        if d * g != len(devices):
            raise ValueError(f"grid {grid} != device count {len(devices)}")
        groups = split_patterns(patterns, g)
        # If fewer pattern groups than shards, replicate the last: a
        # duplicate group changes nothing under any-match.
        while len(groups) < grid[1]:
            groups.append(groups[-1])
        self.grid = (d, grid[1])
        self.mesh = Mesh(np.asarray(devices).reshape(self.grid), ("data", "pattern"))
        # Under jax.distributed the mesh spans processes: host numpy
        # can no longer be handed to jit/device_put directly — every
        # process holds the SAME full array and materializes only its
        # addressable shards (make_array_from_callback; the
        # replicated-input SPMD recipe). Single-process keeps the
        # zero-copy direct path.
        self._multiprocess = jax.process_count() > 1
        if impl in ("pallas", "pallas_interpret"):
            self._init_pallas(groups, ignore_case, impl)
            return
        progs = [compile_patterns(grp, ignore_case=ignore_case) for grp in groups]
        self.dp = nfa.stack_programs(progs)
        self.match_all = self.dp.match_all

        prog_sharding = jax.tree_util.tree_map(
            lambda _: NamedSharding(self.mesh, P("pattern")), self.dp
        )
        if self._multiprocess:
            self.dp = jax.tree_util.tree_map(self._global_leaf, self.dp,
                                             prog_sharding)
        else:
            self.dp = jax.device_put(self.dp, prog_sharding)
        if impl == "gspmd":
            self._fn = jax.jit(
                nfa.match_batch_grouped,
                in_shardings=(
                    prog_sharding,
                    NamedSharding(self.mesh, P("data", None)),
                    NamedSharding(self.mesh, P("data")),
                ),
                out_shardings=NamedSharding(self.mesh, P("data")),
            )
        elif impl == "shard_map":
            def per_shard(dp_shard, batch_local, lengths_local):
                # dp leaves arrive with a leading local group axis of 1.
                local = jax.tree_util.tree_map(lambda x: x[0], dp_shard)
                matched = nfa.match_batch(local, batch_local, lengths_local)
                # OR across pattern shards = max of 0/1 over the axis;
                # rides ICI when the mesh spans chips.
                return jax.lax.pmax(matched.astype(jnp.int32), "pattern") > 0

            specs = dict(
                mesh=self.mesh,
                in_specs=(
                    jax.tree_util.tree_map(lambda _: P("pattern"), self.dp),
                    P("data", None),
                    P("data"),
                ),
                out_specs=P("data"),
            )
            # The scan carry is zeros-initialized inside match_batch,
            # which the varying-manual-axes checker flags as
            # unvarying-meets-varying; the pmax above establishes the
            # replication the out_spec needs, so the check is safely
            # off.
            self._fn = jax.jit(
                shard_map(per_shard, check_vma=False, **specs))
        else:
            raise ValueError(f"unknown impl {impl!r}")
        self.impl = impl

    def _init_pallas(self, groups: list[list[str]], ignore_case: bool,
                     impl: str) -> None:
        """shard_map with the grouped Pallas kernel as per-shard compute
        — the production multi-chip hot path, running the SAME
        architecture as single-chip: host-side fused pack+classify (the
        device classify gather measured as ~85% of device time,
        BENCH_DEVICE.json), int8 class ids sharded over `data`, kernel
        consuming classes directly, pmax OR across `pattern` shards.

        Shards must be shape-uniform, so each shard's pattern set
        compiles twice: once to learn its natural (G, S, C), then with
        forced pads to the maxima (dead filler groups can never match).
        Because every shard must classify a line identically for ONE
        host-side cls array to serve all pattern shards, the per-shard
        classifiers are refined into a GLOBAL one (unique rows of the
        stacked byte->class signatures) and each shard's char_mask rows
        are re-laid-out onto the global classes.

        KLOGS_TPU_PREFILTER=1 additionally stacks per-shard class-domain
        prefilter tables so each shard tile-skips on its own patterns'
        candidate mask (all-or-nothing across shards, matching the
        single-chip usability rule)."""
        import dataclasses

        from klogs_tpu.ops.nfa import _pad_to
        from klogs_tpu.utils.env import read as env_read
        from klogs_tpu.ops.pallas_nfa import (
            match_batch_grouped_pallas,
            match_cls_grouped_pallas,
        )

        probe = [nfa.compile_grouped(ps, ignore_case=ignore_case)[0]
                 for ps in groups]
        G = max(p.follow.shape[0] for p in probe)
        S = max(p.n_states for p in probe)
        # No classes_pad: the whole class axis (char_mask rows,
        # byte_class, sentinels, n_classes) is rebuilt onto the global
        # classifier below, so only group/state shapes need forcing.
        dps = [nfa.compile_grouped(ps, ignore_case=ignore_case,
                                   n_groups=G, states_pad=S)[0]
               for ps in groups]
        live, acc = S - 2, S - 1

        # Global classifier: bytes equivalent in EVERY shard collapse.
        sig = np.stack([np.asarray(d.byte_class) for d in dps], axis=1)
        uniq, glob = np.unique(sig, axis=0, return_inverse=True)
        n_glob = uniq.shape[0]
        C = _pad_to(n_glob + 3, 8)
        begin_c, end_c, pad_c = C - 3, C - 2, C - 1
        redps = []
        for k, d in enumerate(dps):
            cm = np.asarray(d.char_mask)  # [G, C_loc, S]
            ncm = np.zeros((G, C, S), dtype=cm.dtype)
            ncm[:, :n_glob, :] = cm[:, uniq[:, k], :]
            ncm[:, begin_c] = cm[:, d.begin_class]
            ncm[:, end_c] = cm[:, d.end_class]
            ncm[:, pad_c] = cm[:, d.pad_class]
            redps.append(dataclasses.replace(
                d,
                char_mask=jnp.asarray(ncm),
                byte_class=jnp.asarray(glob.astype(np.int32)),
                begin_class=begin_c, end_class=end_c, pad_class=pad_c,
                n_classes=C,
                # match_all is pytree AUX and may differ across shards;
                # stacking requires identical aux, so force the any()
                # verdict uniformly — the OR across shards is what the
                # engine computes anyway. pattern_group (also aux)
                # differs per shard too and only feeds the single-chip
                # per-(tile, group) gate; the mesh path gates per tile,
                # so clear it uniformly.
                match_all=any(x.match_all for x in dps),
                pattern_group=(),
            ))
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *redps)
        if self._multiprocess:
            shardings = jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P("pattern")), stacked)
            stacked = jax.tree_util.tree_map(self._global_leaf, stacked,
                                             shardings)
        self.dp = stacked
        self.match_all = stacked.match_all
        self.cls_table = glob.astype(np.int8) if C <= 127 else None
        self._glob = glob.astype(np.int32)
        self.begin_class, self.end_class, self.pad_class = begin_c, end_c, pad_c
        interpret = impl == "pallas_interpret"

        pf_stacked = None
        if env_read("KLOGS_TPU_PREFILTER", "0") == "1" \
                and self.cls_table is not None:
            pf_stacked = self._stack_prefilters(groups, ignore_case, glob, C)

        # Device literal sweep (thousand-pattern fused path): per-shard
        # sweep tables stacked shape-uniform, gating each shard's
        # (tile, group) grid cells on ITS patterns' factor-index
        # candidate mask. The sweep-vs-prefilter precedence is the ONE
        # shared rule (cpu.device_gate_choice, same as tpu._init_sweep):
        # auto K threshold + real accelerator, explicit prefilter
        # opt-in beats auto sweep, forced sweep supersedes — and a
        # working prefilter is only discarded after the tables built.
        sweep_stacked = None
        n_patterns = sum(len(ps) for ps in groups)
        from klogs_tpu.filters.cpu import (
            device_gate_choice,
            note_sweep_supersedes,
        )

        if device_gate_choice(n_patterns,
                              have_prefilter=pf_stacked is not None,
                              interpret=interpret) == "sweep":
            sweep_stacked = self._stack_sweeps(groups, ignore_case, dps, G)
            if sweep_stacked is None:
                self._on_degrade("sweep_build")
            if sweep_stacked is not None and pf_stacked is not None:
                note_sweep_supersedes(mesh=True)
                pf_stacked = None

        # Same chain-variant policy as the single-chip hot path
        # (tune.chain_selection: measured default mask_block=4 on
        # hardware, env-overridable), minus `fused` — it has no gated
        # sibling while this one per_shard body backs both the plain and
        # gated builds, so chain_selection drops it and we warn.
        from klogs_tpu.ops.tune import chain_selection

        vkw, self._chain_defaulted, dropped_fused = chain_selection(
            not interpret, allow_fused=False)
        if dropped_fused:
            from klogs_tpu.ui import term

            term.warning(
                "KLOGS_TPU_FUSED_GROUPS=1 has no mesh per-shard variant; "
                "using the default chain instead")
        # tile_b is a cap; the kernel wrapper pads any local batch up
        # to a tile multiple, so non-power-of-two shard sizes work.
        vkw.setdefault("tile_b", 2048)
        self._vkw = vkw

        def build(with_pf: bool, vkw=vkw):
            def per_shard(dp_shard, cls_local, *pf_shard):
                local = jax.tree_util.tree_map(lambda x: x[0], dp_shard)
                pf = tuple(x[0] for x in pf_shard) if pf_shard else None
                matched = match_cls_grouped_pallas(
                    local, live, acc, cls_local,
                    interpret=interpret,
                    prefilter_tables=pf, **vkw,
                )
                return jax.lax.pmax(matched.astype(jnp.int32), "pattern") > 0

            in_specs = [
                jax.tree_util.tree_map(lambda _: P("pattern"), stacked),
                P("data", None),
            ]
            if with_pf:
                in_specs.extend(P("pattern") for _ in pf_stacked)
            smapped = shard_map(per_shard, mesh=self.mesh,
                                in_specs=tuple(in_specs),
                                out_specs=P("data"), check_vma=False)
            if with_pf:
                return jax.jit(
                    lambda dp, cls, pf=pf_stacked: smapped(dp, cls, *pf))
            return jax.jit(smapped)

        self._build = build

        def build_sweep(vkw=vkw):
            def per_shard(dp_shard, batch_local, lengths_local,
                          sweep_shard):
                local = jax.tree_util.tree_map(lambda x: x[0], dp_shard)
                st = jax.tree_util.tree_map(lambda x: x[0], sweep_shard)
                matched = match_batch_grouped_pallas(
                    local, live, acc, batch_local, lengths_local,
                    interpret=interpret, sweep_tables=st, **vkw)
                return jax.lax.pmax(matched.astype(jnp.int32),
                                    "pattern") > 0

            specs = dict(
                mesh=self.mesh,
                in_specs=(
                    jax.tree_util.tree_map(lambda _: P("pattern"),
                                           stacked),
                    P("data", None),
                    P("data"),
                    jax.tree_util.tree_map(lambda _: P("pattern"),
                                           sweep_stacked),
                ),
                out_specs=P("data"),
            )
            smapped = shard_map(per_shard, check_vma=False, **specs)
            return jax.jit(
                lambda dp, batch, lengths, st=sweep_stacked:
                smapped(dp, batch, lengths, st))

        # The plain fn always exists: it is both the default path and
        # the degrade target when the opt-in gated kernel fails (same
        # contract as the single-chip fetch-time fallback).
        self._fn = build(False)
        self._fn_gated = build(True) if pf_stacked is not None else None
        # Byte-consuming fused path: match_batch routes through it when
        # built (frame -> sweep -> gated match per shard, one device
        # dispatch); match_cls cannot (no bytes to sweep).
        self._fn_sweep = (build_sweep() if sweep_stacked is not None
                          else None)
        self.impl = impl

    def disable_prefilter(self) -> None:
        """Degrade to the plain kernel (e.g. after a gated-kernel
        compile/execution failure surfaced at fetch)."""
        self._fn_gated = None

    @property
    def gated(self) -> bool:
        return getattr(self, "_fn_gated", None) is not None

    def disable_sweep(self) -> None:
        """Degrade the fused sweep path to host-classify + plain kernel
        (e.g. after a sweep-kernel failure surfaced at fetch)."""
        self._fn_sweep = None

    @property
    def swept(self) -> bool:
        return getattr(self, "_fn_sweep", None) is not None

    @staticmethod
    def _stack_sweeps(groups, ignore_case, dps, G):
        """Per-shard device-sweep tables over each shard's OWN pattern
        set, retargeted to its grouped program's pattern_group map (the
        forced-uniform G makes always/group bitsets shape-uniform), and
        stacked [n_shards, ...] via ops.sweep.stack_sweep_tables.
        Returns None (sweep off everywhere) when any shard's tables
        fail to build — shard_map runs one program."""
        from klogs_tpu.filters.compiler.groups import analyze, plan_groups
        from klogs_tpu.filters.compiler.index import FactorIndex
        from klogs_tpu.ops.sweep import stack_sweep_tables

        progs = []
        try:
            for ps, dp in zip(groups, dps):
                infos = analyze(ps, ignore_case=ignore_case)
                index = FactorIndex(infos, plan_groups(infos))
                progs.append(index.sweep_program(
                    group_of=np.asarray(dp.pattern_group,
                                        dtype=np.int32),
                    n_groups=G))
            return stack_sweep_tables(progs)
        except Exception as e:
            from klogs_tpu.ui import term

            term.warning(
                "mesh device sweep unavailable (%s: %s); running the "
                "plain kernel", type(e).__name__, e)
            return None

    @staticmethod
    def _stack_prefilters(groups, ignore_case, glob, C):
        """Per-shard class-domain prefilter tables over the GLOBAL
        classifier, padded shape-uniform and stacked [n_shards, ...].
        Returns None (gating off everywhere) unless every shard's
        pattern set is usable — a shard that cannot gate must still
        scan all its tiles, and shard_map runs one program."""
        from klogs_tpu.filters.compiler.prefilter import compile_prefilter
        from klogs_tpu.ops.prefilter import class_tables

        pfs = [compile_prefilter(ps, ignore_case=ignore_case)
               for ps in groups]
        if not all(pf.usable for pf in pfs):
            return None
        slots = max(pf.lut1.shape[1] * 32 for pf in pfs)
        pats = max(pf.req.shape[0] for pf in pfs)
        tabs = [class_tables(pf, glob, C, slots_pad=slots,
                             patterns_pad=pats) for pf in pfs]
        if any(t is None for t in tabs):
            return None
        return tuple(jnp.stack(xs) for xs in zip(*tabs))

    @property
    def data_parallelism(self) -> int:
        return self.grid[0]

    def _global_leaf(self, arr, sharding):
        """Full host array -> global jax.Array under a multi-process
        mesh (this process materializes its addressable shards)."""
        arr = np.asarray(arr)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])

    def _place_data(self, arr: np.ndarray, spec):
        """Batch-input placement: direct (jit shards it) in one
        process, global-Array construction across processes."""
        if not self._multiprocess:
            return arr
        return self._global_leaf(arr, NamedSharding(self.mesh, spec))

    def match_batch(self, batch: np.ndarray, lengths: np.ndarray):
        """[B, L] u8 + [B] i32 -> [>=B] bool mask, returned as a DEVICE
        array (padded rows at the tail; callers slice after np.asarray —
        keeps dispatch non-blocking for the async pipeline). B is padded
        up to a multiple of the data axis so every shard gets equal rows.

        The pallas impls consume class ids, so this entry classifies on
        the host (vectorized numpy over the global table) and routes to
        match_cls — same verdicts, one extra host pass; filters that can
        produce cls directly (pack_classify) should call match_cls."""
        if self.impl in ("pallas", "pallas_interpret"):
            if self.swept:
                try:
                    return self._match_batch_swept(batch, lengths)
                except Exception as e:
                    # Fused-sweep compile/dispatch trouble degrades to
                    # the classify path, not a dead stream (same
                    # contract as the gated kernel).
                    from klogs_tpu.ui import term

                    term.warning(
                        "mesh fused sweep kernel unavailable (%s); "
                        "falling back to host classify + plain NFA",
                        str(e)[:120])
                    self._on_degrade("sweep_kernel")
                    self.disable_sweep()
            from klogs_tpu.filters.tpu import classify_batch

            cls = classify_batch(batch, lengths, self._glob,
                                 self.begin_class, self.end_class,
                                 self.pad_class)
            return self.match_cls(cls)
        B = batch.shape[0]
        d = self.grid[0]
        Bp = math.ceil(B / d) * d
        if Bp != B:
            batch = np.concatenate(
                [batch, np.zeros((Bp - B, batch.shape[1]), dtype=batch.dtype)]
            )
            lengths = np.concatenate(
                [lengths, np.zeros((Bp - B,), dtype=lengths.dtype)]
            )
        return self._placed(
            self._fn(self.dp, self._place_data(batch, P("data", None)),
                     self._place_data(lengths, P("data"))))

    def _match_batch_swept(self, batch: np.ndarray, lengths: np.ndarray):
        """Fused byte path: [B, L] u8 + [B] i32 -> [>=B] bool device
        mask via frame -> device sweep -> gated match per shard (one
        dispatch, no host classify). Rows pad to a data-axis multiple;
        zero-length pad rows can never host a factor or match."""
        B = batch.shape[0]
        d = self.grid[0]
        Bp = math.ceil(B / d) * d
        if Bp != B:
            batch = np.concatenate(
                [batch, np.zeros((Bp - B, batch.shape[1]),
                                 dtype=batch.dtype)])
            lengths = np.concatenate(
                [lengths, np.zeros((Bp - B,), dtype=lengths.dtype)])
        from klogs_tpu.obs import trace

        with trace.TRACER.span("mesh.dispatch", impl=self.impl,
                               rows=Bp, swept=True,
                               grid=f"{self.grid[0]}x{self.grid[1]}"):
            return self._placed(self._fn_sweep(
                self.dp, self._place_data(batch, P("data", None)),
                self._place_data(np.ascontiguousarray(lengths,
                                                      dtype=np.int32),
                                 P("data"))))

    def match_cls(self, cls: np.ndarray, plain: bool = False):
        """Hot-path entry for pallas impls: [B, T] int8/int32 class ids
        (pack_classify layout) -> [>=B] bool device mask. Rows are
        padded (all-PAD: cannot match) to a data-axis multiple. The
        gated fn is used when built (KLOGS_TPU_PREFILTER=1) unless
        ``plain`` forces the fallback."""
        B = cls.shape[0]
        d = self.grid[0]
        Bp = math.ceil(B / d) * d
        if Bp != B:
            cls = np.concatenate(
                [cls, np.full((Bp - B, cls.shape[1]), self.pad_class,
                              dtype=cls.dtype)]
            )
        use_gated = not plain and self.gated
        fn = self._fn_gated if use_gated else self._fn
        cls = self._place_data(cls, P("data", None))
        from klogs_tpu.obs import trace

        try:
            with trace.TRACER.span("mesh.dispatch", impl=self.impl,
                                   rows=Bp, gated=use_gated,
                                   grid=f"{self.grid[0]}x{self.grid[1]}"):
                return self._placed(fn(self.dp, cls))
        except Exception as e:
            # Chain-variant compile fragility is a known failure mode
            # (mask_block=8/16 fail Mosaic on v5e). A DEFAULTED variant
            # failing on the PLAIN fn degrades to the plain chain
            # instead of killing the run. A gated-fn failure is NOT
            # attributed to the chain (the prefilter machinery is the
            # other suspect) — it propagates to the caller's
            # disable-prefilter retry, whose plain rerun comes back
            # through here and exercises this degrade if the chain
            # really is at fault. An env-forced variant stays loud —
            # the operator asked to measure exactly that kernel.
            if use_gated or not getattr(self, "_chain_defaulted", False):
                raise
            from klogs_tpu.ui import term

            term.warning(
                "default mask_block=%d chain failed on this backend (%s); "
                "rebuilding with the plain chain",
                self._vkw.get("mask_block"), str(e)[:120])
            self._on_degrade("chain")
            self.degrade_chain()
            return self._placed(self._fn(self.dp, cls))

    def degrade_chain(self) -> None:
        """Rebuild both fns on the plain serial chain (mask_block=1) —
        the degrade target after a defaulted-chain-variant failure
        (sync, via match_cls; or async at fetch, via the filter's retry
        closure)."""
        self._chain_defaulted = False
        self._vkw = dict(self._vkw, mask_block=1)
        self._fn = self._build(False, self._vkw)
        if self.gated:
            self._fn_gated = self._build(True, self._vkw)

    def _placed(self, verdicts):
        """Note how many devices hold a verdict array (its sharding is
        known at dispatch; no wait for the device)."""
        self._verdict_devices = len(verdicts.sharding.device_set)
        return verdicts

    def placement(self) -> dict:
        """The mesh as it ran: (data, pattern) shape, the devices it
        spans, and how many devices held the last verdict array — the
        proof that verdicts were sharded over every chip, not computed
        on the first one."""
        return {"shape": list(self.grid), "impl": self.impl,
                "devices": int(self.mesh.devices.size),
                "verdict_devices": self._verdict_devices}

    def close(self) -> None:
        pass
