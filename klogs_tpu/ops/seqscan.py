"""Sequence-parallel matching of ONE huge line (the long-context op).

The vector scan (ops/nfa, ops/pallas_nfa) is latency-bound on a single
line: T sequential steps, one tiny matmul each — a 1 MB line takes ~1.5 s
at ~1.5 us/step no matter how wide the machine is. This module removes
the sequential bottleneck with the classic linear-recurrence trick:

The AUGMENTED automaton (nfa.augment — inject folded into the `live`
self-loop, accept into the absorbing `acc` sink) makes the per-byte
update LINEAR over the boolean semiring:

    v_{t} = v_{t-1} @ A[c_t],   A[c][i,j] = Follow[i,j] AND B[c][j]

Matrix products are associative, so a tile of T0 bytes folds into one
transfer matrix M_tile = A[c_1] ... A[c_T0] by a log2(T0)-depth tree of
BATCHED [S,S]x[S,S] matmuls — T0-way parallel work the MXU eats whole —
and tiles compose across the line (and across DEVICES, each taking a
contiguous span, with one [S,S] matrix per device to gather: the
sequence-parallel layout SURVEY.md §5 notes as the scaling option).

Cost model, honestly: the matrix path does S x more multiply work per
byte than the vector scan (S^3 vs S^2 per step-ish), but it converts a
serial chain into parallel batched matmuls. For S=128 on v5e the vector
scan is ~us/byte (latency) while the tree is ~ns/byte (throughput) —
a ~100x single-line win, growing linearly with devices. Use it when one
line is huge; the batched vector kernel remains optimal when
parallelism already comes from many lines.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from klogs_tpu.ops.nfa import DeviceProgram

DEFAULT_TILE_T = 512

# Peak footprint of the tree's first level is N*T0 step matrices of S^2
# int8 each (>=16 KB per input byte at S=128). One jumbo line processed
# in a single call therefore OOMs the device — a ~1 MB line would want
# ~16 GB. Matching is instead CHUNKED: at most this many step-matrix
# bytes are materialized per tile_transfer_matrices call, and the
# resulting per-chunk matrices fold sequentially into the carry.
DEFAULT_STEP_BYTES_BUDGET = 128 << 20


def _tiles_per_chunk(tile_t: int, n_states: int,
                     budget: int = DEFAULT_STEP_BYTES_BUDGET) -> int:
    return max(1, budget // (tile_t * n_states * n_states))


def _bmm_bool(a: jax.Array, b: jax.Array) -> jax.Array:
    """Batched boolean matrix product on int8 0/1 operands."""
    return (
        jnp.einsum("bij,bjk->bik", a, b, preferred_element_type=jnp.int32) > 0
    ).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=())
def tile_transfer_matrices(dp: DeviceProgram, cls: jax.Array) -> jax.Array:
    """classes [N, T0] -> transfer matrices [N, S, S] (one per tile),
    each the ordered product of its per-character step matrices, built
    by a log-depth pairwise tree so every level is one batched matmul.
    T0 must be a power of two (pad with pad_class: its step matrix is
    absorbing for live/acc and kills everything else, which is exactly
    the semantics of positions past the end of the line).

    Materializes N*T0 step matrices — callers must bound N*T0 (see
    _tiles_per_chunk / DEFAULT_STEP_BYTES_BUDGET)."""
    N, T0 = cls.shape
    S = dp.n_states
    # A[c][i,j] = follow[i,j] & char_mask[c][j]
    a = dp.follow[None, :, :].astype(jnp.int8) * \
        dp.char_mask[cls.reshape(-1)][:, None, :].astype(jnp.int8)  # [N*T0,S,S]
    while a.shape[0] > N:
        a = _bmm_bool(a[0::2], a[1::2])
    return a


def _pad_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def classify_line(dp: DeviceProgram, line: bytes, tile_t: int) -> np.ndarray:
    """Class ids for one line incl. BEGIN/END/latch, padded to a
    multiple of tile_t (tile_t must be a power of two)."""
    body = np.frombuffer(line, dtype=np.uint8)
    cls = np.asarray(dp.byte_class)[body]
    full = np.concatenate([
        np.array([dp.begin_class], dtype=np.int32),
        cls.astype(np.int32),
        np.array([dp.end_class, dp.pad_class], dtype=np.int32),  # END + latch
    ])
    T = len(full)
    pad = -T % tile_t
    if pad:
        full = np.concatenate(
            [full, np.full(pad, dp.pad_class, dtype=np.int32)])
    return full


@functools.partial(jax.jit, static_argnames=("live",))
def _scan_chunked(dp: DeviceProgram, cls3: jax.Array, live: int) -> jax.Array:
    """cls3 [n_chunks, tiles_per_chunk, tile_t] -> final state vector.
    The outer scan bounds peak memory to ONE chunk's step matrices."""

    def chunk_step(v, cls_chunk):
        mats = tile_transfer_matrices(dp, cls_chunk)  # [tpc, S, S]

        def fold(v, m):
            return (
                jnp.einsum("j,jk->k", v, m,
                           preferred_element_type=jnp.int32) > 0
            ).astype(jnp.int8), None

        v, _ = jax.lax.scan(fold, v, mats)
        return v, None

    v0 = (jnp.arange(dp.n_states) == live).astype(jnp.int8)
    v, _ = jax.lax.scan(chunk_step, v0, cls3)
    return v


def _chunk_classes(dp: DeviceProgram, cls: np.ndarray, tile_t: int,
                   tiles_per_chunk: int, round_to: int = 1) -> np.ndarray:
    """[n_tiles, tile_t] -> [n_chunks, tiles_per_chunk, tile_t], chunk
    count padded (with pad_class tiles, which are identity for live/acc)
    up to a power of two times ``round_to`` so the jit cache sees
    O(log line-length) distinct shapes, not one per length."""
    n_tiles = cls.shape[0]
    n_chunks = _pad_pow2(-(-n_tiles // tiles_per_chunk))
    n_chunks = -(-n_chunks // round_to) * round_to
    pad = n_chunks * tiles_per_chunk - n_tiles
    if pad:
        cls = np.concatenate(
            [cls, np.full((pad, tile_t), dp.pad_class, dtype=np.int32)])
    return cls.reshape(n_chunks, tiles_per_chunk, tile_t)


def match_line_scan(dp: DeviceProgram, live: int, acc: int, line: bytes,
                    tile_t: int = DEFAULT_TILE_T,
                    step_bytes_budget: int = DEFAULT_STEP_BYTES_BUDGET) -> bool:
    """Single-device sequence-parallel match of one line: per-tile
    transfer matrices by batched tree, then a cheap sequential
    vector-matrix fold across tiles (S^2 per tile_t bytes). Peak device
    memory is bounded by ``step_bytes_budget`` regardless of line size —
    tiles are processed in fixed-size chunks folded into the carry."""
    return match_lines_scan(dp, live, acc, [line], tile_t,
                            step_bytes_budget)[0]


@functools.partial(jax.jit, static_argnames=("live",))
def _scan_chunked_batch(dp: DeviceProgram, cls4: jax.Array,
                        live: int) -> jax.Array:
    """[N, n_chunks, tpc, tile_t] -> [N, S] final state vectors — N
    jumbo lines advancing together (vmap of the chunked fold)."""
    return jax.vmap(lambda c: _scan_chunked(dp, c, live))(cls4)


def match_lines_scan(dp: DeviceProgram, live: int, acc: int,
                     lines: list[bytes],
                     tile_t: int = DEFAULT_TILE_T,
                     step_bytes_budget: int = DEFAULT_STEP_BYTES_BUDGET,
                     ) -> list[bool]:
    """Batched sequence-parallel matching of N jumbo lines: lines are
    grouped by padded chunk-count (a power of two, so the jit cache
    sees O(log max-length) shapes — no recompilation per line) and each
    group runs as ONE vmapped device program. The step-matrix budget is
    split across the lines scanned together, keeping peak memory at
    ``step_bytes_budget`` for the whole call."""
    assert tile_t & (tile_t - 1) == 0, "tile_t must be a power of two"
    if not lines:
        return []
    S = dp.n_states
    # Every shape knob is quantized to a power of two — line count for
    # the budget split, tiles-per-chunk, group batch dim — so the jit
    # cache stays O(log^2), not one entry per concurrent-line count.
    per_line = max(step_bytes_budget // _pad_pow2(len(lines)),
                   tile_t * S * S)
    tpc = _pow2_floor(_tiles_per_chunk(tile_t, S, per_line))
    groups: dict[int, list[int]] = {}
    cls3s: list[np.ndarray] = []
    for i, line in enumerate(lines):
        cls = classify_line(dp, line, tile_t).reshape(-1, tile_t)
        cls3 = _chunk_classes(dp, cls, tile_t, tpc)
        cls3s.append(cls3)
        groups.setdefault(cls3.shape[0], []).append(i)
    out = [bool(dp.match_all)] * len(lines)
    # Peak memory = vmap-width x one chunk's step matrices; cap the
    # width so N concurrent jumbo lines can never multiply past the
    # budget (the batch dim is as real a memory axis as the chunk dim).
    max_n = max(1, _pow2_floor(
        step_bytes_budget // (tpc * tile_t * S * S)))
    for idxs in groups.values():
        for lo in range(0, len(idxs), max_n):
            sub = idxs[lo : lo + max_n]
            rows = [cls3s[i] for i in sub]
            # Pad the batch dim with all-PAD pseudo-lines (identity
            # folds, never match) up to a power of two.
            pad_n = _pad_pow2(len(rows)) - len(rows)
            if pad_n:
                rows.extend([np.full_like(rows[0], dp.pad_class)] * pad_n)
            stacked = jnp.asarray(np.stack(rows))
            v = np.asarray(_scan_chunked_batch(dp, stacked, live))
            for i, hit in zip(sub, v[:, acc]):
                out[i] = bool(hit) or dp.match_all
    return out


def _sharded_fn(mesh, n_states: int):
    """Build (once per mesh, via the jit cache on the returned callable)
    the shard_map'd per-device chunked fold."""
    import jax.sharding as shd
    from jax import shard_map

    P = shd.PartitionSpec

    def per_device(dp, cls3_local):
        eye = jnp.eye(n_states, dtype=jnp.int8)

        def chunk_step(m_acc, cls_chunk):
            mats = tile_transfer_matrices(dp, cls_chunk)  # [tpc, S, S]

            def fold(m, m2):
                return _bmm_bool(m[None], m2[None])[0], None

            m, _ = jax.lax.scan(fold, m_acc, mats)
            return m, None

        # Chunked fold bounds peak memory to one chunk's step matrices.
        m_dev, _ = jax.lax.scan(chunk_step, eye, cls3_local)  # [S, S]
        # One matrix per device; compose in device order.
        all_m = jax.lax.all_gather(m_dev, "seq")  # [D, S, S]

        def fold2(m_acc, m):
            return _bmm_bool(m_acc[None], m[None])[0], None

        m_total, _ = jax.lax.scan(fold2, eye, all_m)
        return m_total[None]  # [1, S, S] -> gathered to [D, S, S]

    return jax.jit(shard_map(per_device, mesh=mesh,
                             in_specs=(P(), P("seq")),
                             out_specs=P("seq"), check_vma=False))


# shard_map'd fold programs, keyed by (device ids, axis name, S) — NOT
# by the Mesh object: two Meshes over the same devices are functionally
# identical, and keying on the object would leak one jitted closure per
# ad-hoc Mesh. Bounded LRU so even pathological device-set churn cannot
# grow it without limit.
_SHARDED_CACHE: "dict" = {}
_SHARDED_CACHE_MAX = 8


def match_line_sharded(dp: DeviceProgram, live: int, acc: int, line: bytes,
                       mesh=None, tile_t: int = DEFAULT_TILE_T,
                       step_bytes_budget: int = DEFAULT_STEP_BYTES_BUDGET) -> bool:
    """Sequence-parallel across DEVICES: the line's tile-chunks shard
    over a 1-D ``seq`` mesh axis; each device folds its contiguous span
    into one [S, S] transfer matrix (chunk by chunk, so peak memory is
    bounded by ``step_bytes_budget`` per device), and the D per-device
    matrices compose after an all-gather — D-1 extra [S,S] matmuls
    total, the analog of a ring/all-to-all sequence-parallel step. The
    shard_map'd program is cached per (mesh, S); chunk counts are padded
    to powers of two so distinct line lengths reuse compilations."""
    import jax.sharding as shd

    if mesh is None:
        devs = np.asarray(jax.devices())
        mesh = shd.Mesh(devs, ("seq",))
    D = mesh.devices.size

    cls = classify_line(dp, line, tile_t).reshape(-1, tile_t)
    tpc = _tiles_per_chunk(tile_t, dp.n_states, step_bytes_budget)
    # Chunk count a power of two AND a multiple of D -> equal spans.
    cls3 = _chunk_classes(dp, cls, tile_t, tpc, round_to=D)

    key = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names,
           dp.n_states)
    fn = _SHARDED_CACHE.pop(key, None)
    if fn is None:
        fn = _sharded_fn(mesh, dp.n_states)
    _SHARDED_CACHE[key] = fn  # re-insert: dict order gives LRU
    while len(_SHARDED_CACHE) > _SHARDED_CACHE_MAX:
        _SHARDED_CACHE.pop(next(iter(_SHARDED_CACHE)))
    m_total = np.asarray(fn(dp, jnp.asarray(cls3)))[0]  # replicated
    v0 = np.zeros(dp.n_states, dtype=np.int64)
    v0[live] = 1
    return bool((v0 @ m_total)[acc] > 0) or dp.match_all
