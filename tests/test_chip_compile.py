"""Ahead-of-time compiles for a described v5e chip: the kernels of the
main path at real widths, as the TPU compiler would take them.

Nothing runs: a compile that passes says Mosaic accepts the kernel at
that shape and that it fits the device, not that its answers are right
(chip_smoke.py checks those on the chip). The topology is described
inside a fixture, never at import: only one process may load the TPU
library, and every test worker imports this file. Keep these tests in
this one file, so that one worker loads it.
"""

import pytest

pytestmark = pytest.mark.filterwarnings("ignore::UserWarning")


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but can never be read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _k32_grouped():
    import bench
    from klogs_tpu.ops import nfa

    return nfa.compile_grouped(bench.PATTERNS)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mask_block", [4, 1])
def test_grouped_cls_kernel_k32_at_1m_rows(one_chip, mask_block):
    """The collector's hot kernel (phase A): host-classified int8 ids,
    [1,048,576 x 131], the 32 north-star patterns."""
    import jax
    import jax.numpy as jnp

    from klogs_tpu.ops.pallas_nfa import match_cls_grouped_pallas

    dp, live, acc = _k32_grouped()
    cls = jax.ShapeDtypeStruct((1_048_576, 131), jnp.int8,
                               sharding=one_chip)
    compiled = match_cls_grouped_pallas.lower(
        _shapes(dp, one_chip), live, acc, cls,
        mask_block=mask_block).compile()
    assert _has_kernel(compiled)


def test_sweep_gated_kernel_k1024(one_chip, monkeypatch):
    """The rule-set kernel (phase B): K=1024, device literal sweep
    fused with the gated grouped kernel, [65,536 x 128] bytes."""
    import jax
    import jax.numpy as jnp

    import bench
    from klogs_tpu.filters.tpu import NFAEngineFilter
    from klogs_tpu.ops.pallas_nfa import match_batch_grouped_pallas
    from klogs_tpu.ops.tune import HW_DEFAULT_MASK_BLOCK

    # The engine's own table build, forced past the interpret-mode
    # auto rule (the hardware rule picks it at this K).
    monkeypatch.setenv("KLOGS_TPU_SWEEP", "1")
    f = NFAEngineFilter(bench.make_patterns(1024), kernel="interpret")
    assert f._sweep_tables is not None
    rows = 65_536
    compiled = match_batch_grouped_pallas.lower(
        _shapes(f._dp_grouped, one_chip), f._g_live, f._g_acc,
        jax.ShapeDtypeStruct((rows, 128), jnp.uint8, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
        sweep_tables=_shapes(f._sweep_tables, one_chip),
        mask_block=HW_DEFAULT_MASK_BLOCK, return_stats=True).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("first,final", [(True, False), (False, False),
                                         (False, True)])
def test_chunk_cls_kernel_at_production_tile(one_chip, first, final):
    """The long-line chunk kernel (phase C): the K=32 augmented union
    program over 4096-byte chunks, 256 long lines per batch."""
    import jax
    import jax.numpy as jnp

    import bench
    from klogs_tpu.filters.tpu import NFAEngineFilter
    from klogs_tpu.ops.pallas_nfa import match_chunk_cls_pallas

    f = NFAEngineFilter(bench.PATTERNS, kernel="interpret")
    dp = f._long_program()
    T = 4096 + (1 if first else 0) + (2 if final else 0)
    B = 256
    compiled = match_chunk_cls_pallas.lower(
        _shapes(dp, one_chip), f._acc,
        jax.ShapeDtypeStruct((B, T), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((B, dp.n_states), jnp.int8, sharding=one_chip),
        final=final).compile()
    assert _has_kernel(compiled)


def test_mesh_per_shard_kernel_on_2x2(topo):
    """The 4-chip collector path (`chip_smoke.py --chips 4`): MeshEngine
    with the per-shard Pallas kernel over the described v5e:2x2 devices,
    its verdicts sharded over all four."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bench
    from klogs_tpu.parallel.mesh import MeshEngine

    eng = MeshEngine(bench.PATTERNS, devices=topo.devices, impl="pallas")
    assert eng.grid[0] * eng.grid[1] == 4
    dp = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(eng.mesh, P("pattern"))),
        eng.dp)
    cls = jax.ShapeDtypeStruct(
        (65_536, 131), jnp.int8,
        sharding=NamedSharding(eng.mesh, P("data", None)))
    compiled = eng._fn.lower(dp, cls).compile()
    assert _has_kernel(compiled)
    (out,) = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert len(out.device_set) == 4
