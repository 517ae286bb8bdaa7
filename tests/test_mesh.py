"""Mesh-sharded engine semantics on the hermetic 8-device CPU mesh
(conftest forces xla_force_host_platform_device_count=8): data x pattern
sharding must be invisible in results (≡ RegexFilter)."""

import random
import re

import numpy as np
import pytest

import jax

from klogs_tpu.filters.cpu import RegexFilter
from klogs_tpu.filters.tpu import NFAEngineFilter
from klogs_tpu.parallel.mesh import MeshEngine, choose_grid, split_patterns
from tests.test_compiler import _rand_line, _rand_pattern, oracle


def test_eight_virtual_devices():
    assert jax.device_count() == 8, "conftest must force an 8-device CPU mesh"


@pytest.mark.parametrize("n_dev,n_pat,expect", [
    (8, 32, (4, 2)),
    (8, 2, (4, 2)),
    (8, 1, (8, 1)),
    (1, 5, (1, 1)),
    (8, 3, (4, 2)),
    (4, 4, (2, 2)),
])
def test_choose_grid(n_dev, n_pat, expect):
    d, g = choose_grid(n_dev, n_pat)
    assert d * g == n_dev
    assert (d, g) == expect


def test_split_patterns_balanced():
    groups = split_patterns([f"p{i}" for i in range(7)], 3)
    assert sorted(len(g) for g in groups) == [2, 2, 3]
    assert sorted(sum(groups, [])) == sorted(f"p{i}" for i in range(7))


@pytest.mark.parametrize("impl", ["gspmd", "shard_map", "pallas_interpret"])
@pytest.mark.parametrize("grid", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_mesh_grids_agree_with_cpu(grid, impl):
    pats = ["ERROR", r"WARN.*\d", "^2026", "timeout$", "a+b", "x{3}"]
    eng = MeshEngine(pats, grid=grid, impl=impl)
    f = NFAEngineFilter(pats, engine=eng)
    lines = [
        b"2026 ERROR x", b"all good", b"WARN 42", b"request timeout",
        b"aab", b"ab" * 40, b"", b"xxx", b"xx",
        b"2026-07-29 WARN latency=9",
    ]
    assert f.match_lines(lines) == RegexFilter(pats).match_lines(lines)


def test_uneven_batch_padding():
    eng = MeshEngine(["foo"], grid=(8, 1))
    f = NFAEngineFilter(["foo"], engine=eng)
    # 3 lines over an 8-wide data axis: padded rows must be sliced off.
    assert f.match_lines([b"foo", b"bar", b"xfoo"]) == [True, False, True]


def test_more_shards_than_patterns_replicates():
    eng = MeshEngine(["only"], grid=(2, 4))
    f = NFAEngineFilter(["only"], engine=eng)
    assert f.match_lines([b"the only one", b"nope"]) == [True, False]


def test_property_mesh_vs_oracle():
    rng = random.Random(7)
    tested = 0
    for _ in range(15):
        k = rng.randrange(1, 6)
        pats = [_rand_pattern(rng) for _ in range(k)]
        try:
            for p in pats:
                re.compile(p.encode())
            eng = MeshEngine(pats, grid=(4, 2))
            f = NFAEngineFilter(pats, engine=eng)
        except (ValueError, re.error):
            continue
        lines = [_rand_line(rng) for _ in range(21)]  # uneven on purpose
        assert f.match_lines(lines) == [oracle(pats, ln) for ln in lines]
        tested += 1
    assert tested >= 8


def test_mixed_match_all_across_pattern_shards_pallas():
    # ADVICE r1 medium: 'a*' (match_all) in one shard + 'ERROR' in the
    # other used to raise 'Mismatch custom node data' at construction
    # because match_all is pytree aux data and differed across shards.
    eng = MeshEngine(["a*", "ERROR"], grid=(4, 2), impl="pallas_interpret")
    f = NFAEngineFilter(["a*", "ERROR"], engine=eng)
    # a* matches every line (zero-width), so everything passes.
    assert f.match_lines([b"ERROR x", b"clean"]) == [True, True]


def test_mixed_match_all_agrees_across_impls():
    for impl in ("gspmd", "shard_map", "pallas_interpret"):
        eng = MeshEngine(["a*", "ERROR"], grid=(4, 2), impl=impl)
        f = NFAEngineFilter(["a*", "ERROR"], engine=eng)
        assert f.match_lines([b"zzz"]) == [True], impl


def test_pallas_shard_non_divisible_local_batch():
    # B=24 over 8 data shards -> local batch 3; the kernel wrapper pads
    # to its tile internally (VERDICT r1 item 5).
    eng = MeshEngine(["needle"], grid=(8, 1), impl="pallas_interpret")
    f = NFAEngineFilter(["needle"], engine=eng)
    lines = [(b"needle %d" % i) if i % 3 == 0 else (b"hay %d" % i)
             for i in range(24)]
    assert f.match_lines(lines) == [i % 3 == 0 for i in range(24)]


def test_pallas_mesh_with_prefilter_optin(monkeypatch):
    """Opt-in two-phase gating inside shard_map (per-shard class
    tables): verdicts identical to the host oracle on the virtual
    mesh — the gated kernel now runs under dryrun conditions too."""
    monkeypatch.setenv("KLOGS_TPU_PREFILTER", "1")
    import numpy as np

    from klogs_tpu.filters.cpu import RegexFilter

    pats = ["panic:", "code=50[34]", "FATAL|CRIT", r"retry \d+/\d+",
            "broken pipe", "oom-killer"]
    devices = jax.devices()[:4]
    eng = MeshEngine(pats, devices=devices, grid=(2, 2),
                     impl="pallas_interpret")
    from klogs_tpu.filters.tpu import pack_lines

    lines = [b"panic: x", b"fine here", b"code=504 y", b"CRIT",
             b"retry 9/9", b"a broken pipe", b"oom-killer hit", b""] * 5
    batch, lengths = pack_lines(lines, 32)
    batch, lengths = batch[: len(lines)], lengths[: len(lines)]
    got = np.asarray(eng.match_batch(batch, lengths))[: len(lines)]
    assert got.tolist() == RegexFilter(pats).match_lines(lines)


def test_engine_filter_routes_cls_to_mesh():
    """NFAEngineFilter with a pallas MeshEngine ships host-classified
    ids straight to match_cls (the multi-chip hot path)."""
    import numpy as np

    from klogs_tpu.filters.cpu import RegexFilter
    from klogs_tpu.filters.tpu import NFAEngineFilter

    pats = ["ERROR", r"WARN.*\d", "panic:"]
    devices = jax.devices()[:4]
    eng = MeshEngine(pats, devices=devices, grid=(2, 2),
                     impl="pallas_interpret")
    assert eng.cls_table is not None
    f = NFAEngineFilter(pats, engine=eng, kernel="interpret")
    lines = [b"ERROR x", b"ok", b"WARN q 7", b"panic: z", b"WARN but none"] * 8
    assert f.match_lines(lines) == RegexFilter(pats).match_lines(lines)


def test_boundary_patterns_across_pattern_shards():
    """\\b/\\B automata carry extra context/boundary-check positions and
    BEGIN/END sentinel memberships; pattern-sharded stacking
    (stack_programs re-lays classes) and the mesh hot path must
    preserve them."""
    pats = [r"\berror\b", r"code=50[34]", r"warn\B", r"\bFATAL",
            r"x\d+\b"]
    eng = MeshEngine(pats, grid=(4, 2))
    f = NFAEngineFilter(pats, engine=eng)
    lines = [b"error", b"errors", b"an error.", b"code=503", b"warned",
             b"warn", b"FATAL x", b"xFATAL", b"x42", b"x42y", b"", b"-"] * 2
    assert f.match_lines(lines) == [oracle(pats, ln) for ln in lines]


def test_exclude_with_mesh_engines():
    """make_pipeline on a multi-device backend builds BOTH the include
    and exclude sides as MeshEngines; the two sharded automata must
    coexist and the combined verdicts must match re."""
    import re as _re

    from klogs_tpu.filters.sink import make_pipeline

    p = make_pipeline(["ERROR", r"\bpanic\b"], "tpu", exclude=["healthz"])
    lines = [b"ERROR up", b"ERROR healthz", b"panic: x", b"panics",
             b"healthz ok", b"fine"] * 4
    got = p.log_filter.match_lines(lines)
    want = [(bool(_re.search(rb"ERROR", ln) or _re.search(rb"\bpanic\b", ln))
             and not _re.search(rb"healthz", ln)) for ln in lines]
    assert got == want
    p.close()


def test_mesh_defaulted_chain_degrades_to_plain(monkeypatch, capsys):
    """A DEFAULTED chain variant that fails to compile on the mesh path
    rebuilds both fns on the plain chain instead of killing the run."""
    import klogs_tpu.ops.pallas_nfa as pallas_nfa
    import klogs_tpu.ops.tune as tune

    monkeypatch.setattr(
        tune, "chain_selection",
        lambda on_hardware, allow_fused=True: ({"mask_block": 4}, True,
                                               False))
    real = pallas_nfa.match_cls_grouped_pallas

    def fragile(*args, **kw):
        if kw.get("mask_block", 1) > 1:
            raise RuntimeError("Mosaic rejected the restructured chain")
        return real(*args, **kw)

    monkeypatch.setattr(pallas_nfa, "match_cls_grouped_pallas", fragile)
    eng = MeshEngine(["ERROR"], grid=(4, 2), impl="pallas_interpret")
    assert eng._chain_defaulted
    f = NFAEngineFilter(["ERROR"], engine=eng)
    assert f.match_lines([b"ERROR x", b"clean"]) == [True, False]
    assert "rebuilding with the plain chain" in capsys.readouterr().out
    assert eng._vkw["mask_block"] == 1
    # Degrade is sticky: the next batch runs the rebuilt fns directly.
    assert f.match_lines([b"more ERROR"]) == [True]


def test_mesh_drops_fused_loudly_and_reapplies_default(monkeypatch, capsys):
    """KLOGS_TPU_FUSED_GROUPS=1 has no mesh per-shard variant: dropping
    it must warn (pick-by-measurement rule), and with the chain then
    unpicked the measured hardware default re-applies."""
    from klogs_tpu.ops.tune import HW_DEFAULT_MASK_BLOCK

    monkeypatch.setenv("KLOGS_TPU_FUSED_GROUPS", "1")
    # impl="pallas" (interpret=False) exercises the hardware branch;
    # construction only builds the jitted wrappers, nothing compiles.
    eng = MeshEngine(["ERROR"], grid=(4, 2), impl="pallas")
    assert "no mesh per-shard variant" in capsys.readouterr().out
    assert "fused" not in eng._vkw
    assert eng._vkw["mask_block"] == HW_DEFAULT_MASK_BLOCK

    # On the interpret impl the plain chain is kept (no hardware
    # default), but the warning still fires.
    eng2 = MeshEngine(["ERROR"], grid=(4, 2), impl="pallas_interpret")
    assert "no mesh per-shard variant" in capsys.readouterr().out
    assert "mask_block" not in eng2._vkw


@pytest.mark.parametrize("path", ["chain", "sweep_kernel", "sweep_build"])
def test_mesh_degrades_report_through_on_degrade(monkeypatch, path):
    """A kernel that gives way inside the mesh is reported by path to
    the on_degrade hook (filters/sink.py wires it to the stats), once,
    and the verdicts stay right."""
    import klogs_tpu.ops.pallas_nfa as pallas_nfa
    import klogs_tpu.ops.tune as tune
    from klogs_tpu.ops import sweep as sweep_mod

    pats = ["ERROR", r"WARN.*\d", "svc-[0-9]+ down", "timeout$"]
    lines = [b"ERROR x", b"fine", b"WARN 42", b"svc-7 down", b"a timeout"]
    if path == "chain":
        monkeypatch.setattr(
            tune, "chain_selection",
            lambda on_hardware, allow_fused=True: ({"mask_block": 4}, True,
                                                   False))
        real = pallas_nfa.match_cls_grouped_pallas

        def fragile(*a, **kw):
            if kw.get("mask_block", 1) > 1:
                raise RuntimeError("Mosaic rejected the chain")
            return real(*a, **kw)
        monkeypatch.setattr(pallas_nfa, "match_cls_grouped_pallas", fragile)
    else:
        monkeypatch.setenv("KLOGS_TPU_SWEEP", "1")
    if path == "sweep_kernel":
        real_b = pallas_nfa.match_batch_grouped_pallas

        def boom(*a, **kw):
            if kw.get("sweep_tables") is not None:
                raise RuntimeError("injected sweep fault")
            return real_b(*a, **kw)
        monkeypatch.setattr(pallas_nfa, "match_batch_grouped_pallas", boom)
    elif path == "sweep_build":
        def boom_build(progs):
            raise RuntimeError("injected build fault")
        monkeypatch.setattr(sweep_mod, "stack_sweep_tables", boom_build)
    seen = []
    eng = MeshEngine(pats, grid=(4, 2), impl="pallas_interpret",
                     on_degrade=seen.append)
    f = NFAEngineFilter(pats, engine=eng, kernel="interpret")
    want = RegexFilter(pats).match_lines(lines)
    assert f.match_lines(lines) == want
    assert f.match_lines(lines) == want
    assert seen == [path]
    assert eng.placement()["verdict_devices"] == 8
