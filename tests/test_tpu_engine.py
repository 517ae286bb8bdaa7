"""JAX batch-NFA engine ≡ host regex, including the chunked long-line
path (SURVEY.md §4: Pallas/engine tested hermetically on CPU; §5
long-context: carried NFA state across chunks of a line)."""

import random
import re

import pytest

from klogs_tpu.filters.cpu import RegexFilter
from klogs_tpu.filters.tpu import NFAEngineFilter, pack_lines
from tests.test_compiler import CASES, _rand_line, _rand_pattern, oracle


KERNELS = ["jnp", "interpret"]  # interpret = the Pallas kernel, interpreted


def group_cases():
    """CASES grouped by pattern set so each group is one batched call."""
    groups: dict[tuple, list] = {}
    for patterns, line, expected in CASES:
        groups.setdefault(tuple(patterns), []).append((line, expected))
    return groups.items()


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("patterns,pairs", list(group_cases()),
                         ids=lambda v: repr(v)[:40])
def test_hand_cases_batched(patterns, pairs, kernel):
    f = NFAEngineFilter(list(patterns), kernel=kernel)
    lines = [line for line, _ in pairs]
    expected = [e for _, e in pairs]
    assert f.match_lines(lines) == expected


def test_trailing_newline_stripped():
    f = NFAEngineFilter(["foo$"])
    assert f.match_lines([b"a foo\n", b"a foo", b"foo bar\n"]) == [True, True, False]


def test_mixed_length_bucketing():
    """Lines spanning several pad buckets in one call keep their order."""
    f = NFAEngineFilter(["needle"])
    lines = [
        b"x" * n + (b"needle" if n % 3 == 0 else b"nope") + b"y" * (n % 7)
        for n in [0, 1, 50, 120, 130, 200, 300, 511, 513, 1000]
    ]
    expect = RegexFilter(["needle"]).match_lines(lines)
    assert f.match_lines(lines) == expect


def test_framed_bucket_widths_clamped_to_chunk_bytes():
    """dispatch_framed's width buckets must match _bucket_len exactly:
    clamped to chunk_bytes, so a non-power-of-two chunk_bytes never
    mints an EXTRA jit shape above it (extra compile + padding on every
    top-bucket batch)."""
    from klogs_tpu.filters.base import frame_lines
    from klogs_tpu.filters.tpu import _bucket_len
    from klogs_tpu.native import hostops

    if hostops is None or not hasattr(hostops, "pack_classify_framed"):
        pytest.skip("native framed packer unavailable")
    f = NFAEngineFilter(["needle"], kernel="interpret", chunk_bytes=3000)
    assert f._use_cls()
    lines = [b"short needle", b"x" * 300 + b"needle",
             b"y" * 2500 + b"needle", b"z" * 2999]
    seen_widths = []
    orig = hostops.pack_classify_framed

    def spy(payload, offsets, n, sel, width, rows, *rest):
        seen_widths.append(width)
        return orig(payload, offsets, n, sel, width, rows, *rest)

    hostops.pack_classify_framed = spy
    try:
        payload, offsets, _ = frame_lines(lines)
        got = f.fetch_framed(f.dispatch_framed(payload, offsets))
    finally:
        hostops.pack_classify_framed = orig
    assert got.tolist() == RegexFilter(["needle"]).match_lines(lines)
    # Every bucket ≤ chunk_bytes, and each equals the list-path rule.
    assert seen_widths and all(w <= 3000 for w in seen_widths)
    assert sorted(seen_widths) == sorted(
        {_bucket_len(len(ln), 3000) for ln in lines})


def test_match_all_shortcut():
    f = NFAEngineFilter(["a|"])  # nullable alternative → match-all
    assert f.match_lines([b"", b"zzz", b"x" * 5000]) == [True, True, True]


@pytest.fixture(params=KERNELS)
def kernel(request):
    return request.param


class TestLongLines:
    """chunk_bytes=16 so chunk boundaries are cheap to hit; runs on both
    the jnp path and the Pallas kernel (interpret)."""

    @pytest.fixture(autouse=True)
    def _kernel(self, kernel):
        self.kernel = kernel

    def mk(self, patterns):
        return NFAEngineFilter(patterns, chunk_bytes=16, kernel=self.kernel)

    def test_match_spans_chunk_boundary(self):
        f = self.mk(["needle"])
        line = b"x" * 13 + b"needle" + b"y" * 30  # straddles bytes 13..19
        assert f.match_lines([line]) == [True]
        assert f.match_lines([b"x" * 13 + b"needl" + b"y" * 30]) == [False]

    def test_anchors_on_long_lines(self):
        f = self.mk(["^start", "end$"])
        assert f.match_lines([b"start" + b"x" * 40]) == [True]
        assert f.match_lines([b"x" * 40 + b"end"]) == [True]
        assert f.match_lines([b"x" + b"start" + b"x" * 40]) == [False]
        assert f.match_lines([b"x" * 40 + b"end" + b"x"]) == [False]

    def test_length_exactly_at_chunk_boundary(self):
        # END sentinel lands exactly on a chunk seam (rem == L deferral).
        f = self.mk(["end$"])
        for total in (16, 32, 48, 17, 31):
            line = b"x" * (total - 3) + b"end"
            assert f.match_lines([line]) == [True], total
            assert f.match_lines([line + b"z"]) == [False], total

    def test_mixed_long_lengths_lockstep(self):
        f = self.mk([r"ab{3}c"])
        ok = b"z" * 20 + b"abbbc" + b"z" * 100
        no = b"z" * 20 + b"abbc" + b"z" * 200
        short_ok = b"abbbc"
        assert f.match_lines([ok, no, short_ok]) == [True, False, True]

    def test_star_across_many_chunks(self):
        f = self.mk(["a[0-9]*b"])
        line = b"a" + b"7" * 100 + b"b"
        assert f.match_lines([line]) == [True]
        assert f.match_lines([b"a" + b"7" * 100 + b"x" + b"b"]) == [False]


def test_pack_lines():
    batch, lengths = pack_lines([b"ab", b"", b"xyz"], 4)
    assert batch.shape == (8, 4)  # batch axis padded to the 8-row bucket
    assert lengths.tolist()[:3] == [2, 0, 3]
    assert lengths.tolist()[3:] == [0] * 5
    assert batch[0, :2].tobytes() == b"ab"
    assert batch[2, :3].tobytes() == b"xyz"


def test_batch_bucketing_slices_pad_rows():
    # "^$" matches the empty pad rows — verdicts must be sliced off.
    f = NFAEngineFilter(["^$"])
    assert f.match_lines([b"x", b"", b"yy"]) == [False, True, False]


def test_trailing_newlines_all_stripped():
    # rstrip parity with RegexFilter on multi-\n endings.
    f = NFAEngineFilter(["foo$"])
    r = RegexFilter(["foo$"])
    lines = [b"foo\n\n", b"foo\n", b"foo", b"foo\nx"]
    assert f.match_lines(lines) == r.match_lines(lines)


def test_utf8_pattern_agrees_with_cpu():
    lines = ["error: café down\n".encode("utf-8"), b"error: cafe down\n"]
    assert NFAEngineFilter(["café"]).match_lines(lines) == \
        RegexFilter(["café"]).match_lines(lines) == [True, False]


@pytest.mark.parametrize("kernel", KERNELS)
def test_property_vs_regex_filter(kernel):
    """Random patterns × random mixed-length batches vs RegexFilter —
    the end-to-end analog of test_compiler's oracle property test."""
    rng = random.Random(99)
    tested = 0
    for _ in range(40):
        k = rng.randrange(1, 4)
        pats = [_rand_pattern(rng) for _ in range(k)]
        pats = [
            ("^" if rng.random() < 0.2 else "") + p + ("$" if rng.random() < 0.2 else "")
            for p in pats
        ]
        try:
            for p in pats:
                re.compile(p.encode("latin-1"))
            f = NFAEngineFilter(pats, chunk_bytes=32, kernel=kernel)
        except (ValueError, re.error):
            continue
        lines = [_rand_line(rng) for _ in range(12)]
        # A few long lines to force the chunk path alongside short ones.
        lines += [
            bytes(rng.choice(b"ab0 .-") for _ in range(rng.randrange(33, 90)))
            for _ in range(3)
        ]
        expect = [oracle(pats, ln) for ln in lines]
        got = f.match_lines(lines)
        assert got == expect, f"patterns={pats!r}"
        tested += len(lines)
    assert tested > 200


def test_empty_batch():
    assert NFAEngineFilter(["x"]).match_lines([]) == []


def test_binary_lines_and_nul_bytes():
    """Log lines are opaque bytes (io.Copy in the reference): NUL and
    high bytes must flow through matching unharmed."""
    pats = ["café", r"a\x00b", "日本"]
    lines = [b"xx caf\xc3\xa9 yy", b"a\x00b", b"\x00\x01\x02",
             "日本語".encode(), b"cafe", bytes(range(256))]
    for kernel in KERNELS:
        f = NFAEngineFilter(pats, kernel=kernel)
        assert f.match_lines(lines) == RegexFilter(pats).match_lines(lines)


def test_many_class_fallback_to_device_classify(monkeypatch):
    """A shared classifier wider than int8 (>127 classes) must fall back
    to the device-classify path, not overflow the host cls table."""
    from klogs_tpu.filters.cpu import RegexFilter
    from klogs_tpu.filters.tpu import NFAEngineFilter
    from klogs_tpu.ops import nfa as nfa_mod

    real = nfa_mod.compile_grouped

    def wide(pats, **kw):
        kw["classes_pad"] = 136  # force past the int8 id ceiling
        return real(pats, **kw)

    monkeypatch.setattr(nfa_mod, "compile_grouped", wide)
    pats = ["ERROR", "panic:", r"code=\d+"]
    f = NFAEngineFilter(pats, kernel="interpret")
    assert f._cls_table is None  # host classification declined
    lines = [b"ERROR x", b"fine", b"panic: y", b"code=77", b"code=x"] * 10
    assert f.match_lines(lines) == RegexFilter(pats).match_lines(lines)


def test_defaulted_chain_variant_degrades_to_plain(monkeypatch, capsys):
    """The hardware-default mask_block=4 chain is compile-fragile on
    unproven backends (K=8/16 already fail Mosaic on v5e): a failure of
    the DEFAULTED variant must degrade to the plain chain and keep the
    run alive, and later batches must skip the broken variant."""
    import klogs_tpu.ops.pallas_nfa as pallas_nfa
    import klogs_tpu.ops.tune as tune
    from klogs_tpu.filters.tpu import NFAEngineFilter

    monkeypatch.setattr(
        tune, "chain_selection",
        lambda on_hardware, allow_fused=True: ({"mask_block": 4}, True,
                                               False))
    real = pallas_nfa.match_cls_grouped_pallas
    seen = []

    def fragile(*args, **kw):
        seen.append(kw.get("mask_block", 1))
        if kw.get("mask_block", 1) > 1:
            raise RuntimeError("Mosaic rejected the restructured chain")
        return real(*args, **kw)

    monkeypatch.setattr(pallas_nfa, "match_cls_grouped_pallas", fragile)
    f = NFAEngineFilter(["ERROR"], kernel="interpret")
    assert f.match_lines([b"ERROR x", b"clean"]) == [True, False]
    assert "continuing on the plain chain" in capsys.readouterr().out
    assert f._chain_fallback
    # Later batches run the plain chain directly — no repeat failures.
    assert f.match_lines([b"ERROR y"]) == [True]
    assert seen[-1] == 1


@pytest.mark.parametrize("platforms,want", [
    ("cpu", "jnp"), (" cpu ", "jnp"), ("", None), ("tpu", None),
    ("cpu,tpu", None)])
def test_resolve_kernel_refuses_an_unasked_cpu(monkeypatch, platforms, want):
    """kernel=auto runs jnp on the CPU only when JAX_PLATFORMS names the
    CPU alone; otherwise a CPU backend means the TPU is missing."""
    import jax

    from klogs_tpu.filters.tpu import DeviceUnavailable, resolve_kernel

    jax.devices()  # the backend is the CPU before the variable changes
    monkeypatch.delenv("KLOGS_TPU_KERNEL", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    if want is None:
        with pytest.raises(DeviceUnavailable, match="needs a TPU"):
            resolve_kernel()
    else:
        assert resolve_kernel() == want
    monkeypatch.setenv("KLOGS_TPU_KERNEL", "mosaic")
    with pytest.raises(ValueError, match="KLOGS_TPU_KERNEL"):
        resolve_kernel()


@pytest.mark.parametrize("entry", ["collector", "filterd"])
def test_backend_tpu_exits_without_a_tpu(monkeypatch, tmp_path, capsys,
                                         entry):
    """--backend tpu on a CPU backend, JAX_PLATFORMS not asking for the
    CPU: both entry points exit non-zero and name the missing device
    instead of carrying on at jnp speed."""
    import sys

    import jax

    from klogs_tpu.utils import cache

    jax.devices()
    monkeypatch.setenv("JAX_PLATFORMS", "")
    monkeypatch.setattr(cache, "enable_compile_cache", lambda: None)
    if entry == "collector":
        from klogs_tpu.cli import main

        rc = main(["-a", "--cluster", "fake", "--match", "ERROR",
                   "--backend", "tpu", "-p", str(tmp_path)])
    else:
        from klogs_tpu.service.__main__ import main

        monkeypatch.setattr(sys, "argv", ["klogs-filterd", "--match",
                                          "ERROR", "--port", "0"])
        with pytest.raises(SystemExit) as exc:
            main()
        rc = exc.value.code
    assert rc == 1
    out = capsys.readouterr()
    assert "needs a TPU, but JAX found none" in out.out + out.err


def _fragile_grouped(real, fail_when):
    def fragile(*args, **kw):
        if fail_when(kw):
            raise RuntimeError("Mosaic rejected the kernel")
        return real(*args, **kw)
    return fragile


class _FailsAtFetch:
    """A device result whose execution fails when it is fetched."""

    def __array__(self, *a, **kw):
        raise RuntimeError("device execution failed")


@pytest.mark.parametrize("path", ["sweep_build", "sweep_kernel",
                                  "gated_kernel", "chain", "fetch"])
def test_every_degrade_path_counts(monkeypatch, path):
    """Each kernel that gives way bumps klogs_engine_degrade_total
    under its own path label (fetch: an asynchronous failure, counted
    beside the path its rerun degraded), and the verdicts stay right."""
    import klogs_tpu.ops.pallas_nfa as pallas_nfa
    import klogs_tpu.ops.tune as tune
    from klogs_tpu.filters.base import FilterStats
    from klogs_tpu.obs.metrics import Registry
    from klogs_tpu.ops import sweep as sweep_mod

    pats = ["ERROR 00[0-9]7", "WARN disk", "user=[a-z]+ failed"]
    lines = [b"x ERROR 0007 boom", b"nothing", b"WARN disk full",
             b"user=bob failed", b""]
    want = RegexFilter(pats).match_lines(lines)
    expect = {path: 1}
    if path in ("sweep_build", "sweep_kernel"):
        monkeypatch.setenv("KLOGS_TPU_SWEEP", "1")
    if path == "sweep_build":
        def boom(prog):
            raise RuntimeError("injected build fault")
        monkeypatch.setattr(sweep_mod, "device_sweep_tables", boom)
    elif path == "sweep_kernel":
        monkeypatch.setattr(pallas_nfa, "match_batch_grouped_pallas",
                            _fragile_grouped(
                                pallas_nfa.match_batch_grouped_pallas,
                                lambda kw: kw.get("sweep_tables") is not None))
    elif path == "gated_kernel":
        monkeypatch.setenv("KLOGS_TPU_PREFILTER", "1")
        monkeypatch.setattr(pallas_nfa, "match_cls_grouped_pallas",
                            _fragile_grouped(
                                pallas_nfa.match_cls_grouped_pallas,
                                lambda kw: kw.get("prefilter_tables")
                                is not None))
    else:
        # The hardware default chain (mask_block=4) on the interpreter.
        monkeypatch.setattr(
            tune, "chain_selection",
            lambda on_hardware, allow_fused=True: ({"mask_block": 4}, True,
                                                   False))
        real = pallas_nfa.match_cls_grouped_pallas
        if path == "chain":
            fake = _fragile_grouped(real, lambda kw: kw["mask_block"] > 1)
        else:
            expect["chain"] = 1

            def fake(*args, **kw):
                if kw["mask_block"] > 1:
                    return _FailsAtFetch()
                return real(*args, **kw)
        monkeypatch.setattr(pallas_nfa, "match_cls_grouped_pallas", fake)
    reg = Registry()
    f = NFAEngineFilter(pats, kernel="interpret",
                        stats=FilterStats(registry=reg))
    assert f.match_lines(lines) == want
    assert f.match_lines(lines) == want  # degraded once, not per batch
    fam = reg.family("klogs_engine_degrade_total")
    got = {lv[0]: c.value for lv, c in fam.children() if c.value}
    assert got == expect
    swept = reg.family("klogs_sweep_fallback_total").value
    assert swept == (1 if path.startswith("sweep") else 0)


def test_env_forced_chain_variant_stays_loud(monkeypatch):
    """An operator-forced variant must fail loudly, not silently run a
    different kernel (the pick-by-measurement rule)."""
    import klogs_tpu.ops.pallas_nfa as pallas_nfa
    from klogs_tpu.filters.tpu import NFAEngineFilter

    monkeypatch.setenv("KLOGS_TPU_MASK_BLOCK", "4")

    def fragile(*args, **kw):
        if kw.get("mask_block", 1) > 1:
            raise RuntimeError("Mosaic rejected the restructured chain")
        raise AssertionError("env-forced variant must not silently degrade")

    monkeypatch.setattr(pallas_nfa, "match_cls_grouped_pallas", fragile)
    f = NFAEngineFilter(["ERROR"], kernel="interpret")
    with pytest.raises(RuntimeError, match="Mosaic rejected"):
        f.match_lines([b"ERROR x"])
