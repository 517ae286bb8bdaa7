"""Long-line stress: tens-of-KB lines (hundreds of chunk crossings) through the chunked
carried-state path, mixed with short lines, on both execution paths —
the long-context scaling story (SURVEY.md §5) at realistic sizes."""

import random

import pytest

from klogs_tpu.filters.cpu import RegexFilter
from klogs_tpu.filters.tpu import NFAEngineFilter


@pytest.mark.parametrize("kernel", ["jnp", "interpret"])
def test_100kb_lines_match_parity(kernel):
    rng = random.Random(3)
    filler = bytes(rng.choice(b"abcdefgh ") for _ in range(20_000))
    lines = [
        filler[:10_000] + b"needle in the middle" + filler[10_000:],
        filler,  # no needle
        b"needle early" + filler,
        filler + b"needle at end",
        b"short needle",
        b"",
    ]
    pats = ["needle"]
    f = NFAEngineFilter(pats, chunk_bytes=2048, kernel=kernel)
    assert f.match_lines(lines) == RegexFilter(pats).match_lines(lines)


def test_anchored_on_long_lines():
    n = 40_000
    body = b"z" * n
    pats = ["^BEGIN", "END$", r"^\d{4}"]
    lines = [
        b"BEGIN" + body,
        body + b"END",
        b"2026" + body,
        b"x" + b"BEGIN" + body,          # ^BEGIN must not fire mid-line
        body + b"END" + b"x",            # END$ must not fire before tail
    ]
    f = NFAEngineFilter(pats, chunk_bytes=4096)
    assert f.match_lines(lines) == RegexFilter(pats).match_lines(lines) == [
        True, True, True, False, False,
    ]


def test_pattern_spanning_many_chunks():
    # A bounded repeat long enough to span several 1 KiB chunks keeps
    # carried NFA state correct across >100 chunk boundaries.
    pats = [r"a[0-9]{600}b"]
    digits = bytes(random.Random(7).choice(b"0123456789") for _ in range(600))
    good = b"x" * 500 + b"a" + digits + b"b" + b"y" * 20_000
    bad = b"x" * 500 + b"a" + digits[:-1] + b"qb" + b"y" * 20_000
    f = NFAEngineFilter(pats, chunk_bytes=512)
    expect = RegexFilter(pats).match_lines([good, bad])
    assert f.match_lines([good, bad]) == expect == [True, False]


@pytest.mark.parametrize("kernel", [
    "jnp",
    # interpret runs the same routing ~90s slower; tier-1 keeps jnp.
    pytest.param("interpret", marks=pytest.mark.slow),
])
def test_huge_lines_route_to_seqscan(kernel, monkeypatch):
    """Lines past SEQ_SCAN_BYTES take the sequence-parallel path and
    still agree with the host regex, mixed with short/long lines."""
    monkeypatch.setattr(NFAEngineFilter, "SEQ_SCAN_BYTES", 8192)
    pats = ["needle", "tail$"]
    huge_hit = b"q" * 20_000 + b"needle" + b"q" * 20_000
    huge_tail = b"q" * 30_000 + b"tail"
    huge_miss = b"q" * 40_000
    lines = [b"short needle", huge_hit, b"q" * 5000, huge_tail, huge_miss]
    f = NFAEngineFilter(pats, chunk_bytes=2048, kernel=kernel)
    assert f.match_lines(lines) == RegexFilter(pats).match_lines(lines)


def test_chunk_kernel_non_divisible_batch():
    """match_chunk_pallas pads internally: a non-power-of-two long-line
    batch that doesn't divide the tile must work end to end."""
    import numpy as np

    from klogs_tpu.filters.compiler.glushkov import compile_patterns
    from klogs_tpu.filters.cpu import RegexFilter
    from klogs_tpu.ops import nfa
    from klogs_tpu.ops.pallas_nfa import initial_state_kernel, match_chunk_pallas

    import jax.numpy as jnp

    pats = ["mark[0-9]+x"]
    prog = compile_patterns(pats)
    dp = nfa.pack_program(nfa.augment(prog), dtype=jnp.int8)
    live, acc = prog.n_states, prog.n_states + 1
    L = 256
    rng = random.Random(9)
    bodies = []
    for i in range(5):  # 5 rows, tile 4 -> pad to 8
        n = rng.randrange(300, 700)
        b = bytes(rng.choice(b"qrs tuv") for _ in range(n))
        if i % 2:
            cut = rng.randrange(0, n)
            b = b[:cut] + b"mark33x" + b[cut:]
        bodies.append(b)
    total = np.array([len(b) for b in bodies], dtype=np.int32)
    n_chunks = int(np.ceil(total.max() / L))
    v = initial_state_kernel(dp, live, len(bodies))
    for k in range(n_chunks):
        seg = [b[k * L : (k + 1) * L].ljust(L, b"\0") for b in bodies]
        chunk = np.frombuffer(b"".join(seg), dtype=np.uint8).reshape(-1, L)
        v, matched = match_chunk_pallas(
            dp, acc, chunk, total - k * L, v,
            first=(k == 0), final=(k == n_chunks - 1),
            tile_b=4, interpret=True)
    assert np.asarray(matched).tolist() == RegexFilter(pats).match_lines(bodies)


def test_host_chunk_classify_equals_device():
    """classify_chunk_host must be byte-identical to the device
    classify_chunk + latch across first/mid/final chunks, including
    END deferral at rem == L and already-ended (rem < 0) rows."""
    import numpy as np

    import jax.numpy as jnp

    from klogs_tpu.filters.compiler.glushkov import compile_patterns
    from klogs_tpu.filters.tpu import classify_chunk_host
    from klogs_tpu.ops import nfa
    from klogs_tpu.ops.nfa import classify_chunk

    prog = compile_patterns(["needle", "x$"])
    dp = nfa.pack_program(nfa.augment(prog), dtype=jnp.int8)
    table = np.asarray(dp.byte_class).astype(np.int8)
    L = 16
    rng = random.Random(21)
    chunk = np.frombuffer(
        bytes(rng.choice(b"nedlx qz") for _ in range(6 * L)),
        dtype=np.uint8).reshape(6, L)
    # rem covers: already ended, ends mid-chunk, ends at L (deferral),
    # continues past, exactly 0 (END at position 0), and negative big.
    rem = np.array([-5, 7, L, L + 9, 0, -1], dtype=np.int32)
    for first in (True, False):
        for final in (True, False):
            host = classify_chunk_host(chunk, rem, table,
                                       dp.begin_class, dp.end_class,
                                       dp.pad_class, first=first, final=final)
            dev = np.asarray(classify_chunk(dp, chunk, rem,
                                            first=first, final=final))
            if final:  # host includes the accept-latch column
                assert (host[:, -1] == dp.pad_class).all()
                host_cmp = host[:, :-1]
            else:
                host_cmp = host
            assert (host_cmp.astype(np.int32) == dev).all(), (first, final)


def test_long_lines_host_cls_path_vs_oracle():
    """NFAEngineFilter long-line path now runs host-classified chunks;
    verdicts must match the regex oracle across many chunk boundaries."""
    from klogs_tpu.filters.cpu import RegexFilter
    from klogs_tpu.filters.tpu import NFAEngineFilter

    pats = ["needle[0-9]x", "END$"]
    f = NFAEngineFilter(pats, chunk_bytes=256, kernel="interpret")
    f._long_program()
    assert f._aug_cls_table is not None
    rng = random.Random(13)
    lines = []
    for i in range(7):
        n = rng.randrange(300, 2500)
        b = bytes(rng.choice(b"abc defg") for _ in range(n))
        if i % 2:
            cut = rng.randrange(0, n)
            b = b[:cut] + b"needle7x" + b[cut:]
        if i % 3 == 0:
            b += b"END"
        lines.append(b)
    assert f.match_lines(lines) == RegexFilter(pats).match_lines(lines)
