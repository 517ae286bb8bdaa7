"""Long-run randomized fuzz of the pattern compiler vs the `re` oracle.

Deeper and wider than tests/test_compiler.py's property tests (which run
in seconds on every pytest invocation): richer alphabet, deeper nesting,
mid-pattern anchors, {m,n} up to 6, ignore-case trials, and — on a
subsample (engine checks pay a jit compile per pattern set) — the full
grouped interpret-kernel path through pack_classify, i.e. exactly the
production TPU hot path run hermetically on CPU.

Every divergence found historically became a unit test in
tests/test_compiler.py (e.g. the possessive-quantifier reject, commit
d491db4); run this after compiler changes and before releases.

Usage: python tools/fuzz_compiler.py [--trials N] [--seed S] [--engine-every K]
Exit 1 on any divergence, with a repro line printed.
"""

import argparse
import os
import random
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # hermetic: engines compared on the CPU

from klogs_tpu.filters.compiler import (  # noqa: E402
    RegexSyntaxError,
    compile_patterns,
    reference_match,
)
from klogs_tpu.filters.cpu import DFAFilter  # noqa: E402

ALPHABET = b"ab01 .-XY\t/=:\xc3\x28\n"  # \n: DOTALL edge
CLASS_BODIES = ["ab", "a-c", "0-9a", "^ab", "^0-9", "b-", "]a", "a-zA-Z",
                "^\\d", "\\w-", ".*+", "^^", "0-9-"]
ESCAPES = [r"\d", r"\D", r"\w", r"\W", r"\s", r"\S", r"\.", r"\-", r"\t",
           r"\x41", r"\x00", r"\(", r"\)", r"\[", r"\|", r"\{", r"\+"]


def rand_pattern(rng: random.Random, depth: int = 0) -> str:
    choices = ["lit", "lit", "lit", "class", "dot", "escape", "anchor",
               "boundary"]
    if depth < 4:
        choices += ["cat", "cat", "cat", "alt", "alt", "star", "plus",
                    "opt", "count", "group", "lazy"]
    kind = rng.choice(choices)
    if kind == "lit":
        return re.escape(chr(rng.choice(b"ab01 XY/=:")))
    if kind == "dot":
        return "."
    if kind == "anchor":
        return rng.choice(["^", "$", r"\A", r"\Z"])
    if kind == "boundary":
        return rng.choice([r"\b", r"\b", r"\B"])
    if kind == "escape":
        return rng.choice(ESCAPES)
    if kind == "class":
        return f"[{rng.choice(CLASS_BODIES)}]"
    if kind == "cat":
        return rand_pattern(rng, depth + 1) + rand_pattern(rng, depth + 1)
    if kind == "alt":
        return f"(?:{rand_pattern(rng, depth + 1)}|{rand_pattern(rng, depth + 1)})"
    if kind == "group":
        opener = rng.choice(["(", "(", "(", "(?i:", "(?-i:",
                             "(?s:", "(?-s:", "(?si:", "(?i-s:",
                             f"(?P<g{rng.randrange(1000)}>"])
        inner = rand_pattern(rng, depth + 1)
        if rng.random() < 0.1:  # comments are lexical splices
            inner += "(?#c)"
        return f"{opener}{inner})"
    inner = rand_pattern(rng, depth + 1)
    if not inner or inner[-1] in "*+?}":
        inner = f"(?:{inner})"
    if kind == "star":
        return inner + "*"
    if kind == "plus":
        return inner + "+"
    if kind == "opt":
        return inner + "?"
    if kind == "lazy":
        return inner + rng.choice(["*?", "+?", "??"])
    lo = rng.randrange(0, 4)
    hi = rng.randrange(lo, lo + 3)
    return rng.choice([f"{inner}{{{lo},{hi}}}", f"{inner}{{{lo},}}",
                       f"{inner}{{{max(lo,1)}}}"])


def rand_line(rng: random.Random) -> bytes:
    # Trailing newlines are stripped: the engine contract matches on
    # newline-stripped bodies (framer output), and re's $-before-
    # trailing-\n rule differs from the END sentinel by design.
    # INTERIOR \n stays — that is the (?s)/DOTALL coverage.
    n = rng.randrange(0, 24)
    return bytes(rng.choice(ALPHABET) for _ in range(n)).rstrip(b"\n")


def oracle(patterns, line: bytes, flags: int = 0) -> bool:
    return any(re.search(p.encode("utf-8"), line, flags) for p in patterns)


class OracleTimeout(Exception):
    """Python re is a backtracking engine: generated patterns like
    nested starred groups go exponential on the right line, and one
    oracle call can outlive the whole sweep (observed: >400s on a
    24-byte line, seed 1785396679 trial ~2xxx — while reference_match
    and the production NFA kernel, both worst-case linear, answer the
    same pattern in microseconds). Trials whose ground truth cannot be
    established within the budget are skipped, not hung on."""


def _alarm(signum, frame):
    raise OracleTimeout


def safe_oracle(patterns, line: bytes, flags: int, budget_s: float = 2.0):
    import signal

    signal.signal(signal.SIGALRM, _alarm)
    signal.setitimer(signal.ITIMER_REAL, budget_s)
    try:
        return oracle(patterns, line, flags)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)


def engine_check(pats, lines, ignore_case, chunk_bytes=4096,
                 mask_block=None, exclude=None):
    """Full production path hermetically: pack_classify -> grouped
    interpret kernel. Returns the verdict list. A small chunk_bytes
    routes longer lines through the carried-state chunk protocol
    (classify_chunk_host + match_chunk_cls_pallas), the subtlest path
    in the engine (END deferral across chunk boundaries).
    ``mask_block`` opts the full-line kernel into the K-step
    mask-precompute restructuring (KLOGS_TPU_MASK_BLOCK) so the fuzz
    also covers that variant's T-padding path. Ambient tuning knobs
    that would conflict with (or silently alter) the selected variant
    are stashed for the duration of the check and restored after."""
    from klogs_tpu.filters.tpu import NFAEngineFilter

    knobs = ("KLOGS_TPU_MASK_BLOCK", "KLOGS_TPU_INTERLEAVE",
             "KLOGS_TPU_FUSED_GROUPS")
    saved = {k: os.environ.pop(k, None) for k in knobs}
    if mask_block:
        os.environ["KLOGS_TPU_MASK_BLOCK"] = str(mask_block)
    try:
        if exclude:
            from klogs_tpu.filters.base import build_include_exclude

            filt = build_include_exclude(
                lambda p: NFAEngineFilter(p, ignore_case=ignore_case,
                                          kernel="interpret",
                                          chunk_bytes=chunk_bytes),
                pats, exclude)
        else:
            filt = NFAEngineFilter(pats, ignore_case=ignore_case,
                                   kernel="interpret",
                                   chunk_bytes=chunk_bytes)
        return filt.match_lines(lines)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=20000)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--engine-every", type=int, default=200,
                    help="run the interpret-kernel path on every Kth trial")
    args = ap.parse_args()
    seed = args.seed if args.seed is not None else int(time.time())
    rng = random.Random(seed)
    print(f"fuzz: seed={seed} trials={args.trials}", flush=True)

    t0 = time.time()
    checked = skipped = engine_runs = backtracked = dfa_runs = 0
    for trial in range(args.trials):
        k = rng.randrange(1, 5)
        pats = [rand_pattern(rng) for _ in range(k)]
        ignore_case = rng.random() < 0.25
        flags = re.IGNORECASE if ignore_case else 0
        try:
            for p in pats:
                re.compile(p.encode("utf-8"), flags)
        except re.error:
            skipped += 1
            continue  # not valid re either: nothing to compare
        try:
            prog = compile_patterns(pats, ignore_case=ignore_case)
        except RegexSyntaxError:
            skipped += 1  # outside the supported subset (rejected loudly)
            continue
        lines = [rand_line(rng) for _ in range(12)] + [b""]
        try:
            expects = [safe_oracle(pats, ln, flags) for ln in lines]
        except OracleTimeout:
            backtracked += 1  # re blew up; NFA ground truth unverifiable
            continue
        for line, expect in zip(lines, expects):
            got = reference_match(prog, line)
            if got != expect:
                print(f"DIVERGENCE (reference_match): seed={seed} "
                      f"trial={trial} patterns={pats!r} ignore_case="
                      f"{ignore_case} line={line!r} nfa={got} re={expect}",
                      flush=True)
                return 1
            checked += 1
        # The strong-CPU DFA engine (subset construction over the same
        # compiler artifacts + native scan) against the same ground
        # truth. Tiny cap: pathological determinizations should skip,
        # not stall the sweep.
        try:
            dfa = DFAFilter(pats, ignore_case=ignore_case,
                            max_states=2048, cache=False)
        except (ValueError, RegexSyntaxError):
            dfa = None  # cap overflow (ValueError) only; the subset
            # was already accepted by compile_patterns above
        if dfa is not None:
            got_dfa = dfa.match_lines(list(lines))
            if got_dfa != expects:
                bad = next(i for i in range(len(lines))
                           if got_dfa[i] != expects[i])
                print(f"DIVERGENCE (dfa engine): seed={seed} "
                      f"trial={trial} patterns={pats!r} ignore_case="
                      f"{ignore_case} line={lines[bad]!r} "
                      f"dfa={got_dfa[bad]} re={expects[bad]}", flush=True)
                return 1
            dfa_runs += 1
        if args.engine_every and trial % args.engine_every == 0:
            # Mix in lines several times the (shrunken) chunk width, so
            # the carried-state chunk protocol crosses many boundaries;
            # line lengths straddle the chunk edge (±2) to hit the
            # END-at-boundary corner exactly.
            long_lines = []
            for _ in range(4):
                target = rng.choice((255, 256, 257, 511, 512, 513, 700,
                                     1100, 2048))
                raw = bytearray(rng.choice(ALPHABET) for _ in range(target))
                # Engine contract strips trailing \n; REPLACE trailing
                # newlines instead so the chunk-boundary target length
                # (255/256/257/...) is preserved exactly.
                i = len(raw)
                while i and raw[i - 1] == 0x0A:
                    raw[i - 1] = 0x61  # 'a'
                    i -= 1
                long_lines.append(bytes(raw))
            try:
                long_expects = [safe_oracle(pats, ln, flags, 5.0)
                                for ln in long_lines]
            except OracleTimeout:
                # re blew up on a long line: keep the short-line engine
                # check (its ground truth is already verified) so
                # backtracking-prone sets still get engine coverage.
                backtracked += 1
                long_lines, long_expects = [], []
            all_lines = lines + long_lines
            all_expects = expects + long_expects
            mb = rng.choice((None, None, 2, 4, 8))
            # Sometimes split the set: last pattern(s) become EXCLUDES
            # (keep = any(include) and not any(exclude)) — the
            # IncludeExcludeFilter combinator under the full grammar.
            exc = []
            if len(pats) >= 2 and rng.random() < 0.3:
                n_exc = rng.randrange(1, len(pats))
                inc_pats, exc = pats[:-n_exc], pats[-n_exc:]
            else:
                inc_pats = pats
            if exc:
                try:
                    all_expects = [
                        e and not safe_oracle(exc, ln, flags)
                        for e, ln in zip(
                            [safe_oracle(inc_pats, ln, flags)
                             for ln in all_lines], all_lines)]
                except OracleTimeout:
                    # Ground truth for the split is unobtainable; test
                    # the undivided set instead of crashing the sweep.
                    backtracked += 1
                    inc_pats, exc = pats, []
            verdicts = engine_check(inc_pats, all_lines, ignore_case,
                                    chunk_bytes=256, mask_block=mb,
                                    exclude=exc)
            if verdicts != all_expects:
                bad = next(i for i in range(len(all_lines))
                           if verdicts[i] != all_expects[i])
                bad_line = all_lines[bad]
                shown = (f"{bad_line[:120]!r}..." if len(bad_line) > 120
                         else repr(bad_line))
                print(f"DIVERGENCE (interpret kernel): seed={seed} "
                      f"trial={trial} patterns={inc_pats!r} exclude={exc!r} "
                      f"ignore_case="
                      f"{ignore_case} mask_block={mb} len={len(bad_line)} "
                      f"line={shown} "
                      f"kernel={verdicts[bad]} re={all_expects[bad]}",
                      flush=True)
                return 1
            engine_runs += 1
        if trial and trial % 2000 == 0:
            print(f"  {trial} trials, {checked} line-checks, "
                  f"{engine_runs} engine sets, {skipped} skipped, "
                  f"{backtracked} oracle-timeouts, {time.time()-t0:.0f}s",
                  flush=True)

    print(f"fuzz OK: {checked} line-checks across {args.trials} trials "
          f"({skipped} outside subset/invalid, {backtracked} re-backtrack "
          f"timeouts — the linear-time NFA has no such blowup), "
          f"{engine_runs} interpret-kernel + {dfa_runs} dfa pattern sets, "
          f"{time.time()-t0:.0f}s, seed={seed}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
