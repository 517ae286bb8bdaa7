"""One-attach-session device A/B: plain NFA kernel vs two-phase
(prefilter-gated) kernel vs candidate-mask alone, plus the (tile_b,
interleave) tune sweep — every configuration measured in the SAME
process on the SAME lines, so numbers are comparable and the prefilter
default can be decided on evidence (VERDICT r3 item 1).

Writes BENCH_DEVICE.json at the repo root:
  {"date": ..., "device": ..., "cpu_regex_lps": ...,
   "plain": {...}, "tune": [...], "gated": {...},
   "candidate_mask_only_lps": ..., "candidate_fraction": ...,
   "decision": "..."}

Method: pipelined rate (N dispatches in flight, one block), so that no
synchronous round trip per batch enters the kernel rate. Each config
reports the best of `repeats` runs.

Usage:  python tools/bench_device_ab.py          # full sweep
        KLOGS_AB_QUICK=1 python tools/...        # small batch smoke
"""

import json
import os
import sys
import time
from datetime import date

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from klogs_tpu.utils.env import read as env_read  # noqa: E402

import bench  # noqa: E402  (repo-root bench.py: PATTERNS, make_lines, cpu_lps)


def pipelined_lps(run, n_lines: int, repeats: int = 3, n_flight: int = 8) -> float:
    import numpy as np

    np.asarray(run())  # compile + warm
    best = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        outs = [run() for _ in range(n_flight)]
        outs[-1].block_until_ready()
        np.asarray(outs[-1])
        best = max(best, n_flight * n_lines / (time.perf_counter() - t0))
    return best


def main() -> None:
    quick = env_read("KLOGS_AB_QUICK") == "1"
    B = 4096 if quick else int(env_read("KLOGS_BENCH_DEVICE_BATCH", "32768"))
    repeats = 2 if quick else 3

    import jax
    import numpy as np

    dev = jax.devices()[0]
    print(f"attached: {dev.device_kind} ({jax.default_backend()})", flush=True)

    from klogs_tpu.filters.compiler.prefilter import compile_prefilter
    from klogs_tpu.filters.tpu import pack_lines
    from klogs_tpu.ops import nfa
    from klogs_tpu.ops.pallas_nfa import match_batch_grouped_pallas
    from klogs_tpu.ops.prefilter import candidate_mask, device_tables

    lines = bench.make_lines(B)
    bodies = [ln.rstrip(b"\n") for ln in lines]
    batch, lengths = pack_lines(bodies, 128)
    db, dl = jax.device_put(batch), jax.device_put(lengths)
    n = batch.shape[0]

    cpu = bench.cpu_lps(lines[: min(len(lines), 30000)], repeats)
    print(f"cpu_regex_lps: {cpu:,.0f}", flush=True)

    dp, live, acc = nfa.compile_grouped(bench.PATTERNS)
    pf = compile_prefilter(bench.PATTERNS)
    tables = device_tables(pf) if pf.usable else None

    out = {
        "date": date.today().isoformat(),
        "device": dev.device_kind,
        "batch": n,
        "line_width_bytes": 128,
        "n_patterns": len(bench.PATTERNS),
        "cpu_regex_lps": round(cpu, 1),
        "method": "pipelined, 8 in flight, best of %d" % repeats,
    }

    # --- 1. plain kernel, default config -------------------------------
    run_plain = lambda: match_batch_grouped_pallas(dp, live, acc, db, dl)
    plain_lps = pipelined_lps(run_plain, n, repeats)
    out["plain"] = {"tile_b": 4096, "interleave": 1,
                    "lps": round(plain_lps, 1),
                    "vs_cpu": round(plain_lps / cpu, 3)}
    print(f"plain default: {plain_lps:,.0f} lines/s "
          f"({plain_lps / cpu:.2f}x cpu)", flush=True)

    # --- 2. tune sweep (plain kernel) ----------------------------------
    sweep = []
    for tile in (1024, 2048, 4096, 8192):
        tile = min(tile, n)
        for il in (1, 2):
            if (tile % il) or any(r["tile_b"] == tile and r["interleave"] == il
                                  for r in sweep):
                continue
            try:
                lps = pipelined_lps(
                    lambda: match_batch_grouped_pallas(
                        dp, live, acc, db, dl, tile_b=tile, interleave=il),
                    n, repeats)
            except Exception as e:
                print(f"tile={tile} il={il} FAILED: {str(e)[:100]}", flush=True)
                continue
            sweep.append({"tile_b": tile, "interleave": il, "lps": round(lps, 1)})
            print(f"tile={tile} il={il}: {lps:,.0f} lines/s", flush=True)
    out["tune"] = sweep
    best = max(sweep, key=lambda r: r["lps"]) if sweep else out["plain"]
    out["best_plain"] = {**best, "vs_cpu": round(best["lps"] / cpu, 3)}

    # --- 3. candidate mask alone ---------------------------------------
    if tables is not None:
        cand = np.asarray(candidate_mask(tables, db, dl))
        frac = float(cand.mean())
        out["candidate_fraction"] = round(frac, 4)
        mask_lps = pipelined_lps(lambda: candidate_mask(tables, db, dl),
                                 n, repeats)
        out["candidate_mask_only_lps"] = round(mask_lps, 1)
        print(f"candidate mask alone: {mask_lps:,.0f} lines/s, "
              f"fraction {frac:.4f}", flush=True)

        # --- 4. gated kernel: default and best-plain config ------------
        def run_gated(tile, il):
            return pipelined_lps(
                lambda: match_batch_grouped_pallas(
                    dp, live, acc, db, dl, tile_b=tile, interleave=il,
                    prefilter_tables=tables),
                n, repeats)

        try:
            g_def = run_gated(4096, 1)
            out["gated"] = {"tile_b": 4096, "interleave": 1,
                            "lps": round(g_def, 1),
                            "vs_cpu": round(g_def / cpu, 3)}
            print(f"gated default: {g_def:,.0f} lines/s "
                  f"({g_def / cpu:.2f}x cpu)", flush=True)
        except Exception as e:
            out["gated"] = {"error": str(e)[:200]}
            print(f"gated default FAILED: {str(e)[:120]}", flush=True)
        if (best["tile_b"], best["interleave"]) != (4096, 1) and \
                "error" not in out.get("gated", {}):
            try:
                g_best = run_gated(best["tile_b"], best["interleave"])
                out["gated_best_tile"] = {
                    "tile_b": best["tile_b"], "interleave": best["interleave"],
                    "lps": round(g_best, 1), "vs_cpu": round(g_best / cpu, 3)}
                print(f"gated best-tile: {g_best:,.0f} lines/s", flush=True)
            except Exception as e:
                print(f"gated best-tile FAILED: {str(e)[:120]}", flush=True)

        # --- 5. smaller gated tile: skip granularity is the tile size,
        # so a smaller tile may win when candidates are sparse ----------
        for tile in (512, 1024):
            if tile >= n:
                continue
            try:
                g = run_gated(tile, 1)
                out[f"gated_tile{tile}"] = {"tile_b": tile, "interleave": 1,
                                            "lps": round(g, 1)}
                print(f"gated tile={tile}: {g:,.0f} lines/s", flush=True)
            except Exception as e:
                print(f"gated tile={tile} FAILED: {str(e)[:120]}", flush=True)
    else:
        out["candidate_fraction"] = None
        print("prefilter not usable for this pattern set", flush=True)

    # --- decision -------------------------------------------------------
    gated_all = [v["lps"] for k, v in out.items()
                 if k.startswith("gated") and isinstance(v, dict) and "lps" in v]
    best_gated = max(gated_all) if gated_all else 0.0
    if best_gated > out["best_plain"]["lps"] * 1.05:
        out["decision"] = ("prefilter ON: best gated %.0f > best plain %.0f "
                           "(+5%% margin)" % (best_gated, out["best_plain"]["lps"]))
    else:
        out["decision"] = ("prefilter OFF by default: best gated %.0f vs best "
                           "plain %.0f — gating overhead (LUT gathers + argsort "
                           "+ reorder) not paid back at this candidate fraction"
                           % (best_gated, out["best_plain"]["lps"]))
    print("DECISION:", out["decision"], flush=True)

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_DEVICE.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}", flush=True)


if __name__ == "__main__":
    main()
