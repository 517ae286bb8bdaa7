"""Sweep the device operating point: batch size x pipeline depth.

A fixed per-measurement cost and a per-dispatch cost sit on top of the
engine's per-line cost (time = c + a*dispatches + b*lines); bigger
batches and deeper pipelines amortize them. This tool measures where
the curve flattens (and where HBM/VMEM stops it).

Method matches bench.py: host-classified ids resident on device, N kernel
dispatches in flight, one block + one representative mask fetch at the
end. Appends one JSON record to OPERATING_POINT.json.

Usage:  python tools/bench_operating_point.py [--date YYYY-MM-DD]
Env:    KLOGS_OP_BATCHES (comma list, default 262144,524288,1048576)
        KLOGS_OP_FLIGHTS (comma list, default 8,16,32,64)
        KLOGS_OP_REPEATS (default 3)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from klogs_tpu.utils.env import read as env_read  # noqa: E402

import bench  # noqa: E402


def fit_runs(runs):
    """Least-squares fit of time = c + a*dispatches + b*lines.

    The constant term c (one per timed measurement: the final
    block_until_ready + mask fetch + ramp) is what makes
    throughput rise with pipeline depth at fixed batch — a model without
    it (time = a*dispatches + b*lines) predicts depth-independent
    throughput, contradicts the measured nf-dependence by up to 30%, and
    mis-attributes the fixed cost to per-dispatch overhead. With c the
    12-point residuals drop under 3%. 1/b is the engine-only ceiling."""
    import numpy as np

    A = np.array([[1.0, r["n_flight"], r["n_flight"] * r["batch"]]
                  for r in runs], dtype=np.float64)
    y = np.array([r["n_flight"] * r["batch"] / r["lps"] for r in runs])
    (c, a, b), *_ = np.linalg.lstsq(A, y, rcond=None)
    pred = A @ np.array([c, a, b])
    return {"model": "time = c + a*dispatches + b*lines",
            "per_measurement_ms": round(c * 1e3, 1),
            "per_dispatch_ms": round(a * 1e3, 3),
            "engine_only_lps": round(1.0 / b, 1) if b > 0 else None,
            "max_residual_pct": round(float(np.max(np.abs(pred - y) / y)) * 100, 1)}


def main() -> None:
    import jax
    import numpy as np

    from klogs_tpu.filters.tpu import pack_classify
    from klogs_tpu.ops import nfa
    from klogs_tpu.ops.pallas_nfa import match_cls_grouped_pallas

    batches = [int(x) for x in env_read(
        "KLOGS_OP_BATCHES", "262144,524288,1048576").split(",")]
    flights = [int(x) for x in env_read(
        "KLOGS_OP_FLIGHTS", "8,16,32,64").split(",")]
    repeats = int(env_read("KLOGS_OP_REPEATS", "3"))

    dev = jax.devices()[0]
    print(f"attached: {dev}", flush=True)

    dp, live, acc = nfa.compile_grouped(bench.PATTERNS)
    table = np.asarray(dp.byte_class).astype(np.int8)

    lines = bench.make_lines(max(batches))
    bodies = [ln.rstrip(b"\n") for ln in lines]
    t0 = time.perf_counter()
    cls_full = pack_classify(bodies, 128, table, dp.begin_class,
                             dp.end_class, dp.pad_class)
    host_prep = len(bodies) / (time.perf_counter() - t0)
    print(f"host pack_classify: {host_prep:,.0f} lines/s", flush=True)

    runs = []
    for B in batches:
        dcls = jax.device_put(cls_full[:B])
        run = lambda: match_cls_grouped_pallas(dp, live, acc, dcls)
        np.asarray(run())  # compile + warm
        for nf in flights:
            best = bench.measure_pipelined(run, B, nf, repeats)
            runs.append({"batch": B, "n_flight": nf,
                         "lps": round(best, 1)})
            print(f"batch {B:>8} x {nf:>2} in flight: "
                  f"{best:>12,.0f} lines/s", flush=True)
        del dcls

    fit = fit_runs(runs)
    print(f"fit: {fit}", flush=True)

    try:
        date = sys.argv[sys.argv.index("--date") + 1]
    except (ValueError, IndexError):
        date = time.strftime("%Y-%m-%d")
    record = {
        "date": date,
        "device": str(dev),
        "n_patterns": len(bench.PATTERNS),
        "line_width_bytes": 128,
        "host_pack_classify_lps": round(host_prep, 1),
        "runs": runs,
        "dispatch_fit": fit,
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "OPERATING_POINT.json")
    existing = []
    if os.path.exists(path):
        with open(path) as f:
            existing = json.load(f)
    existing.append(record)
    with open(path, "w") as f:
        json.dump(existing, f, indent=1)
    print(f"wrote {path}", flush=True)


if __name__ == "__main__":
    main()
