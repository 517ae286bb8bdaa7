"""End-to-end --match runs: the filter stage gating file writes, on
both the cpu (host regex) and tpu (batch NFA) backends — the full
north-star slice over FakeCluster."""

import asyncio
import os

import pytest

from klogs_tpu import app
from klogs_tpu.cli import parse_args
from klogs_tpu.cluster.fake import FakeCluster


def run_app(argv, backend, stop=None):
    opts = parse_args(argv)
    return asyncio.run(app.run_async(opts, backend=backend, stop=stop))


def make_cluster(lines=80):
    # Frozen clock: identical line content across runs, so cpu-vs-tpu
    # output comparison is byte-exact (timestamps are embedded in lines).
    return FakeCluster.synthetic(
        n_pods=3, n_containers=1, lines_per_container=lines,
        clock=lambda: 1_753_800_000.0,
    )


def read_all(out_dir):
    out = {}
    for f in sorted(os.listdir(out_dir)):
        with open(os.path.join(out_dir, f), "rb") as fh:
            out[f] = fh.read().splitlines(keepends=True)
    return out


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_match_gates_writes(tmp_path, backend):
    out_dir = str(tmp_path / backend)
    rc = run_app(
        ["-n", "default", "-a", "-p", out_dir,
         "--match", "INFO", "--backend", backend],
        make_cluster(),
    )
    assert rc == 0
    files = read_all(out_dir)
    assert len(files) == 3
    total = 0
    for lines in files.values():
        for ln in lines:
            assert b"INFO" in ln
        total += len(lines)
    assert total > 0, "filter dropped everything — fake stream has INFO lines"


def test_cpu_and_tpu_agree(tmp_path):
    outs = {}
    for backend in ("cpu", "tpu"):
        out_dir = str(tmp_path / backend)
        rc = run_app(
            ["-n", "default", "-a", "-p", out_dir,
             "--match", r"(?:ERROR|WARN).*\d", "--backend", backend],
            make_cluster(),
        )
        assert rc == 0
        outs[backend] = read_all(out_dir)
    assert outs["cpu"] == outs["tpu"]


def test_stats_summary_printed(tmp_path, capsys):
    out_dir = str(tmp_path / "logs")
    rc = run_app(
        ["-n", "default", "-a", "-p", out_dir,
         "--match", "INFO", "--backend", "tpu", "--stats"],
        make_cluster(),
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "Filter stats:" in out
    assert "lines/sec" in out


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_stats_json_device_block(tmp_path, backend):
    """--stats-json names the device a --backend tpu run filtered on
    (here the suite's deliberate CPU run, on the jnp kernel); the host
    backend has no device block."""
    import json

    import jax

    stats = tmp_path / "stats.json"
    rc = run_app(
        ["-n", "default", "-a", "-p", str(tmp_path / "logs"),
         "--match", "INFO", "--backend", backend,
         "--stats-json", str(stats)],
        make_cluster(),
    )
    assert rc == 0
    doc = json.loads(stats.read_text())
    if backend == "cpu":
        assert "device" not in doc
        return
    dev = doc["device"]
    assert (dev["platform"], dev["kernel"]) == ("cpu", "jnp")
    assert dev["count"] == jax.device_count()
    assert dev["device_kind"] == jax.devices()[0].device_kind


def test_multiple_match_patterns_union(tmp_path):
    out_dir = str(tmp_path / "logs")
    rc = run_app(
        ["-n", "default", "-a", "-p", out_dir,
         "--match", "ERROR", "--match", "WARN", "--backend", "tpu"],
        make_cluster(),
    )
    assert rc == 0
    for lines in read_all(out_dir).values():
        for ln in lines:
            assert b"ERROR" in ln or b"WARN" in ln
