"""Cold-start discipline: jax must stay un-imported off the filter
paths (BASELINE round-5 status: 126ms `klogs -v` — only holds while
nothing on the non-filter path drags jax in)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_probe(code: str) -> str:
    res = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-1500:]
    return res.stdout


def test_version_path_imports_no_heavy_modules():
    out = _run_probe("""
import sys
sys.argv = ["klogs", "-v"]
import runpy
try:
    runpy.run_module("klogs_tpu.cli", run_name="__main__")
except SystemExit:
    pass
for mod in ("jax", "numpy", "aiohttp", "grpc"):
    assert mod not in sys.modules, f"{mod} imported on -v path"
print("clean")
""")
    assert "clean" in out


def test_unfiltered_fetch_imports_no_jax():
    out = _run_probe("""
import os, sys, tempfile
os.environ.update(KLOGS_FAKE_PODS="2", KLOGS_FAKE_LINES="10")
out_dir = tempfile.mkdtemp()
sys.argv = ["klogs", "-a", "--cluster", "fake", "-p", out_dir]
import runpy
try:
    runpy.run_module("klogs_tpu.cli", run_name="__main__")
except SystemExit:
    pass
assert "jax" not in sys.modules, "jax imported on unfiltered fetch"
assert os.path.exists(os.path.join(out_dir, "pod-0000__c0.log"))
print("clean")
""")
    assert "clean" in out


def test_cpu_filtered_fetch_imports_no_jax():
    """--backend=cpu (the DFA engine) must not touch jax either."""
    out = _run_probe("""
import os, sys, tempfile
os.environ.update(KLOGS_FAKE_PODS="2", KLOGS_FAKE_LINES="10")
out_dir = tempfile.mkdtemp()
sys.argv = ["klogs", "-a", "--cluster", "fake", "--match", "ERROR",
            "--backend", "cpu", "-p", out_dir]
import runpy
try:
    runpy.run_module("klogs_tpu.cli", run_name="__main__")
except SystemExit:
    pass
assert "jax" not in sys.modules, "jax imported on cpu filter path"
print("clean")
""")
    assert "clean" in out
