"""The single-file klogs.pyz artifact: build it, run it, check the
version stamp and that no bytecode droppings inflate it (release.yml
publishes exactly this)."""

import os
import subprocess
import sys
import zipfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(tmp_path, version=None):
    env = dict(os.environ)
    env.pop("KLOGS_BUILD_VERSION", None)
    if version:
        env["KLOGS_BUILD_VERSION"] = version
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "build_pyz.py"),
         str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=120)
    assert res.returncode == 0, res.stderr[-1500:]
    return os.path.join(str(tmp_path), "klogs.pyz")


def test_pyz_builds_and_runs(tmp_path):
    pyz = _build(tmp_path, version="v0.0.0-test")
    with zipfile.ZipFile(pyz) as z:
        names = z.namelist()
    assert "__main__.py" in names
    assert not [n for n in names if n.endswith(".pyc")]
    env = dict(os.environ)
    env.pop("KLOGS_BUILD_VERSION", None)  # the BAKED stamp must answer
    res = subprocess.run([sys.executable, pyz, "-v"],
                         capture_output=True, text=True, env=env,
                         timeout=60)
    assert res.returncode == 0, res.stderr[-800:]
    assert "v0.0.0-test" in res.stdout + res.stderr


def test_pyz_runs_filtered_fetch(tmp_path):
    pyz = _build(tmp_path)
    out_dir = tmp_path / "logs"
    env = dict(os.environ)
    env.update(KLOGS_FAKE_PODS="2", KLOGS_FAKE_LINES="20")
    res = subprocess.run(
        [sys.executable, pyz, "-a", "--cluster", "fake", "--match",
         "ERROR", "--backend", "cpu", "-p", str(out_dir)],
        capture_output=True, text=True, env=env, timeout=120)
    assert res.returncode == 0, res.stderr[-1500:]
    data = (out_dir / "pod-0000__c0.log").read_bytes()
    assert data and all(b"ERROR" in ln for ln in data.splitlines())
