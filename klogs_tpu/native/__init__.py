"""Native host-ops loader: compile-on-first-use with Python fallback.

The extension is a single C file with no dependencies beyond CPython;
building it is one cc invocation, done lazily and cached next to the
source — or, when the package directory is not writable (installed
site-packages owned by root, or the single-file klogs.pyz zipapp where
the "directory" is inside a zip), under ``~/.cache/klogs-tpu`` keyed by
a hash of the C source, so every build of the artifact gets its own
cached object. Environments without a toolchain (or where the build
fails for any reason) silently fall back to the pure-Python
implementations — the native layer is a fast path, never a requirement.

Set KLOGS_NO_NATIVE=1 to force the fallback (used by tests to cover
both paths).
"""

import hashlib
import os
import subprocess
import sysconfig

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "_hostops.c")
_EXT = sysconfig.get_config_var("EXT_SUFFIX") or ".so"

hostops = None


def _read_source() -> "bytes | None":
    """C source bytes — from the filesystem, or from inside the zipapp
    via the package loader when there is no real file."""
    try:
        with open(_SRC, "rb") as f:
            return f.read()
    except OSError:
        pass
    try:
        import importlib.resources

        return (importlib.resources.files(__package__)
                .joinpath("_hostops.c").read_bytes())
    except Exception:
        return None


def _cache_path(src: bytes) -> str:
    from klogs_tpu.utils.cache import cache_dir

    tag = hashlib.sha256(src).hexdigest()[:16]
    return os.path.join(cache_dir(), f"_hostops-{tag}{_EXT}")


def _build(c_src: str, so_path: str) -> bool:
    """Compile to a pid-suffixed temp and os.replace into place: the
    cache can be shared by many concurrently-starting processes, and a
    half-written .so observed by another process would silently pin it
    to the pure-Python fallback for its lifetime."""
    include = sysconfig.get_paths()["include"]
    cc = os.environ.get("CC", "cc")
    tmp = f"{so_path}.tmp{os.getpid()}"
    cmd = [cc, "-O3", "-shared", "-fPIC", "-pthread", f"-I{include}",
           c_src, "-o", tmp]
    try:
        res = subprocess.run(cmd, capture_output=True, timeout=120)
        if res.returncode != 0:
            return False
        os.replace(tmp, so_path)
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        try:
            os.remove(tmp)
        except OSError:
            pass


def build(so_path: str) -> bool:
    """Compile ``_hostops.c`` into ``so_path`` now. chip_smoke.py builds
    a fresh copy and pins it (KLOGS_NATIVE_SO), so that no untracked
    .so left in a copied tree decides a chip run."""
    return _build(_SRC, so_path)


def _ensure_so() -> "str | None":
    """Path to an up-to-date compiled extension, building if needed.
    Preference order: next to the source (repo checkouts — mtime keeps
    it fresh), else the user cache keyed by source hash (read-only
    installs and zipapps)."""
    in_tree = os.path.join(_DIR, f"_hostops{_EXT}")
    src_exists = os.path.exists(_SRC)
    if src_exists and os.path.exists(in_tree) and (
            os.path.getmtime(_SRC) <= os.path.getmtime(in_tree)):
        return in_tree
    if src_exists and os.access(_DIR, os.W_OK):
        return in_tree if _build(_SRC, in_tree) else None
    # Read-only package (or zipapp): build into the user cache.
    src = _read_source()
    if src is None:
        return None
    cached = _cache_path(src)
    if os.path.exists(cached):
        return cached
    try:
        os.makedirs(os.path.dirname(cached), exist_ok=True)
    except OSError:
        return None
    tmp_c = cached[: -len(_EXT)] + ".c"
    try:
        with open(tmp_c, "wb") as f:
            f.write(src)
    except OSError:
        return None
    return cached if _build(tmp_c, cached) else None


def _load():
    global hostops
    from klogs_tpu.utils.env import read as _env_read

    if _env_read("KLOGS_NO_NATIVE"):
        return
    # KLOGS_NATIVE_SO pins the exact extension binary to load — the
    # sanitizer harness (tools/build_native_asan.py, docs/NATIVE.md)
    # uses it to run the parity tests against an ASan/UBSan build. A
    # pinned path that fails to load raises instead of silently
    # falling back: a sanitizer run that quietly tested the pure-
    # Python path would green-light memory bugs.
    forced = _env_read("KLOGS_NATIVE_SO")
    so = forced if forced else _ensure_so()
    if so is None:
        return
    try:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "klogs_tpu.native._hostops", so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        hostops = mod
    except Exception as e:
        hostops = None
        if forced:
            raise RuntimeError(
                f"KLOGS_NATIVE_SO={forced!r} could not be loaded: {e}"
            ) from e


_load()
