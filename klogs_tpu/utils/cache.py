"""Cache directories.

- ``cache_dir``: the one host-cache resolver (native .so builds, DFA
  tables): XDG_CACHE_HOME else ~/.cache, under a klogs-tpu namespace,
  so the two caches can never end up in different places.
- ``enable_compile_cache``: JAX's persistent compilation cache for the
  entry points that drive the device, plus the process's compile-time
  count that their device block reports.
"""

import os
import threading

from klogs_tpu.utils.env import jax_cpu_requested

# <checkout>/.jax_cache: one fixed path, because the directory is part
# of what a later process must find again — a temp name, pid or time in
# it would never hit.
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def cache_dir() -> str:
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "klogs-tpu")


def compile_cache_dir() -> "str | None":
    """Where ``enable_compile_cache`` points JAX's compile cache, or
    None when it sets nothing: JAX_COMPILATION_CACHE_DIR already places
    it (JAX reads that itself), or JAX_PLATFORMS=cpu marks a test or
    rehearsal, whose CPU programs do not belong in the checkout."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or jax_cpu_requested():
        return None
    return JAX_CACHE_DIR


class CompileClock:
    """Seconds spent in XLA backend compiles and persistent-cache hits,
    fed by JAX's process-wide monitoring events. A cache hit still
    reports a (short) backend-compile duration: the retrieval."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._seconds = 0.0
        self._hits = 0
        self._listening = False

    def listen(self) -> None:
        import jax

        with self._lock:
            if self._listening:
                return
            self._listening = True
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            with self._lock:
                self._seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            with self._lock:
                self._hits += 1

    def doc(self) -> dict:
        """{} until ``listen`` ran (a count that never started must not
        read as zero compile time)."""
        with self._lock:
            if not self._listening:
                return {}
            return {"compile_s": self._seconds,
                    "compile_cache_hits": self._hits}


# Process-wide, like the JAX monitoring listeners that feed it.
COMPILES = CompileClock()


def enable_compile_cache() -> "str | None":
    """Called once by each entry point that drives the device (the CLI
    and filterd with --backend tpu, bench.py's device child), before
    its first compile; never at import. Sets ``jax_compilation_cache_dir``
    to ``compile_cache_dir()`` when that is not None, starts the
    compile clock either way, and returns the directory in use."""
    import jax

    COMPILES.listen()
    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
        return path
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
