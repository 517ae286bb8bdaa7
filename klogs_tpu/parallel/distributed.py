"""Multi-host initialization for the filter mesh.

Single-host multi-chip needs nothing: ``jax.devices()`` sees every local
chip and MeshEngine builds its (data, pattern) mesh over them, with the
pattern-OR collective riding ICI.

Multi-host (e.g. v5e-16+ pods, or a filterd fleet spanning hosts) uses
jax's standard distributed runtime over DCN: every process calls
``initialize()`` before first jax use, after which ``jax.devices()``
is the GLOBAL device list and the same MeshEngine code shards over all
hosts — collectives ride ICI within a slice and DCN across hosts, laid
out by XLA from the mesh axes (scaling-book recipe; nothing here is
host-count-aware).

The reference is strictly single-process (one Go binary, SURVEY.md §2);
this is the subsystem its design never needed but the TPU architecture
makes first-class.

Environment-driven (the TPU runtime populates these on Cloud TPU pods;
set them manually elsewhere):
  KLOGS_COORDINATOR   host:port of process 0 (else jax defaults apply)
  KLOGS_NUM_PROCESSES total process count
  KLOGS_PROCESS_ID    this process's index

CPU fleets: cross-process collectives ride jax's gloo backend (the
default `jax_cpu_collectives_implementation`), with the platform
pinned (JAX_PLATFORMS=cpu) before the first backend init. Validated
live by tests/test_distributed.py's two-controller run. Without a
metadata server (a sealed TPU host, a laptop), pass the coordinator
address, process count and id explicitly: called without them,
jax.distributed looks them up and fails.
"""

import jax

from klogs_tpu.utils.env import read as _env_read


def initialize(coordinator: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> None:
    """Idempotent jax.distributed bring-up. No-ops when the environment
    describes a single process."""
    coordinator = coordinator or _env_read("KLOGS_COORDINATOR")
    num_processes = num_processes or _int_env("KLOGS_NUM_PROCESSES")
    process_id = process_id if process_id is not None else _int_env("KLOGS_PROCESS_ID")
    if num_processes in (None, 1):
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def _int_env(name: str) -> int | None:
    v = _env_read(name)
    return int(v) if v else None
