"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform *before* jax is first
imported anywhere, so multi-chip sharding (mesh axes data x pattern) is
exercised hermetically without TPU hardware, per SURVEY.md §4.
"""

import os

# Force, don't setdefault: the ambient environment may name the TPU,
# and tests must stay hermetic. JAX reads both variables when it first
# initializes a backend, which no test module does at import.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

from klogs_tpu.ui import term  # noqa: E402


@pytest.fixture(autouse=True)
def _no_colors():
    """Deterministic plain output in tests unless a test opts in."""
    term.set_colors(False)
    yield
    term.set_colors(None)


async def http_get(port: int, path: str) -> tuple[int, bytes]:
    """Raw-socket GET against a localhost obs sidecar -> (status,
    body). Shared by test_obs and test_service so the sidecar's
    response framing is asserted in exactly one shape."""
    import asyncio

    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), body
