"""Source-subsystem suite (docs/SOURCES.md contracts).

Covers the PR 18 source abstraction end to end: replay rotation/
truncation/resume semantics, archive decompression framing parity
against a line-by-line oracle, the named-error classes for damaged
archives, socket backpressure-by-construction, ClusterSource
conformance (the kube path is byte-identical through the adapter),
chaos source.read faults absorbed by the shared reconnect policy, and
the backfill-vs-follow byte-parity acceptance property on a rotated +
gzipped set.
"""

import asyncio
import gzip
import os
import zlib

import pytest

from klogs_tpu.cluster.fake import FakeCluster
from klogs_tpu.cluster.types import LogOptions
from klogs_tpu.resilience import FAULTS
from klogs_tpu.runtime import fanout as fanout_mod
from klogs_tpu.runtime.fanout import FanoutRunner, plan_source_jobs
from klogs_tpu.sources.archive import (
    ArchiveSource,
    ArchiveStream,
    group_archives,
    strip_compress_ext,
)
from klogs_tpu.sources.base import SourceError, SourceRef
from klogs_tpu.sources.cluster import ClusterSource
from klogs_tpu.sources.replay import ReplaySource
from klogs_tpu.sources.socket import SocketSource


def run(coro, timeout=20):
    return asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.clear()
    FAULTS.bind_registry(None)
    yield
    FAULTS.clear()
    FAULTS.bind_registry(None)


@pytest.fixture(autouse=True)
def _fast_backoff(monkeypatch):
    monkeypatch.setattr(fanout_mod, "_BACKOFF_BASE_S", 0.01)
    monkeypatch.setattr(fanout_mod, "_BACKOFF_MAX_S", 0.05)


async def _collect(stream) -> bytes:
    out = bytearray()
    async for chunk in stream:
        out += chunk
    await stream.close()
    return bytes(out)


def _fast_replay(paths, **kw):
    kw.setdefault("poll_interval_s", 0.01)
    return ReplaySource(paths, **kw)


# ---- replay: rotation / truncation / resume --------------------------


def test_replay_batch_reads_whole_file_newline_aligned(tmp_path):
    p = tmp_path / "a.log"
    body = b"".join(b"line %04d x\n" % i for i in range(500)) + b"partial"
    p.write_bytes(body)
    src = _fast_replay([str(p)], read_size=256)

    async def scenario():
        refs = await src.discover()
        assert [r.target for r in refs] == [str(p)]
        chunks = []
        stream = await src.open_stream(refs[0], LogOptions(follow=False))
        async for chunk in stream:
            chunks.append(chunk)
        await stream.close()
        return chunks

    chunks = run(scenario())
    assert b"".join(chunks) == body
    # Every slab except the EOF-flushed tail is newline-cut.
    for c in chunks[:-1]:
        assert c.endswith(b"\n")


def test_replay_rotation_rename_drains_old_fd_then_follows_new(tmp_path):
    """logrotate move: EOF + changed inode -> drain the old fd
    (bytes written between our last read and the rename survive),
    then pick up the successor from offset 0."""
    p = tmp_path / "app.log"
    p.write_bytes(b"".join(b"old %03d\n" % i for i in range(50)))
    src = _fast_replay([str(p)], read_size=128)

    async def scenario():
        refs = await src.discover()
        stream = await src.open_stream(refs[0], LogOptions(follow=True))
        got = bytearray()
        it = stream.__aiter__()
        while b"old 049\n" not in got:
            got += await it.__anext__()
        # Rotate: append a straggler the reader hasn't seen, rename,
        # then write the successor file.
        with open(p, "ab") as f:
            f.write(b"straggler\n")
        os.rename(p, tmp_path / "app.log.1")
        p.write_bytes(b"")
        with open(p, "ab") as f:
            f.write(b"".join(b"new %03d\n" % i for i in range(20)))
        while b"new 019\n" not in got:
            got += await it.__anext__()
        await stream.close()
        return bytes(got)

    got = run(scenario())
    assert got.count(b"straggler\n") == 1, "old-fd remainder lost or duped"
    assert got.index(b"straggler\n") < got.index(b"new 000\n")
    for i in range(50):
        assert got.count(b"old %03d\n" % i) == 1
    for i in range(20):
        assert got.count(b"new %03d\n" % i) == 1


def test_replay_copytruncate_reopens_at_zero(tmp_path):
    p = tmp_path / "app.log"
    p.write_bytes(b"aaaa\nbbbb\ncccc\n")
    src = _fast_replay([str(p)])

    async def scenario():
        refs = await src.discover()
        stream = await src.open_stream(refs[0], LogOptions(follow=True))
        got = bytearray()
        it = stream.__aiter__()
        while b"cccc\n" not in got:
            got += await it.__anext__()
        # copytruncate: size drops below our position, same inode.
        p.write_bytes(b"")
        with open(p, "ab") as f:
            f.write(b"dddd\n")
        while b"dddd\n" not in got:
            got += await it.__anext__()
        await stream.close()
        return bytes(got)

    got = run(scenario())
    assert got == b"aaaa\nbbbb\ncccc\ndddd\n"


def test_replay_resume_offset_reemits_at_most_one_partial_line(tmp_path):
    """Per-(path, inode) line-aligned resume: a re-open continues where
    the last delivered LINE ended, so only the partial line that was in
    flight is ever re-emitted (the PR 5 reconnect gap-bound, for
    files)."""
    p = tmp_path / "a.log"
    p.write_bytes(b"alpha\nbeta\ngamma")  # no trailing newline
    src = _fast_replay([str(p)])

    async def scenario():
        refs = await src.discover()
        first = await _collect(
            await src.open_stream(refs[0], LogOptions(follow=False)))
        with open(p, "ab") as f:
            f.write(b"-cont\ndelta\n")
        second = await _collect(
            await src.open_stream(refs[0], LogOptions(follow=False)))
        return first, second

    first, second = run(scenario())
    assert first == b"alpha\nbeta\ngamma"
    # Resume re-serves ONLY the in-flight partial line, now completed.
    assert second == b"gamma-cont\ndelta\n"


# ---- archive: grouping, framing parity, named errors -----------------


def test_group_archives_orders_rotated_sets_oldest_first():
    files = ["d/app.log", "d/app.log.1.gz", "d/app.log.10.gz",
             "d/app.log.2.gz", "d/other.log.1", "d/other.log"]
    groups = group_archives(files)
    assert groups["d/app.log"] == [
        "d/app.log.10.gz", "d/app.log.2.gz", "d/app.log.1.gz", "d/app.log"]
    assert groups["d/other.log"] == ["d/other.log.1", "d/other.log"]
    assert strip_compress_ext("a.log.2.gz") == ("a.log.2", "gz")
    assert strip_compress_ext("a.log") == ("a.log", "")


def test_archive_framing_parity_vs_line_oracle(tmp_path):
    """Multi-member gzip + tiny slabs: the slab stream must be
    byte-identical to the oracle (decompress whole file, split lines)
    and every slab except a final partial must end on a newline —
    the no-straddle framing contract, exercised across member
    boundaries and slab-boundary newlines."""
    # Varied line lengths, including one line far longer than the slab.
    lines = [b"x" * (i % 37 + 1) + b" %d" % i for i in range(400)]
    lines[100] = b"L" * 5000  # forces tail-carry across many chunks
    plain = b"\n".join(lines) + b"\n"
    p = tmp_path / "app.log.1.gz"
    # Two concatenated gzip members in ONE file (logrotate-compress
    # append shape).
    with open(p, "wb") as f:
        f.write(gzip.compress(plain[:3000]))
        f.write(gzip.compress(plain[3000:]))
    ref = SourceRef(kind="archive", group="g", unit="archive")
    stream = ArchiveStream(ref, [str(p)],
                           metrics=ArchiveSource([]).metrics,
                           slab_bytes=1024)

    async def scenario():
        slabs = []
        async for s in stream:
            slabs.append(s)
        await stream.close()
        return slabs

    slabs = run(scenario())
    assert b"".join(slabs) == plain
    for s in slabs[:-1]:
        assert s.endswith(b"\n"), "slab straddles a line"
    oracle = [ln for ln in plain.split(b"\n") if ln]
    got = [ln for ln in b"".join(slabs).split(b"\n") if ln]
    assert got == oracle


def test_truncated_gzip_member_raises_named_source_error(tmp_path):
    whole = gzip.compress(b"".join(b"line %d\n" % i for i in range(2000)))
    p = tmp_path / "cut.log.1.gz"
    p.write_bytes(whole[: len(whole) // 2])  # mid-member truncation
    ref = SourceRef(kind="archive", group="g", unit="archive")
    stream = ArchiveStream(ref, [str(p)],
                           metrics=ArchiveSource([]).metrics)

    with pytest.raises(SourceError) as ei:
        run(_collect(stream))
    assert ei.value.path == str(p)
    assert isinstance(ei.value.offset, int) and ei.value.offset >= 0
    assert "truncated" in str(ei.value)


def test_corrupt_gzip_bytes_raise_named_source_error(tmp_path):
    blob = bytearray(gzip.compress(b"good bytes\n" * 500))
    blob[len(blob) // 2] ^= 0xFF
    p = tmp_path / "bad.log.1.gz"
    p.write_bytes(bytes(blob))
    ref = SourceRef(kind="archive", group="g", unit="archive")
    stream = ArchiveStream(ref, [str(p)],
                           metrics=ArchiveSource([]).metrics)
    with pytest.raises(SourceError) as ei:
        run(_collect(stream))
    assert ei.value.path == str(p)
    # zlib may fault the checksum at EOF (reported as truncation) or
    # the stream mid-way (reported as corruption); both name the file.
    assert "gzip" in str(ei.value)


def test_archive_discover_empty_is_an_error(tmp_path):
    src = ArchiveSource([str(tmp_path / "nothing")])
    with pytest.raises(SourceError):
        run(src.discover())


# ---- socket: backpressure by construction, ephemeral EOF -------------


def test_socket_backpressure_blocks_fast_peer_until_consumed(tmp_path):
    """No unbounded buffer anywhere: with the consumer stalled, a peer
    blasting bytes must stall in drain() (StreamReader flow limit ->
    TCP window -> peer send buffer); once the consumer reads, the
    writes complete and every byte arrives."""
    payload = b"y" * 4096 + b"\n"
    n_chunks = 2000  # ~8 MB >> flow limit + kernel buffers

    async def scenario():
        src = SocketSource("127.0.0.1:0", max_conns=4)
        await src.start()
        port = src.bound_port()
        reader_done = asyncio.Event()

        async def peer():
            _r, w = await asyncio.open_connection("127.0.0.1", port)
            sent = 0
            for _ in range(n_chunks):
                w.write(payload)
                await w.drain()
                sent += len(payload)
            w.close()
            await w.wait_closed()
            return sent

        peer_task = asyncio.create_task(peer())
        await asyncio.sleep(0.2)
        refs = await src.discover()
        assert len(refs) == 1 and refs[0].ephemeral
        # Consumer stalled: the peer must NOT have finished pushing.
        assert not peer_task.done(), \
            "peer pushed ~8MB with no consumer: buffering is unbounded"
        stream = await src.open_stream(refs[0], LogOptions(follow=True))
        got = 0
        async for chunk in stream:
            got += len(chunk)
        reader_done.set()
        sent = await peer_task
        await src.close()
        return sent, got

    sent, got = run(scenario(), timeout=30)
    assert sent == n_chunks * len(payload)
    assert got == sent


def test_socket_conn_cap_rejects_excess_peers():
    async def scenario():
        src = SocketSource("127.0.0.1:0", max_conns=1)
        await src.start()
        port = src.bound_port()
        _r1, w1 = await asyncio.open_connection("127.0.0.1", port)
        await asyncio.sleep(0.1)
        r2, w2 = await asyncio.open_connection("127.0.0.1", port)
        # The over-cap peer is closed by the listener: EOF on read.
        assert await r2.read() == b""
        refs = await src.discover()
        assert len(refs) == 1
        for w in (w1, w2):
            w.close()
        await src.close()

    run(scenario())


def test_socket_unix_listener_roundtrip(tmp_path):
    sock_path = str(tmp_path / "in.sock")

    async def scenario():
        src = SocketSource(f"unix:{sock_path}", max_conns=4)
        await src.start()
        _r, w = await asyncio.open_unix_connection(sock_path)
        w.write(b"hello over uds\n")
        await w.drain()
        w.close()
        await w.wait_closed()
        await asyncio.sleep(0.1)
        refs = await src.discover()
        assert len(refs) == 1
        data = await _collect(
            await src.open_stream(refs[0], LogOptions(follow=True)))
        await src.close()
        return data

    assert run(scenario()) == b"hello over uds\n"
    assert not os.path.exists(sock_path), "stale socket file left behind"


# ---- ClusterSource conformance (kube path byte-identical) ------------


def test_cluster_source_conformance_matches_backend_bytes():
    fc = FakeCluster.synthetic(n_pods=2, n_containers=2,
                               lines_per_container=25)
    src = ClusterSource(fc, "default")

    async def scenario():
        refs = await src.discover()
        assert len(refs) == 4  # 2 pods x 2 containers
        assert all(r.kind == "pod" and not r.ephemeral for r in refs)
        via_source = {}
        for r in refs:
            opts = LogOptions(follow=False, container=r.unit)
            via_source[(r.group, r.unit)] = await _collect(
                await src.open_stream(r, opts))
        direct = {}
        for r in refs:
            opts = LogOptions(follow=False, container=r.unit)
            direct[(r.group, r.unit)] = await _collect(
                await fc.open_log_stream("default", r.group, opts))
        return via_source, direct

    via_source, direct = run(scenario())
    assert via_source == direct, "adapter changed the kube byte stream"
    assert all(v for v in via_source.values())


# ---- chaos: injected source.read faults ------------------------------


def test_source_read_fault_reconnects_with_line_integrity(tmp_path):
    """An injected source.read fault mid-follow is absorbed by the
    SAME reconnect policy the kube path uses; the replay resume offset
    makes the retry line-aligned, so every line arrives exactly once."""
    p = tmp_path / "a.log"
    p.write_bytes(b"".join(b"seq=%03d\n" % i for i in range(30)))
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    FAULTS.load_spec("source.read:error*1")
    src = _fast_replay([str(p)])

    async def scenario():
        refs = await src.discover()
        jobs = plan_source_jobs(refs, str(out_dir))
        runner = FanoutRunner(None, "local", LogOptions(follow=True),
                              source=src, max_reconnects=4)
        stop = asyncio.Event()
        task = asyncio.create_task(runner.run(jobs, stop=stop))
        for _ in range(200):
            await asyncio.sleep(0.05)
            if os.path.exists(jobs[0].path) \
                    and b"seq=029\n" in open(jobs[0].path, "rb").read():
                break
        stop.set()
        results = await task
        return jobs, results

    jobs, results = run(scenario(), timeout=30)
    assert results[0].error is None
    got = open(jobs[0].path, "rb").read()
    for i in range(30):
        assert got.count(b"seq=%03d\n" % i) == 1, f"seq {i} lost or duped"


def test_source_read_fault_fails_batch_stream_with_named_error(tmp_path):
    """Non-follow: a read fault is a per-stream error (no reconnect
    loop to hide behind), isolated from sibling streams."""
    for name in ("a.log", "b.log"):
        (tmp_path / name).write_bytes(b"content\n" * 10)
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    FAULTS.load_spec("source.read:error*1")
    src = _fast_replay([str(tmp_path / "a.log"), str(tmp_path / "b.log")])

    async def scenario():
        refs = await src.discover()
        jobs = plan_source_jobs(refs, str(out_dir))
        runner = FanoutRunner(None, "local", LogOptions(follow=False),
                              source=src)
        return jobs, await runner.run(jobs)

    jobs, results = run(scenario())
    failed = [r for r in results if r.error]
    healthy = [r for r in results if not r.error]
    assert len(failed) == 1 and len(healthy) == 1
    assert "injected source.read fault" in failed[0].error
    assert open(healthy[0].job.path, "rb").read() == b"content\n" * 10


# ---- backfill vs follow byte parity ----------------------------------


def _rotated_gz_set(d, n=300):
    """app.log.2.gz + app.log.1.gz + app.log; returns the bytes a live
    follow of the un-rotated file would have produced."""
    lines = [b"event %05d payload %s\n" % (i, b"z" * (i % 23))
             for i in range(n)]
    plain = b"".join(lines)
    third = len(lines) // 3
    with gzip.open(d / "app.log.2.gz", "wb") as f:
        f.writelines(lines[:third])
    with gzip.open(d / "app.log.1.gz", "wb") as f:
        f.writelines(lines[third:2 * third])
    (d / "app.log").write_bytes(b"".join(lines[2 * third:]))
    return plain


def test_backfill_byte_parity_with_follow_of_unrotated_stream(tmp_path):
    """The acceptance property: a rotated + gzipped set backfills to
    EXACTLY the bytes a live follow of the same logical stream would
    have produced — one logical stream, oldest member first."""
    arch = tmp_path / "arch"
    arch.mkdir()
    plain = _rotated_gz_set(arch)
    # The follow-side twin: the same logical stream as one live file.
    live = tmp_path / "live"
    live.mkdir()
    (live / "app.log").write_bytes(plain)

    async def scenario():
        a_src = ArchiveSource([str(arch)])
        refs = await a_src.discover()
        assert len(refs) == 1
        backfill = await _collect(await a_src.open_stream(
            refs[0], LogOptions(follow=False)))
        await a_src.close()
        r_src = _fast_replay([str(live / "app.log")])
        rrefs = await r_src.discover()
        follow = await _collect(await r_src.open_stream(
            rrefs[0], LogOptions(follow=False)))
        return backfill, follow

    backfill, follow = run(scenario())
    assert backfill == plain
    assert backfill == follow


def test_backfill_app_e2e_matches_replay_app_e2e(tmp_path):
    """Same property through the FULL app (sinks, pipeline, teardown):
    `--backfill DIR` output is byte-identical to `--source replay:FILE`
    over the pre-concatenated stream."""
    from klogs_tpu import app
    from klogs_tpu.cli import parse_args

    arch = tmp_path / "arch"
    arch.mkdir()
    plain = _rotated_gz_set(arch, n=240)
    live = tmp_path / "live"
    live.mkdir()
    (live / "app.log").write_bytes(plain)

    out_a = tmp_path / "out_a"
    out_b = tmp_path / "out_b"
    rc = run(app.run_async(parse_args(
        ["-p", str(out_a), "--backfill", str(arch)])))
    assert rc == 0
    rc = run(app.run_async(parse_args(
        ["-p", str(out_b), "--source", f"replay:{live / 'app.log'}"])))
    assert rc == 0

    def only_file(d):
        files = [f for f in os.listdir(d) if f.endswith(".log")]
        assert len(files) == 1, files
        return open(os.path.join(d, files[0]), "rb").read()

    a, b = only_file(out_a), only_file(out_b)
    assert a == plain
    assert a == b


# ---- CLI validation ---------------------------------------------------


def test_cli_source_spec_validation_exit_codes(capsys, tmp_path):
    from klogs_tpu.cli import main

    # Unknown scheme.
    assert main(["--source", "ftp://nope", "-p", str(tmp_path)]) == 1
    assert "invalid --source" in capsys.readouterr().out
    # socket requires follow.
    assert main(["--source", "socket:127.0.0.1:9", "-p", str(tmp_path)]) == 1
    assert "requires -f" in capsys.readouterr().out
    # backfill and source are mutually exclusive.
    assert main(["--source", "replay:x", "--backfill", "y",
                 "-p", str(tmp_path)]) == 1
    assert "mutually exclusive" in capsys.readouterr().out
    # backfill is run-to-completion.
    assert main(["--backfill", "y", "-f", "-p", str(tmp_path)]) == 1
    assert "run-to-completion" in capsys.readouterr().out
    # replay-rate must be positive.
    assert main(["--source", "replay:x", "--replay-rate", "-2",
                 "-p", str(tmp_path)]) == 1
    assert "positive" in capsys.readouterr().out


# ---- archive: zstd members, producer lifecycle, multi-producer -------

def test_zstd_multi_frame_parity_vs_oracle(tmp_path):
    """Concatenated zstd frames in one file (the logrotate-append
    shape _gunzip already handles for .gz) must decompress end to end:
    read_across_frames keeps the reader from stopping silently at the
    first frame boundary."""
    zstandard = pytest.importorskip("zstandard")
    lines = [b"z line %d" % i for i in range(2000)]
    plain = b"\n".join(lines) + b"\n"
    p = tmp_path / "app.log.1.zst"
    cctx = zstandard.ZstdCompressor()
    with open(p, "wb") as f:
        f.write(cctx.compress(plain[:5000]))
        f.write(cctx.compress(plain[5000:]))
    ref = SourceRef(kind="archive", group="g", unit="archive")
    stream = ArchiveStream(ref, [str(p)],
                           metrics=ArchiveSource([]).metrics,
                           slab_bytes=1024)
    got = run(_collect(stream))
    assert got == plain
    # the no-straddle framing contract holds across frame boundaries
    assert got.endswith(b"\n")


def test_truncated_zstd_member_raises_named_source_error(tmp_path):
    zstandard = pytest.importorskip("zstandard")
    whole = zstandard.ZstdCompressor().compress(
        b"".join(b"line %d\n" % i for i in range(5000)))
    p = tmp_path / "cut.log.1.zst"
    p.write_bytes(whole[: len(whole) // 2])  # mid-frame truncation
    ref = SourceRef(kind="archive", group="g", unit="archive")
    stream = ArchiveStream(ref, [str(p)],
                           metrics=ArchiveSource([]).metrics)
    with pytest.raises(SourceError) as ei:
        run(_collect(stream))
    assert ei.value.path == str(p)
    assert isinstance(ei.value.offset, int) and ei.value.offset >= 0
    assert "zstd" in str(ei.value)


def test_multi_producer_backfill_byte_parity(tmp_path):
    """Four rotated sets consumed CONCURRENTLY — four producer threads
    feeding four bounded readahead queues on one event loop — must
    each stay byte-identical to its single-producer oracle."""
    sets = {}
    for k in range(4):
        plain = b"".join(b"set%d line %d\n" % (k, i)
                         for i in range(3000))
        p = tmp_path / f"app{k}.log.1.gz"
        with open(p, "wb") as f:
            f.write(gzip.compress(plain[:4000]))
            f.write(gzip.compress(plain[4000:]))
        sets[str(p)] = plain

    async def scenario():
        streams = [
            ArchiveStream(SourceRef(kind="archive", group=f"g{k}",
                                    unit="archive"),
                          [path], metrics=ArchiveSource([]).metrics,
                          slab_bytes=2048, readahead_slabs=2)
            for k, path in enumerate(sets)
        ]
        return await asyncio.gather(*(_collect(s) for s in streams))

    got = run(scenario())
    assert got == list(sets.values())


def test_archive_close_joins_producer_thread(tmp_path):
    """close() mid-archive must not leave the producer thread alive
    pumping slabs into a drained queue (regression for the un-joined
    producer found by the resource-lifecycle pass)."""
    plain = b"".join(b"line %d\n" % i for i in range(200000))
    p = tmp_path / "big.log.1.gz"
    p.write_bytes(gzip.compress(plain))
    ref = SourceRef(kind="archive", group="g", unit="archive")
    stream = ArchiveStream(ref, [str(p)],
                           metrics=ArchiveSource([]).metrics,
                           slab_bytes=4096, readahead_slabs=2)

    async def scenario():
        async for _ in stream:
            break  # one slab, then abandon mid-archive
        await stream.close()
        t = stream._thread
        assert t is not None and not t.is_alive()

    run(scenario())


def test_replay_open_failure_does_not_leak_fd(tmp_path, monkeypatch):
    """fstat failing between open() and ownership transfer must close
    the fd (regression for the raise-edge leak found by the
    resource-lifecycle pass)."""
    import builtins

    import klogs_tpu.sources.replay as replay_mod
    from klogs_tpu.sources.replay import ReplayStream

    path = tmp_path / "a.log"
    path.write_bytes(b"hello\n")
    ref = SourceRef(kind="replay", group="g", unit="file",
                    target=str(path))
    stream = ReplayStream(ref, False, offsets={},
                          metrics=ReplaySource([]).metrics)

    opened = []
    real_open = builtins.open

    def capture_open(*a, **kw):
        f = real_open(*a, **kw)
        opened.append(f)
        return f

    def raising_fstat(fd):
        raise OSError("injected fstat failure")

    monkeypatch.setattr(builtins, "open", capture_open)
    monkeypatch.setattr(replay_mod.os, "fstat", raising_fstat)
    with pytest.raises(OSError, match="injected"):
        stream._open_file()
    assert len(opened) == 1 and opened[0].closed
