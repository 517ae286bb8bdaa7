"""The event loop's own accounting (obs/loopprof.py): stage samples
taken where the loop is, the kernel's CPU counters, the stage table
resolving to real functions, and the collector starting and stopping
it with its loop."""

import asyncio
import os
import threading
import time

import numpy as np
import pytest

from klogs_tpu import native
from klogs_tpu.obs import Registry, loopprof, register_all, snapshot
from klogs_tpu.obs.profiler import refresh_process_metrics

needs_sampler = pytest.mark.skipif(
    native.load_loopsampler() is None,
    reason="stage samples need the native sampler module")


def _registry():
    r = Registry()
    register_all(r)
    return r


def _spin(seconds):
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        pass


def _ticks_during(coro_fn):
    """Stage ticks counted while ``coro_fn()`` runs on a sampled loop."""
    async def scenario():
        prof = loopprof.LoopProfiler(_registry()).start()
        try:
            before = dict(prof.ticks)
            await coro_fn()
            after = dict(prof.ticks)
        finally:
            prof.stop()
        return {s: after[s] - before[s] for s in loopprof.STAGES}

    return asyncio.run(scenario())


def _value(registry, family, **labels):
    for s in snapshot(registry)[family]["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["value"]
    raise KeyError((family, labels))


@needs_sampler
def test_a_spin_inside_the_framer_samples_as_frame():
    from klogs_tpu.filters.base import FilterStats
    from klogs_tpu.filters.sink import FilteredSink
    from klogs_tpu.runtime.sink import Sink

    class SpinBatcher:
        pending_lines = 0

        def feed(self, chunk):  # unlisted: FilteredSink.write names it
            _spin(0.25)
            return 0

    class Null(Sink):
        async def write(self, chunk):
            pass

        async def close(self):
            pass

        @property
        def bytes_written(self):
            return 0

    sink = FilteredSink(Null(), object(), FilterStats())
    sink._batcher = SpinBatcher()
    d = _ticks_during(lambda: sink.write(b"x"))
    total = sum(d.values())
    assert total >= 10, d
    assert d["frame"] >= 0.7 * total, d


@needs_sampler
def test_an_idle_loop_samples_as_poll():
    d = _ticks_during(lambda: asyncio.sleep(0.3))
    total = sum(d.values())
    assert total >= 15, d
    assert d["poll"] >= 0.8 * total, d


@needs_sampler
def test_a_loop_busy_with_ready_callbacks_is_not_idle():
    # Between callbacks the loop selects with timeout 0: not a wait.
    async def busy():
        loop = asyncio.get_running_loop()
        done = loop.create_future()
        t_end = time.perf_counter() + 0.3

        def step():
            _spin(0.001)
            if time.perf_counter() < t_end:
                loop.call_soon(step)
            else:
                done.set_result(None)

        loop.call_soon(step)
        await done

    d = _ticks_during(busy)
    total = sum(d.values())
    assert total >= 15, d
    assert d["poll"] <= 0.1 * total, d
    assert d["other"] >= 0.8 * total, d


@needs_sampler
def test_a_group_splits_at_its_fetch_into_dispatch_and_resume():
    from klogs_tpu.filters.async_service import AsyncFilterService

    class SlowVerdicts:
        def __init__(self, n):
            self.n = n

        def __getitem__(self, sl):  # the set_result loop slices here
            _spin(0.2)
            return np.ones(self.n, dtype=bool)[sl]

    class SlowFilter:
        def dispatch_framed(self, payload, offsets):
            _spin(0.2)
            return len(offsets) - 1

        def fetch_framed(self, n):
            return SlowVerdicts(n)

        def close(self):
            pass

    async def one_group():
        svc = AsyncFilterService(SlowFilter(), fetch_workers=1,
                                 coalesce_delay_s=0.0)
        try:
            out = await svc.match_framed(
                b"a\n", np.array([0, 2], dtype=np.int32))
            assert out.tolist() == [True]
        finally:
            await svc.aclose()

    d = _ticks_during(one_group)
    total = sum(d.values())
    assert total >= 20, d
    assert d["dispatch"] >= 0.35 * total, d
    assert d["resume"] >= 0.35 * total, d


def test_cpu_counters_never_fall_and_stay_within_wall_time():
    r = _registry()

    def reading():
        refresh_process_metrics(r)
        return (time.monotonic(),
                {m: _value(r, "klogs_loop_cpu_seconds_total", mode=m)
                 for m in ("user", "sys")},
                {m: _value(r, "klogs_process_cpu_seconds_total", mode=m)
                 for m in ("user", "sys")})

    async def scenario():
        prof = loopprof.LoopProfiler(r).start()
        try:
            reads = [reading()]
            for _ in range(4):
                _spin(0.1)
                await asyncio.sleep(0.02)
                reads.append(reading())
        finally:
            prof.stop()
        return reads

    reads = asyncio.run(scenario())
    tick = 1.0 / os.sysconf("SC_CLK_TCK")
    for (t0, loop0, proc0), (t1, loop1, proc1) in zip(reads, reads[1:]):
        for m in ("user", "sys"):
            assert loop1[m] >= loop0[m] and proc1[m] >= proc0[m]
    (t0, loop0, proc0), (t1, loop1, proc1) = reads[0], reads[-1]
    d_loop = sum(loop1.values()) - sum(loop0.values())
    d_proc = sum(proc1.values()) - sum(proc0.values())
    assert 0 < d_loop <= (t1 - t0) + 2 * tick
    assert d_proc >= d_loop - 2 * tick


def _import_table_modules():
    import importlib

    for entry in loopprof.TABLE:
        importlib.import_module(entry[0])


def test_every_table_entry_resolves_to_a_real_function():
    _import_table_modules()
    codes, missing = loopprof.resolve()
    assert missing == []
    assert len(codes) == len(loopprof.TABLE)
    for stage, split, after in codes.values():
        assert stage in loopprof.STAGES and after in loopprof.STAGES
    splits = [v for v in codes.values() if v[1] >= 0]
    assert splits == [("dispatch", splits[0][1], "resume")]


def test_a_renamed_function_or_await_is_reported_missing():
    gone = ("klogs_tpu.filters.framer", "FramedBatcher.feed_renamed",
            "frame")
    no_await = ("klogs_tpu.filters.async_service",
                "AsyncFilterService._run_group", "dispatch",
                ("no_such_call", "resume"))
    _import_table_modules()
    codes, missing = loopprof.resolve((gone, no_await))
    assert codes == {} and missing == [gone, no_await]


def test_off_the_main_thread_only_the_cpu_counters_move():
    r = _registry()
    out = {}

    def run_loop():
        async def scenario():
            prof = loopprof.LoopProfiler(r).start()
            out["sampled"] = loopprof._SAMPLED is prof
            _spin(0.05)
            prof.stop()
            out["ticks"] = sum(prof.ticks.values())

        asyncio.run(scenario())

    t = threading.Thread(target=run_loop)
    t.start()
    t.join(timeout=30)
    assert out == {"sampled": False, "ticks": 0}
    assert _value(r, "klogs_loop_cpu_seconds_total", mode="user") > 0


@needs_sampler
def test_the_collector_accounts_its_loop_and_stops_with_it(tmp_path):
    import json

    from klogs_tpu import app
    from klogs_tpu.cli import parse_args
    from klogs_tpu.cluster.fake import FakeCluster

    fc = FakeCluster.synthetic(n_pods=2, n_containers=1,
                               lines_per_container=2000)
    dump = str(tmp_path / "stats.json")
    opts = parse_args(["-n", "default", "-a", "--match", "ERROR",
                       "--backend", "cpu", "-p", str(tmp_path / "logs"),
                       "--stats-json", dump])
    assert asyncio.run(app.run_async(opts, backend=fc)) == 0
    with open(dump) as f:
        metrics = json.load(f)["metrics"]
    stages = {s["labels"]["stage"]
              for s in metrics["klogs_loop_samples_total"]["samples"]}
    assert stages == set(loopprof.STAGES)
    for fam in ("klogs_loop_cpu_seconds_total",
                "klogs_process_cpu_seconds_total"):
        assert sum(s["value"] for s in metrics[fam]["samples"]) > 0
    assert loopprof._SAMPLED is None and loopprof._ACTIVE == []
    assert not [t for t in threading.enumerate()
                if t.name == "klogs-loopprof"]


@needs_sampler
def test_concurrent_refreshes_neither_lose_nor_repeat_ticks():
    # A scrape's refresh and the accounting thread both sync; with the
    # switch interval cut short, a lost or doubled update would leave
    # the counter off the handler's own tally.
    import sys

    r = _registry()

    async def scenario():
        prof = loopprof.LoopProfiler(r).start()
        stop = threading.Event()

        def scrape():
            while not stop.is_set():
                loopprof.refresh(r)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        threads = [threading.Thread(target=scrape)
                   for _ in range(min(64, 2 * (os.cpu_count() or 2)))]
        try:
            for t in threads:
                t.start()
            _spin(0.3)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            sys.setswitchinterval(old)
            prof.stop()
        assert not any(t.is_alive() for t in threads)
        return prof

    prof = asyncio.run(scenario())
    counted = {s: _value(r, "klogs_loop_samples_total", stage=s)
               for s in loopprof.STAGES}
    assert counted == prof.ticks
    assert sum(counted.values()) >= 10
