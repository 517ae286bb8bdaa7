"""The event-loop readers (metrics/loop_*.py, workers_cpu.bulk.py): each
reads the window's deltas of the collector's loop counters, and nothing
on a collector without them or with nothing in the window."""

import importlib.util
import json
import os

import pytest

import run as harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    LOOP_METRICS = [m["name"] for m in json.load(f)["per_layer"]
                    if m["layer"] == "event loop"
                    and m["name"] != "loop_lag_ms.follow"]

SAMPLES = "klogs_loop_samples_total"
LOOP_CPU = "klogs_loop_cpu_seconds_total"
PROCESS_CPU = "klogs_process_cpu_seconds_total"


def read(metric, run):
    spec = importlib.util.spec_from_file_location(
        "m_" + metric.replace(".", "_"),
        os.path.join(BENCH, "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def snap(samples, loop_cpu, process_cpu):
    out = {(SAMPLES, (("stage", s),)): v for s, v in samples.items()}
    for fam, cpu in ((LOOP_CPU, loop_cpu), (PROCESS_CPU, process_cpu)):
        out.update({(fam, (("mode", m),)): v for m, v in cpu.items()})
    return out


STAGES = ("read", "frame", "flush", "dispatch", "resume", "flusher",
          "poll", "other")


def window(d_samples, d_loop, d_process, seconds=10.0):
    r = harness.Run()
    r.window_s = seconds
    zero = dict.fromkeys(STAGES, 5)
    r.m0 = snap(zero, {"user": 1.0, "sys": 0.5}, {"user": 3.0, "sys": 1.0})
    r.m1 = snap({s: 5 + d_samples.get(s, 0) for s in STAGES},
                {"user": 1.0 + d_loop[0], "sys": 0.5 + d_loop[1]},
                {"user": 3.0 + d_process[0], "sys": 1.0 + d_process[1]})
    return r


def test_every_loop_metric_in_the_benchmark_has_a_case_here():
    assert sorted(LOOP_METRICS) == sorted(
        ["loop_busy.bulk", "loop_busy.follow", "loop_sys_share.bulk",
         "loop_stalled.bulk", "loop_stalled.follow", "workers_cpu.bulk"]
        + [f"loop_stage.{s}.bulk"
           for s in ("read", "frame", "flush", "dispatch", "resume")]
        + [f"loop_stage.{s}.follow" for s in ("read", "flush", "flusher")])


def test_the_readers_read_the_window_deltas():
    # 1,000 samples: 400 read, 100 frame, 50 flush, 150 dispatch,
    # 50 resume, 20 flusher, 200 poll, 30 other; the loop used 6 s user
    # and 2 s sys of a 10 s window, the process 9 s and 4 s.
    r = window({"read": 400, "frame": 100, "flush": 50, "dispatch": 150,
                "resume": 50, "flusher": 20, "poll": 200, "other": 30},
               (6.0, 2.0), (9.0, 4.0))
    want = {"loop_busy.bulk": 80.0, "loop_busy.follow": 80.0,
            "loop_sys_share.bulk": 25.0,
            "loop_stalled.bulk": 0.0, "loop_stalled.follow": 0.0,
            "workers_cpu.bulk": 50.0,
            "loop_stage.read.bulk": 40.0, "loop_stage.frame.bulk": 10.0,
            "loop_stage.flush.bulk": 5.0, "loop_stage.dispatch.bulk": 15.0,
            "loop_stage.resume.bulk": 5.0, "loop_stage.read.follow": 40.0,
            "loop_stage.flush.follow": 5.0,
            "loop_stage.flusher.follow": 2.0}
    assert sorted(want) == sorted(LOOP_METRICS)
    for name, value in want.items():
        assert read(name, r) == pytest.approx(value), name


def test_stalled_is_the_wanted_share_less_the_cpu():
    # 90% of samples outside poll, 60% of a core: 30% stalled.
    r = window({"read": 900, "poll": 100}, (5.0, 1.0), (6.0, 1.0))
    assert read("loop_stalled.bulk", r) == pytest.approx(30.0)
    assert read("loop_stalled.follow", r) == pytest.approx(30.0)


@pytest.mark.parametrize("metric", LOOP_METRICS)
def test_a_collector_without_the_counters_reads_nothing(metric):
    r = harness.Run()
    r.window_s = 10.0
    r.m0 = {("klogs_sink_lines_total", ()): 10.0}
    r.m1 = {("klogs_sink_lines_total", ()): 20.0}
    assert read(metric, r) is None


@pytest.mark.parametrize("metric", LOOP_METRICS)
def test_a_window_with_nothing_in_it_reads_nothing(metric):
    # No sample and no CPU moved between the window's two scrapes.
    r = window({}, (0.0, 0.0), (0.0, 0.0))
    assert read(metric, r) is None
