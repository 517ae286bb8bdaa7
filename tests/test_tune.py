"""Autotune harness plumbing (runner injected; no device timing)."""

import numpy as np
import pytest

from klogs_tpu.ops import nfa
from klogs_tpu.ops.tune import env_overrides, load_cached, tune_grouped


@pytest.fixture
def dp():
    d, live, acc = nfa.compile_grouped(["ERROR", "WARN"])
    return d, live, acc


def test_tune_picks_best_and_caches(dp, tmp_path, monkeypatch):
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))
    d, live, acc = dp
    batch = np.zeros((4096, 128), np.uint8)
    lengths = np.full(4096, 100, np.int32)
    calls = []

    def runner(tile_b, interleave):
        calls.append((tile_b, interleave))
        return 1000.0 * tile_b / (1 + interleave)  # favor tile 8192, il 1

    best = tune_grouped(d, live, acc, batch, lengths, runner=runner, quiet=True)
    # Tiles are clamped to the 4096-row batch, so 4096/il=1 wins.
    assert best["tile_b"] == 4096 and best["interleave"] == 1
    assert len(calls) >= 6
    assert all(t <= 4096 for t, _ in calls)
    cached = load_cached(d, batch.shape, _device_kind())
    assert cached == best


def test_tune_survives_failing_configs(dp, tmp_path, monkeypatch):
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))
    d, live, acc = dp
    batch = np.zeros((1024, 128), np.uint8)
    lengths = np.full(1024, 10, np.int32)

    def runner(tile_b, interleave):
        if tile_b > 1024:
            raise RuntimeError("VMEM OOM")
        return 500.0 / interleave

    best = tune_grouped(d, live, acc, batch, lengths, runner=runner, quiet=True)
    assert best["tile_b"] == 1024 and best["interleave"] == 1


def test_env_overrides(monkeypatch):
    assert env_overrides() == {}
    monkeypatch.setenv("KLOGS_TPU_TILE", "2048")
    monkeypatch.setenv("KLOGS_TPU_INTERLEAVE", "2")
    assert env_overrides() == {"tile_b": 2048, "interleave": 2}


def test_kernel_kwargs_hardware_default(monkeypatch):
    from klogs_tpu.ops.tune import HW_DEFAULT_MASK_BLOCK, kernel_kwargs

    # Real hardware, no env: the measured default chain variant.
    assert kernel_kwargs(True) == {"mask_block": HW_DEFAULT_MASK_BLOCK}
    # Interpret/CPU paths: plain chain.
    assert kernel_kwargs(False) == {}
    # KLOGS_TPU_MASK_BLOCK=1 forces the plain chain on hardware.
    monkeypatch.setenv("KLOGS_TPU_MASK_BLOCK", "1")
    assert kernel_kwargs(True) == {"mask_block": 1}
    monkeypatch.setenv("KLOGS_TPU_MASK_BLOCK", "8")
    assert kernel_kwargs(True) == {"mask_block": 8}
    monkeypatch.delenv("KLOGS_TPU_MASK_BLOCK")
    # A CONFLICTING env-picked chain variant suppresses the default
    # (the combos are rejected loudly by the kernel)...
    monkeypatch.setenv("KLOGS_TPU_INTERLEAVE", "2")
    assert kernel_kwargs(True) == {"interleave": 2}
    # ...but restating the interleave default (=1) does not: only
    # interleave>1 conflicts with mask_block.
    monkeypatch.setenv("KLOGS_TPU_INTERLEAVE", "1")
    assert kernel_kwargs(True) == {
        "interleave": 1, "mask_block": HW_DEFAULT_MASK_BLOCK}
    monkeypatch.delenv("KLOGS_TPU_INTERLEAVE")
    monkeypatch.setenv("KLOGS_TPU_FUSED_GROUPS", "1")
    assert kernel_kwargs(True) == {"fused": True}
    monkeypatch.delenv("KLOGS_TPU_FUSED_GROUPS")
    # A bare tile override is not a chain variant: default still applies.
    monkeypatch.setenv("KLOGS_TPU_TILE", "4096")
    assert kernel_kwargs(True) == {
        "tile_b": 4096, "mask_block": HW_DEFAULT_MASK_BLOCK}


def _device_kind():
    import jax

    return jax.devices()[0].device_kind


def test_chain_selection_flags(monkeypatch):
    from klogs_tpu.ops.tune import HW_DEFAULT_MASK_BLOCK, chain_selection

    # Default applied -> defaulted (degrade-eligible), no fused drop.
    assert chain_selection(True) == (
        {"mask_block": HW_DEFAULT_MASK_BLOCK}, True, False)
    assert chain_selection(False) == ({}, False, False)
    # Env-forced mask_block: never defaulted (failures stay loud).
    monkeypatch.setenv("KLOGS_TPU_MASK_BLOCK", "4")
    assert chain_selection(True) == ({"mask_block": 4}, False, False)
    monkeypatch.delenv("KLOGS_TPU_MASK_BLOCK")
    # Mesh path (allow_fused=False): env fused is dropped LOUDLY and the
    # chain, unpicked again, gets the hardware default back.
    monkeypatch.setenv("KLOGS_TPU_FUSED_GROUPS", "1")
    assert chain_selection(True, allow_fused=False) == (
        {"mask_block": HW_DEFAULT_MASK_BLOCK}, True, True)
    # ...but on interpret there is no default to re-apply.
    assert chain_selection(False, allow_fused=False) == ({}, False, True)
    # allow_fused=True passes fused through untouched.
    assert chain_selection(True) == ({"fused": True}, False, False)


# ---- the adaptive operating-point controller (PR 20) -----------------


class FakeTunedService:
    """Duck-types the AsyncFilterService tuning surface."""

    def __init__(self, coalesce=524288, flight=16):
        self._c, self._f = coalesce, flight
        self.applied = []

    @property
    def coalesce_lines(self):
        return self._c

    @property
    def max_in_flight(self):
        return self._f

    def apply_tuning(self, coalesce_lines=None, max_in_flight=None):
        self.applied.append((coalesce_lines, max_in_flight))
        if coalesce_lines is not None:
            self._c = coalesce_lines
        if max_in_flight is not None:
            self._f = max_in_flight


SURFACE = {"coalesce_lines": (262144, 1048576), "max_in_flight": (8, 64)}


def _ctrl(svc=None, **kw):
    from klogs_tpu.ops.tune import AdaptiveController

    svc = FakeTunedService() if svc is None else svc
    kw.setdefault("interval_s", 0.01)
    kw.setdefault("step", 0.5)
    kw.setdefault("surface", SURFACE)
    return AdaptiveController(svc, **kw), svc


def _press_doc(svc):
    return {"enabled": True, "samples": {
        "device.in_flight_used": float(svc.max_in_flight),
        "coalescer.queue_depth": 3.0,
        "coalescer.pending_lines": 0.0}}


def _idle_doc():
    return {"enabled": True, "samples": {
        "device.in_flight_used": 0.0,
        "coalescer.queue_depth": 0.0,
        "coalescer.pending_lines": 0.0}}


def _step(ctrl, doc):
    import asyncio

    return asyncio.run(ctrl.step_once(doc))


def test_operating_surface_is_the_old_sweeps_search_range():
    from klogs_tpu.ops.tune import (
        COALESCE_LINES_RANGE,
        MAX_IN_FLIGHT_RANGE,
        OPERATING_SURFACE,
    )

    assert COALESCE_LINES_RANGE == (262144, 1048576)
    assert MAX_IN_FLIGHT_RANGE == (8, 64)
    assert OPERATING_SURFACE == {"coalesce_lines": COALESCE_LINES_RANGE,
                                 "max_in_flight": MAX_IN_FLIGHT_RANGE}


def test_tune_mode_default_off_and_validation(monkeypatch):
    from klogs_tpu.ops.tune import tune_mode

    monkeypatch.delenv("KLOGS_TUNE", raising=False)
    assert tune_mode() == "off"
    monkeypatch.setenv("KLOGS_TUNE", " AUTO ")
    assert tune_mode() == "auto"
    monkeypatch.setenv("KLOGS_TUNE", "sorta")
    with pytest.raises(ValueError, match="KLOGS_TUNE"):
        tune_mode()


def test_maybe_controller_off_is_none_auto_builds(monkeypatch):
    from klogs_tpu.ops.tune import maybe_controller

    svc = FakeTunedService()
    monkeypatch.delenv("KLOGS_TUNE", raising=False)
    assert maybe_controller(svc) is None
    assert svc.applied == []  # off = byte-identical fixed flags
    monkeypatch.setenv("KLOGS_TUNE", "auto")
    assert maybe_controller(svc) is not None
    # No tuning surface (CPU batch path, remote tier) -> no controller.
    assert maybe_controller(object()) is None


def test_controller_bounds_hug_surface_and_initial():
    ctrl, _ = _ctrl()
    assert ctrl.bounds == {"coalesce_lines": (262144, 1048576),
                           "max_in_flight": (8, 64)}
    # An operator flag OUTSIDE the measured surface widens the bound:
    # the controller can always return to the flags it started from.
    ctrl2, _ = _ctrl(FakeTunedService(coalesce=131072, flight=128))
    assert ctrl2.bounds["coalesce_lines"][0] == 131072
    assert ctrl2.bounds["max_in_flight"][1] == 128
    # Without a surface, bounds collapse: hold, never move.
    ctrl3, svc3 = _ctrl(surface={})
    assert ctrl3.bounds["max_in_flight"] == (16, 16)
    for _ in range(10):
        _step(ctrl3, _press_doc(svc3))
    assert ctrl3.steps_applied == 0


def test_controller_steps_up_after_sustained_pressure():
    ctrl, svc = _ctrl()
    assert _step(ctrl, _press_doc(svc)) is None  # 1 tick: hold
    assert _step(ctrl, _press_doc(svc)) == ("max_in_flight", "up")
    # One bounded multiplicative step: 16 -> 24, not the ceiling.
    assert svc.max_in_flight == 24
    # Cooldown: the next 2 pressure ticks move nothing.
    assert _step(ctrl, _press_doc(svc)) is None
    assert _step(ctrl, _press_doc(svc)) is None
    assert ctrl.steps_applied == 1


def test_controller_steps_down_after_sustained_idle():
    ctrl, svc = _ctrl()
    for _ in range(3):
        assert _step(ctrl, _idle_doc()) is None
    assert _step(ctrl, _idle_doc()) == ("max_in_flight", "down")
    assert svc.max_in_flight == 10  # 16 / 1.5, bounded below by 8


def test_controller_group_pressure_steps_coalescer():
    ctrl, svc = _ctrl()
    doc = {"enabled": True, "samples": {
        "device.in_flight_used": 1.0,
        "coalescer.queue_depth": 0.0,
        "coalescer.pending_lines": float(svc.coalesce_lines)}}
    _step(ctrl, doc)
    assert _step(ctrl, doc) == ("coalesce_lines", "up")
    assert svc.coalesce_lines == 786432  # 524288 * 1.5, under the cap


def test_controller_pinned_at_ceiling_holds():
    ctrl, svc = _ctrl(FakeTunedService(flight=64))
    for _ in range(6):
        assert _step(ctrl, _press_doc(svc)) is None
    assert svc.max_in_flight == 64 and svc.applied == []


def test_controller_disabled_doc_and_oscillation_hold():
    ctrl, svc = _ctrl()
    assert _step(ctrl, {"enabled": False}) is None
    # A signal oscillating tick-to-tick never builds a streak: the
    # hysteresis keeps the operating point still across a long soak.
    for i in range(100):
        doc = _press_doc(svc) if i % 2 else _idle_doc()
        _step(ctrl, doc)
    assert ctrl.steps_applied == 0 and svc.applied == []


@pytest.mark.parametrize("knob", ["KLOGS_TUNE_INTERVAL_S",
                                  "KLOGS_TUNE_STEP"])
@pytest.mark.parametrize("bad", ["nan", "inf", "0", "-1"])
def test_controller_env_knobs_fail_loudly(monkeypatch, knob, bad):
    from klogs_tpu.ops.tune import AdaptiveController

    monkeypatch.setenv(knob, bad)
    with pytest.raises(ValueError, match=knob):
        AdaptiveController(FakeTunedService(), surface=SURFACE)


def test_controller_run_loop_survives_injected_faults():
    """The tune.step fault point: an armed fault skips the tick and
    must NOT kill the loop (the pipeline keeps flying at the held
    operating point)."""
    import asyncio

    from klogs_tpu.resilience import FAULTS

    ctrl, svc = _ctrl(profile_fn=lambda: _press_doc(svc_holder[0]),
                      interval_s=0.01)
    svc_holder = [svc]

    async def scenario():
        FAULTS.load_spec("tune.step:error*")
        stop = asyncio.Event()
        task = asyncio.create_task(ctrl.run(stop))
        await asyncio.sleep(0.1)
        stop.set()
        await asyncio.wait_for(task, 5)

    try:
        asyncio.run(scenario())
    finally:
        FAULTS.clear()
    assert ctrl.steps_applied == 0  # every tick was skipped, none died
