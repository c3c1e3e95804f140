"""The trace reduction on a small synthetic profile, laid out as the TPU
profiler lays one out (device plane with module and op lines; host plane
with the harness's annotations)."""
from types import SimpleNamespace as NS

import pytest

import _tiny  # noqa: F401
from bench import trace


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile():
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_edge_step(11)", 100, 300),
            ev("jit_cloud_step_masked(12)", 500, 200),
            ev("jit_edge_step(11)", 900, 100)]),
        NS(name="XLA Ops", events=[
            ev("%fusion.1 = bf16[8] fusion(x)", 100, 200),
            ev("%while.2 = (s32[]) while(y)", 300, 100),
            ev("%fusion.1 = bf16[8] fusion(x)", 500, 200),
            ev("%fusion.1 = bf16[8] fusion(x)", 900, 100)]),
        NS(name="Async XLA Ops", events=[ev("copy", 0, 5000)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 50, 1000),
        ev("bench.tick", 60, 650),
        ev("bench.wait", 720, 170),
        ev("bench.admit", 895, 150),
        ev("unrelated", 0, 10)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), device, host])


def test_reduce_profile():
    out = trace.reduce_profile(profile())
    assert out["window_s"] == pytest.approx(1000e-9)
    # busy: 100-400, 500-700, 900-1000 inside the window 50-1050
    assert out["busy_s"] == pytest.approx(600e-9)
    assert out["programs"]["edge_step"] == {"count": 2,
                                            "seconds": pytest.approx(400e-9)}
    assert out["programs"]["cloud_step_masked"]["count"] == 1
    ops = dict(out["device_ops"])
    assert ops["edge_step:fusion.1"] == pytest.approx(300e-9)
    assert ops["edge_step:while.2"] == pytest.approx(100e-9)
    assert ops["cloud_step_masked:fusion.1"] == pytest.approx(200e-9)
    # idle: 50-100 and 400-500 under the tick, 700-900 mostly waiting,
    # 1000-1050 under the admission
    idle = dict(out["idle_gaps"])
    assert idle["tick"] == pytest.approx(150e-9)
    assert idle["wait"] == pytest.approx(200e-9)
    assert idle["admit"] == pytest.approx(50e-9)


def test_no_window_or_no_device_reads_nothing():
    p = profile()
    p.planes[2].lines[0].events = p.planes[2].lines[0].events[1:]
    assert trace.reduce_profile(p) is None
    assert trace.reduce_profile(NS(planes=[profile().planes[2]])) is None


def test_names():
    assert trace.program_name("jit_edge_prefill_padded(3)") == \
        "edge_prefill_padded"
    assert trace.op_name("%fusion.12 = bf16[8] fusion(a)") == "fusion.12"
