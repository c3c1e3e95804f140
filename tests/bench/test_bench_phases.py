"""The reduction of the engine's phase spans (bench/phases.py) on a small
synthetic profile laid out as the TPU profiler lays one out, and the
phase counters of a tiny served window."""
from types import SimpleNamespace as NS

import pytest

import _tiny
from bench import phases, trace


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


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
        ev("engine.tick#rows=8#", 62, 643, rows=8),
        ev("engine.resolve", 62, 18),
        ev("engine.edge_launch", 80, 20),
        ev("engine.edge_fetch", 100, 320),
        ev("engine.upload", 420, 30),
        ev("engine.cloud_launch", 450, 70),
        ev("engine.resolve", 525, 180),
        ev("engine.cloud_fetch", 525, 175),
        ev("bench.wait", 720, 170),
        ev("bench.admit", 895, 150),
        ev("engine.admit", 896, 144),
        ev("engine.prefill", 900, 135),
        ev("engine.prefill_fetch", 990, 40),
        # runs past the window's end: counted up to it
        ev("engine.tick", 1045, 55),
        ev("unrelated", 0, 10)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), device, host])


def test_idle_goes_to_the_innermost_span_cut_at_its_edges():
    out = phases.reduce_phases(profile())
    # the device is idle 50-100, 400-500, 700-900 and 1000-1050
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["idle_s"] == pytest.approx(400e-9)
    idle = phases.idle_by_phase(out)
    want = {"outside": 12 + 191 + 5,            # 50-62, 705-896, 1040-45
            "engine.resolve": 18 + 5,           # 62-80, 700-705
            "engine.edge_launch": 20, "engine.edge_fetch": 20,
            "engine.upload": 30, "engine.cloud_launch": 50,
            "engine.cloud_fetch": 0,            # the device was busy
            "engine.prefill_fetch": 30,         # 1000-1030
            "engine.prefill": 5,                # 1030-1035
            "engine.admit": 4 + 5,              # 896-900, 1035-1040
            "engine.tick": 5}                   # 1045-1050
    assert idle == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert sum(idle.values()) == pytest.approx(out["idle_s"])
    assert list(idle)[0] == "outside"            # largest first


def test_calls_seconds_and_self_seconds():
    p = phases.reduce_phases(profile())["phases"]
    assert p["engine.tick"]["calls"] == 2
    assert p["engine.tick"]["seconds"] == pytest.approx((643 + 5) * 1e-9)
    # 520-525 of the first tick has no child open; the second is all self
    assert p["engine.tick"]["self_seconds"] == pytest.approx(10e-9)
    assert p["engine.resolve"]["calls"] == 2
    assert p["engine.resolve"]["self_seconds"] == pytest.approx(23e-9)
    assert p["engine.cloud_fetch"]["self_seconds"] == pytest.approx(175e-9)
    assert p["engine.prefill"]["self_seconds"] == pytest.approx(95e-9)
    assert p["outside"]["calls"] == 0
    total_self = sum(v["self_seconds"] for v in p.values())
    assert total_self == pytest.approx(1000e-9)


def test_the_harness_reduction_is_unchanged_by_engine_spans():
    out = trace.reduce_profile(profile())
    assert out["busy_s"] == pytest.approx(600e-9)
    idle = dict(out["idle_gaps"])
    assert idle["tick"] == pytest.approx(150e-9)
    assert idle["wait"] == pytest.approx(200e-9)
    assert idle["admit"] == pytest.approx(50e-9)


def test_innermost_partitions_the_range():
    pieces = phases.innermost([(2, 8, "a"), (3, 5, "b"), (5, 6, "c")], 0, 10)
    assert pieces == [(0, 2, "outside"), (2, 3, "a"), (3, 5, "b"),
                      (5, 6, "c"), (6, 8, "a"), (8, 10, "outside")]


def test_span_names_lose_their_arguments():
    assert phases.span_name("engine.tick#rows=8#") == "engine.tick"
    assert phases.span_name("engine.upload") == "engine.upload"


def test_no_window_or_no_device_reads_nothing():
    p = profile()
    p.planes[2].lines[0].events = p.planes[2].lines[0].events[1:]
    assert phases.reduce_phases(p) is None
    assert phases.reduce_phases(NS(planes=[profile().planes[2]])) is None


def test_window_counts_one_engine_tick_per_tick():
    out = phases.window(_tiny.cell(), seed=2 ** 33 + 7, seconds=0.5,
                        trace_on=False, clock=_tiny.Ticker())
    assert out["ticks"] > 0
    assert out["compiles"] == 0
    assert out["phases"]["engine.tick"]["calls"] == out["ticks"]
    assert out["phases"]["engine.edge_fetch"]["calls"] == out["ticks"]
    assert "idle_by_phase" not in out


def test_a_long_tick_is_split_by_innermost_span(monkeypatch):
    monkeypatch.setattr(phases, "STALL_S", 600e-9)
    out = phases.reduce_phases(profile())
    (stall,) = out["stalls"]
    assert stall["span"] == "engine.tick"
    assert stall["seconds"] == pytest.approx(643e-9)
    assert stall["by_span"]["engine.edge_fetch"] == pytest.approx(320e-9)
    assert sum(stall["by_span"].values()) == pytest.approx(643e-9)
    monkeypatch.undo()
    assert "stalls" not in phases.reduce_phases(profile())


def test_op_scope_skips_jax_components():
    assert phases.op_scope("jit(edge_step)/while/body/closed_call/attention/"
                           "kv_write/scatter:") == ("edge_step", "attention")
    assert phases.op_scope("jit(cloud_step_masked)/jit(_where)/select_n:") \
        == ("cloud_step_masked", "-")
    assert phases.op_scope("jit(f)/mlp/dot_general:") == ("f", "mlp")


def test_device_scopes_from_an_hlo_stats_table():
    def row(name, self_us):
        return {"c": [{"v": 1.0}, {"v": name}, {"v": self_us}]}

    table = {"cols": [{"id": "rank"}, {"id": "tf_op_name"},
                      {"id": "total_self_time"}],
             "rows": [row("jit(edge_step)/while/body/mlp/dot_general:", 300.0),
                      row("jit(edge_step)/while/body/mlp/add:", 100.0),
                      row("jit(edge_step)/exit_heads/dot_general:", 50.0),
                      row("jit(edge_step)/while/body/dynamic_slice:", 20.0),
                      row("", 5.0)]}
    assert phases.scopes_from_hlo_stats(table) == {
        "edge_step:mlp": pytest.approx(400e-6),
        "edge_step:exit_heads": pytest.approx(50e-6),
        "edge_step:-": pytest.approx(20e-6)}


def test_the_command_runs_nothing_without_a_tpu(capsys):
    rc = phases.main(["--workload", "deepseek-llm-7b.collm.chat",
                      "--seed", "1", "--seconds", "1"])
    assert rc == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs 1 TPU chip(s)" in out.err


def test_the_command_refuses_an_unknown_cell(capsys):
    assert phases.main(["--workload", "no-such.cell", "--seed", "1",
                        "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""
