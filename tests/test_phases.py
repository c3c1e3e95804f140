"""The serving engine's phase spans and counters (repro.serving.phases)."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.collm import CollmConfig
from repro.models.registry import build_model
from repro.serving.engine import ServingSystem
from repro.serving.phases import Phases, total

TICK_PHASES = ("engine.resolve", "engine.edge_launch", "engine.edge_fetch",
               "engine.upload", "engine.cloud_launch")


def test_phases_count_nested_calls():
    ph = Phases()
    for _ in range(3):
        with ph.phase("tick", rows=2):
            with ph.phase("edge_fetch", rows=2):
                pass
    snap = ph.snapshot()
    assert list(snap) == ["engine.edge_fetch", "engine.tick"]
    assert snap["engine.tick"]["calls"] == 3
    assert 0 <= snap["engine.edge_fetch"]["seconds"] \
        <= snap["engine.tick"]["seconds"]
    both = total([snap, snap])
    assert both["engine.tick"]["calls"] == 6
    ph.reset()
    assert ph.snapshot() == {}


def test_phase_counts_a_raising_body():
    ph = Phases()
    with pytest.raises(ValueError):
        with ph.phase("admit"):
            raise ValueError
    assert ph.snapshot()["engine.admit"]["calls"] == 1


@pytest.fixture(scope="module")
def system(tiny_ee_cfg):
    model = build_model(tiny_ee_cfg)
    params = model.init(jax.random.PRNGKey(0))
    # random weights: no exit is confident at 0.8, every token is a cloud
    # request, so each tick runs every tick phase
    return ServingSystem(model, params, CollmConfig(theta=0.8))


def _prompts(n):
    rng = np.random.default_rng(0)
    return [rng.integers(0, 256, 6 + 3 * i).astype(np.int32)
            for i in range(n)]


def test_generate_reports_one_tick_and_one_prefill_per_call(system):
    r = system.generate(_prompts(3), 6, mode="collm", num_slots=2)
    ph = r["phases"]
    (sched,) = system._schedulers.values()
    assert ph["engine.tick"]["calls"] == sched._tick_no > 0
    assert ph["engine.prefill"]["calls"] == 3
    # one edge fetch and, collm, one cloud fetch per prefill
    assert ph["engine.prefill_fetch"]["calls"] == 6
    for name in TICK_PHASES:
        assert ph[name]["calls"] >= 1
    assert ph["engine.edge_fetch"]["calls"] == ph["engine.tick"]["calls"]
    # children's seconds never exceed their parent's
    assert sum(ph[n]["seconds"] for n in TICK_PHASES) \
        <= ph["engine.tick"]["seconds"]
    assert ph["engine.cloud_fetch"]["seconds"] \
        <= ph["engine.resolve"]["seconds"]
    assert ph["engine.prefill_fetch"]["seconds"] \
        <= ph["engine.prefill"]["seconds"] <= ph["engine.admit"]["seconds"]
    # a second run reports its own counts, not the sum of both
    again = system.generate(_prompts(3), 6, mode="collm", num_slots=2)
    assert again["phases"]["engine.prefill"]["calls"] == 3


def test_generate_multi_sums_the_engines(system):
    r = system.generate_multi(_prompts(2), 4, mode="collm")
    assert r["phases"]["engine.prefill"]["calls"] == 2
    assert r["phases"]["engine.cloud_fetch"]["calls"] >= 1


def _host_spans(trace_dir):
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name.split("#")[0], dict(ev.stats)))
    return spans


def test_spans_land_in_the_profiler_trace_nested_under_the_tick(
        system, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    r = system.generate(_prompts(2), 5, mode="collm", num_slots=2)
    jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    by_name = {}
    for s in spans:
        by_name.setdefault(s[2], []).append(s)
    for name, v in r["phases"].items():
        assert len(by_name[name]) == v["calls"], name
    ticks = by_name["engine.tick"]
    admits = by_name["engine.admit"]
    assert all(t[3]["rows"] >= 1 for t in ticks)

    def inside(span, parents):
        return any(p[0] <= span[0] and span[1] <= p[1] for p in parents)

    for name in TICK_PHASES + ("engine.cloud_fetch",):
        assert all(inside(s, ticks) for s in by_name[name]), name
    assert all(inside(s, by_name["engine.prefill"])
               for s in by_name["engine.prefill_fetch"])
    assert all(inside(s, admits) for s in by_name["engine.prefill"])
    assert {s[3]["tokens"] for s in by_name["engine.prefill"]} == {6, 9}
