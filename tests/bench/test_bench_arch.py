"""Architecture adapters (``bench/arch``): the registry, the reference
side's independence from the program, layer ranges in the work counts, a
trace's ``scopes`` (filled only for a reader that asks), and an
architecture added by files alone: the program's sparse-expert block,
whose adapter (``arch/tiny_moe.py``) and configuration (``_moe.py``)
live with these tests."""
import json
import os
import subprocess
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

import _moe
import _tiny
from bench import (arch, flops, harness, phases, reference, spec, trace,
                   weights)
from bench.driver import build_params, model_config
from test_bench_trace import profile

CHIP_CELLS = ("deepseek-llm-7b.collm.chat", "yi-6b.collm.summarize")


def test_a_configuration_without_model_type_is_llama():
    assert "model_type" not in _tiny.CONFIG
    llama = arch.load("llama")
    assert arch.of(_tiny.CONFIG) is llama
    for name in CHIP_CELLS:
        assert arch.of(spec.cell(name)["config"]) is llama


def test_an_unknown_model_type_names_the_file_looked_for():
    with pytest.raises(spec.SpecError,
                       match=r"bench/arch/no_such_block\.py"):
        arch.of({"model_type": "no_such_block"})


def test_an_adapter_outside_bench_is_found_on_the_search_path():
    with pytest.raises(spec.SpecError, match="tiny_moe"):
        arch.of(_moe.CONFIG)
    with _moe.registered():
        assert arch.of(_moe.CONFIG).__file__ == str(_moe.ARCH
                                                    / "tiny_moe.py")


# loads every adapter, draws each block's tensors and runs the reference
# over a small batch; prints the program's modules that got loaded
_REFERENCE_SIDE = """
import json, pathlib, sys
import numpy as np
from bench import arch, reference, weights
cfgs, extra = json.loads(sys.argv[1]), pathlib.Path(sys.argv[2])
arch.SEARCH = arch.SEARCH + (extra,)
for d in arch.SEARCH:
    for p in sorted(d.glob("*.py")):
        if p.stem != "__init__":
            arch.load(p.stem)
batch = {"tokens": np.arange(12, dtype=np.int32)[None] % 200,
         "cloud_keys": np.ones((1, 12), bool),
         "wire": np.arange(12)[None] >= 6,
         "read": np.arange(5, 12, dtype=np.int32)[None]}
key = weights.base_key(1)
for cfg in cfgs:
    for name in arch.of(cfg).tensors(cfg):
        weights.tensor(cfg, key, name, 0)
    for lowp in (False, True):
        reference.readings(cfg, 1, (1, 2), batch, lowp=lowp)
print(json.dumps(sorted(m for m in sys.modules
                        if m == "repro" or m.startswith("repro."))))
"""


def test_the_reference_side_imports_nothing_of_the_program():
    # the program is importable: only what the code imports keeps it out
    path = os.pathsep.join([str(_tiny.ROOT), str(_tiny.ROOT / "src")])
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=path)
    p = subprocess.run(
        [sys.executable, "-c", _REFERENCE_SIDE,
         json.dumps([_tiny.CONFIG, _moe.CONFIG]), str(_moe.ARCH)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    assert json.loads(p.stdout.splitlines()[-1]) == []


@pytest.fixture(scope="module")
def moe_run():
    return _moe.run(seed=2 ** 33 + 9, control=True)


def test_an_architecture_added_by_files_alone_serves_correctly(moe_run):
    res, info = moe_run["result"], moe_run["info"]
    assert res["correct"], info
    assert not info["errors"]
    assert res["attempted"] >= 20 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   spec.benchmark()["end_to_end"]}
    assert {"l1", "l2", "cloud"} <= set(info["served_by"])


def test_its_fp8_control_is_not_correct(moe_run):
    assert moe_run["info"]["control"]["correct"] is False


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_moe_program_weights_are_the_reference_weights(dtype):
    from repro.models.transformer import Model
    cfg = {**_moe.CONFIG, "torch_dtype": dtype}
    key = weights.base_key(2 ** 33 + 1)
    with _moe.registered():
        model = Model(model_config(cfg), param_dtype=weights.served_dtype(cfg))
        moe = model.cfg.moe
        assert moe.capacity_factor * moe.top_k == moe.num_experts
        params = build_params(model, cfg, key)
        si = len(model.segments) - 1
        last = model.segments[si].end - 1
        for leaf in ("router", "w_gate", "w_up", "w_down"):
            got = params["segments"][si]["moe"][leaf][-1]
            want = reference.w(cfg, key, f"moe/{leaf}", last)
            assert np.array_equal(np.asarray(got, np.float32),
                                  np.asarray(want))
    # the program keeps its router in float32, holding the served values
    assert params["segments"][si]["moe"]["router"].dtype == jnp.float32


def test_moe_step_work_hand_count():
    m = dict(d=64, h=4, kv=2, hd=16, ff=128, vocab=256, experts=4, top_k=2)
    attn = m["d"] * m["hd"] * (4 + 2 * 2) + 4 * 16 * 64            # 12288
    expert = 3 * m["d"] * m["ff"]                                   # 24576
    router = m["d"] * m["experts"]
    per_token = 2 * (attn + router + 2 * expert)
    head = 2 * m["vocab"] * m["d"]
    with _moe.registered():
        cfg = _moe.CONFIG
        f, b = flops.step_work(cfg, [2, 3], 1, [5, 7])
        assert f == sum(2 * per_token + 2 * 4 * 4 * 16 * c + head
                        for c in (5, 7))
        # each layer's attention, router, norms and all four experts; then
        # the output head and its norm
        read = (2 * (attn + router + 4 * expert + 2 * m["d"])
                + m["vocab"] * m["d"] + m["d"])
        kv = 2 * (2 * 2 * 16 * 2) * (5 + 7)
        assert b == (read * 2 + kv)
        assert flops.prefill_flops(cfg, [0], 2, 10) == (
            per_token * 10 + 2 * 4 * 16 * 10 * 11 + 2 * head)
        assert flops.count_params(cfg, (1, 2)) == (
            2 * m["vocab"] * m["d"]
            + 4 * (attn + router + 4 * expert + 2 * m["d"]) + 3 * m["d"])


def test_a_count_of_blocks_is_refused_where_the_blocks_differ(monkeypatch):
    with _moe.registered():
        cfg = _moe.CONFIG
        adapter = arch.of(cfg)
        want = (flops.step_work(cfg, range(2), 1, [5]),
                flops.prefill_flops(cfg, range(2), 1, 9),
                flops.kv_bytes_per_token(cfg, range(2)))
        monkeypatch.setattr(adapter, "uniform", lambda cfg: False)
        for count in (lambda: flops.step_work(cfg, 2, 1, [5]),
                      lambda: flops.prefill_flops(cfg, 2, 1, 9),
                      lambda: flops.kv_bytes_per_token(cfg, 2),
                      lambda: flops.count_params(cfg, (), layers=2)):
            with pytest.raises(ValueError, match="blocks differ"):
                count()
        assert (flops.step_work(cfg, range(2), 1, [5]),
                flops.prefill_flops(cfg, range(2), 1, 9),
                flops.kv_bytes_per_token(cfg, range(2))) == want
        assert flops.count_params(cfg) > 0


@pytest.mark.parametrize("name", CHIP_CELLS)
def test_a_layer_range_counts_what_the_count_does(name):
    cfg = spec.cell(name)["config"]
    l1, l2 = cfg["assumed"]["exit_layers"]
    assert flops.step_work(cfg, range(l2), 2, [30, 90]) == \
        flops.step_work(cfg, l2, 2, [30, 90])
    assert flops.prefill_flops(cfg, range(l1), 1, 77) == \
        flops.prefill_flops(cfg, l1, 1, 77)
    assert flops.kv_bytes_per_token(cfg, range(5, 9)) == \
        flops.kv_bytes_per_token(cfg, 4)
    assert flops.count_params(cfg, (l1, l2), layers=range(
        cfg["num_hidden_layers"])) == flops.count_params(cfg, (l1, l2))


def _hlo_stats_table():
    """xprof's hlo_stats table: one row per XLA op."""
    def row(name, self_us):
        return {"c": [{"v": 1.0}, {"v": name}, {"v": self_us}]}

    return {"cols": [{"id": "rank"}, {"id": "tf_op_name"},
                     {"id": "total_self_time"}],
            "rows": [row("jit(edge_step)/while/body/mlp/dot_general:", 300.0),
                     row("jit(edge_step)/while/body/mlp/add:", 100.0),
                     row("jit(edge_step)/exit_heads/dot_general:", 50.0),
                     row("jit(edge_step)/while/body/dynamic_slice:", 20.0),
                     row("", 5.0)]}


def _xprof(monkeypatch, convert):
    """xprof's converter, as ``convert(paths, tool, options)``."""
    conv = types.ModuleType("xprof.convert.raw_to_tool_data")
    conv.xspace_to_tool_data = convert
    package = types.ModuleType("xprof.convert")
    package.raw_to_tool_data = conv
    monkeypatch.setitem(sys.modules, "xprof", types.ModuleType("xprof"))
    monkeypatch.setitem(sys.modules, "xprof.convert", package)
    monkeypatch.setitem(sys.modules, "xprof.convert.raw_to_tool_data", conv)


def test_scopes_are_none_without_xprof(monkeypatch, capsys):
    for name in ("xprof", "xprof.convert", "xprof.convert.raw_to_tool_data"):
        monkeypatch.setitem(sys.modules, name, None)
    assert trace.scopes("t.xplane.pb") is None
    assert "device scopes unavailable" in capsys.readouterr().err


def test_scopes_are_none_where_the_converter_fails(monkeypatch, capsys):
    def fails(paths, tool, options):
        raise RuntimeError("cannot read this trace")

    _xprof(monkeypatch, fails)
    assert trace.scopes("t.xplane.pb") is None
    assert "cannot read this trace" in capsys.readouterr().err


def test_scopes_come_from_an_hlo_stats_table(monkeypatch):
    _xprof(monkeypatch, lambda paths, tool, options: (
        json.dumps(_hlo_stats_table()), "application/json"))
    assert trace.scopes("t.xplane.pb") == {
        "edge_step:mlp": pytest.approx(400e-6),
        "edge_step:exit_heads": pytest.approx(50e-6),
        "edge_step:-": pytest.approx(20e-6)}


def test_an_op_without_a_framework_name_counts_under_its_program():
    def row(program_id, name, self_us):
        return {"c": [{"v": program_id}, {"v": name}, {"v": self_us}]}

    table = {"cols": [{"id": "program_id"}, {"id": "tf_op_name"},
                      {"id": "total_self_time"}],
             "rows": [row("7", "jit(edge_step)/while/body/mlp/add:", 300.0),
                      row("7", "", 40.0),
                      row("9", "jit(_scatter_row)/scatter:", 2.0),
                      row("9", "", 60.0),
                      row("11", "", 5.0)]}
    assert trace.scopes_from_hlo_stats(table) == {
        "edge_step:mlp": pytest.approx(300e-6),
        "_scatter_row:-": pytest.approx(62e-6),
        "edge_step:-": pytest.approx(40e-6)}


@pytest.mark.parametrize("asks", [False, True])
def test_a_traced_run_converts_scopes_only_for_a_reader_that_asks(
        asks, monkeypatch, tmp_path):
    import jax
    calls, seen = [], []
    summary = trace.reduce_profile(profile())
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(trace, "reduce_dir", lambda d: dict(summary))
    monkeypatch.setattr(trace, "find_xplane", lambda d: "t.xplane.pb")
    monkeypatch.setattr(trace, "scopes", lambda path: calls.append(path)
                        or {"edge_step:mlp": 1.0})
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: None)
    stub = types.SimpleNamespace(
        read=lambda run: seen.append(run.trace.get("scopes")) or 1.0)
    if asks:
        stub.SCOPES = True
    monkeypatch.setattr(spec, "reader", lambda name: stub)
    c = _tiny.cell()
    c["per_layer"], c["units"] = ["stub"], {"stub": "%"}
    out = harness.run(c, seed=5, seconds=0.3, trace_on=True,
                      t_start=0.0, clock=_tiny.Ticker())
    assert out["result"]["metrics"]["stub"]["value"] == 1.0
    assert calls == (["t.xplane.pb"] if asks else [])
    assert seen == [{"edge_step:mlp": 1.0} if asks else None]


def test_phases_reexports_the_scope_reduction():
    for name in ("op_scope", "scopes_from_hlo_stats", "device_scopes",
                 "JAX_COMPONENTS"):
        assert getattr(phases, name) is getattr(trace, name)
