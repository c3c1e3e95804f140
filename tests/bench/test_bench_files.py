"""Every cell of BENCHMARK.json finds its files by name, and the file keeps
to the benchmark's contract on names, units and bounds."""
import json
import re

import pytest

import _tiny  # noqa: F401  (puts the repository root on sys.path)
from bench import spec, weights

BM = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BM["end_to_end"] + BM["per_layer"]


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51
    for p in BM["paths"]:
        assert (spec.ROOT / p).is_dir()
    assert BM["command"][1] == "bench/run.py"


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_workload_files_exist_and_agree(w):
    c = spec.cell(w["name"])
    assert c["cell"]["config"] == w["config"]
    assert c["cell"]["traffic"] == w["traffic"]
    assert w["chips"] in (1, 4)
    assert len(w["why"]) <= 200
    for name in c["end_to_end"] + c["per_layer"]:
        assert (spec.BENCH / "metrics" / f"{name}.py").exists()
    assert "setup_s" in c["end_to_end"] and len(c["end_to_end"]) >= 2
    assert c["per_layer"]
    cfg = c["config"]
    assert weights.dims(cfg)["layers"] >= max(cfg["assumed"]["exit_layers"])
    assert c["cell"]["max_seq"] >= (c["traffic"]["prompt_len"]["max"]
                                    + c["traffic"]["output_len"]["max"])


@pytest.mark.parametrize("cfg", BM["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    path = spec.ROOT / cfg["file"]
    data = json.loads(path.read_text())
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    assert any(w["config"] == cfg["name"] for w in BM["workloads"])


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    assert callable(spec.reader(m["name"]).read)
    if m in BM["per_layer"]:
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        # every cell that reads it reports the metric it moves
        for w in m.get("workloads", [x["name"] for x in BM["workloads"]]):
            assert m["moves"] in spec.cell(w)["end_to_end"]
    else:
        assert 0.01 <= m["bound"] <= 0.25


def test_names_are_unique():
    for group in (BM["configs"], BM["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("m", BM["per_layer"], ids=lambda m: m["name"])
def test_reader_finds_nothing_in_an_empty_run(m):
    from bench.harness import Run
    c = _tiny.cell()
    run = Run(cfg=c["config"], cell=c["cell"], mix=c["traffic"],
              seconds=1.0, setup_s=1.0, t0=0.0, t_end=1.0, records=[],
              ticks=[])
    assert spec.reader(m["name"]).read(run) is None


def test_missing_workload_is_refused():
    with pytest.raises(spec.SpecError):
        spec.cell("no-such-cell")
