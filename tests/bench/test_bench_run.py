"""The harness end to end at a CPU size through the library entry, and the
command's refusal to run without a TPU."""
import os
import subprocess
import sys

import pytest

import _tiny
from bench import spec


@pytest.fixture(scope="module")
def served():
    return _tiny.run(seed=2 ** 33 + 5, seconds=2.0)


def test_open_loop_run_is_correct(served):
    res, info = served["result"], served["info"]
    assert res["correct"], info
    assert not info["errors"]
    assert res["attempted"] >= 20 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   spec.benchmark()["end_to_end"]}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    assert info["checked_tokens"] > 0
    for number, (request, pos, *_rest) in info["check_worst"].items():
        assert number in res["check"] and request >= 0 and pos >= 0
    # every kind of head served something at this theta
    assert {"l1", "l2", "cloud"} <= set(info["served_by"])


def test_no_compilation_inside_the_window(served):
    assert not any("compilations" in e for e in served["info"]["errors"])
    assert served["info"]["calls"]["tick"] > 0


def test_traced_run_reports_per_layer_metrics():
    out = _tiny.run(seed=11, seconds=1.5, trace=True)
    res = out["result"]
    assert res["correct"], out["info"]
    names = {m["name"] for m in spec.benchmark()["per_layer"]}
    assert set(res["metrics"]) <= names
    # host and counter readers read on any platform; device ones need a TPU
    assert {"queue_wait_ms", "slot_occupancy", "cloud_share"} <= \
        set(res["metrics"])


def test_closed_loop_run():
    out = _tiny.run(seed=12, seconds=1.5, loop="closed", stagger_s=0.2)
    assert out["result"]["correct"], out["info"]
    assert out["info"]["finished"] >= 4


def _cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(_tiny.ROOT / "bench" / "run.py"),
                           *args], capture_output=True, text=True, env=env,
                          timeout=300)


def test_cli_refuses_without_a_tpu():
    p = _cli("--workload", "deepseek-llm-7b.collm.chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == 2
    assert "TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_cli_refuses_an_unknown_workload():
    p = _cli("--workload", "nope", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode == 2 and not p.stdout.strip()
