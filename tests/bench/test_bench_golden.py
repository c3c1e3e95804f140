"""The architecture adapters changed how the harness is built, not what it
reads: for the same seed, the weights, the program's parameter tree, the
reference's readings and a whole tiny run (check numbers, and the value
of every reader that ``golden.json`` holds) are exactly what the harness
gave before the move (``golden.json``, written by ``_golden.py``).  A
metric or a key of the result added later is allowed beside them."""
import json

import _golden
import _tiny
from bench import arch

GOLDEN = json.loads(_golden.PATH.read_text())


def test_weights_are_unchanged():
    assert _golden.tensors() == GOLDEN["tensors"]


def test_program_parameters_are_unchanged():
    assert _golden.program_params() == GOLDEN["program_params"]


def test_reference_readings_are_unchanged():
    assert _golden.readings() == GOLDEN["readings"]


def _holds(want, got, at="run"):
    """Every key of ``want`` is in ``got`` with the same value, level by
    level; ``got`` may hold more (a metric or a key added later)."""
    if not isinstance(want, dict):
        assert got == want, at
        return
    assert isinstance(got, dict), at
    for k, v in want.items():
        assert k in got, f"{at}.{k}"
        _holds(v, got[k], f"{at}.{k}")


def test_a_whole_tiny_run_is_unchanged(tmp_path):
    want = GOLDEN["run"]
    got = _golden.run(tmp_path, names=sorted(want["result"]["metrics"]))
    assert set(got["result"]["metrics"]) == set(want["result"]["metrics"])
    _holds(want["result"], got["result"], "result")
    _holds(want["info"], got["info"], "info")


def test_the_golden_tensors_are_the_llama_blocks():
    llama = arch.load("llama")
    assert set(llama.tensors(_tiny.CONFIG)) == set(_golden.LAYER_NAMES)
    assert set(llama.LEAVES.values()) == set(_golden.LAYER_NAMES)
