"""The comparison that decides ``correct`` fails what it should: the fp8
control in the program's place, and the program with its timed path broken
in each way a served cell can break."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _tiny
from bench import check, weights
from bench.driver import build_params, model_config

LIMITS = _tiny.CELL["check"]["limits"]


def test_program_passes_and_control_fails():
    out = _tiny.run(seed=7, control=True)
    assert out["result"]["correct"], out["info"]
    assert out["info"]["control"]["correct"] is False, out["info"]["control"]


def _edge_state_unchanged(orig):
    def step(self, params, token, caches, pos, block_tbl=None):
        return orig(self, params, token, caches, pos, block_tbl)._replace(
            caches=caches)
    return "edge_step", step


def _cloud_state_unchanged(orig):
    def step(self, params, upload, caches, pos, mask, block_tbl=None):
        logits, _ = orig(self, params, upload, caches, pos, mask, block_tbl)
        return logits, caches
    return "cloud_step_masked", step


def _half_batch_left_out(orig):
    def step(self, params, upload, caches, pos, mask, block_tbl=None):
        keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
        logits, new = orig(self, params, upload, caches, pos, mask & keep,
                           block_tbl)
        return jnp.where(keep[:, None], logits, 0.0), new
    return "cloud_step_masked", step


def _token_altered(orig):
    def step(self, params, token, caches, pos, block_tbl=None):
        out = orig(self, params, token, caches, pos, block_tbl)
        return out._replace(token=(out.token + 1)
                            % self.model.cfg.vocab_size)
    return "edge_step", step


@pytest.mark.parametrize("fault", [_edge_state_unchanged,
                                   _cloud_state_unchanged,
                                   _half_batch_left_out, _token_altered])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.core.collm import CoLLM
    name = "edge_step" if fault in (_edge_state_unchanged,
                                    _token_altered) else "cloud_step_masked"
    attr, step = fault(getattr(CoLLM, name))
    monkeypatch.setattr(CoLLM, attr, step)
    out = _tiny.run(seed=6)
    assert not out["result"]["correct"]
    # the numbers fail it, not an error of the run
    numbers = {k: v["value"] for k, v in out["result"]["check"].items()}
    assert check.verdict(numbers, [], LIMITS)[0] is False


def _one_token(gap1, gap2, c1, c2):
    """Readings of one position at the two exits (the served token's logit
    lies ``gap`` below the best) and the exits' confidences."""
    def head(gap, conf):
        return {"best": np.array([[1.0]]), "at": np.array([[[1.0 - gap]]]),
                "conf": np.array([[conf]])}
    return {"l1": head(gap1, c1), "l2": head(gap2, c2)}


@pytest.mark.parametrize("gaps,confs,want", [
    # served at l2 (c1 < theta <= c2) with a near tie there; l1's argmax
    # is the token: read at l2, not at l1's contradicting gate
    ((0.0, 0.02), (0.46, 1.3), {"gap_exit": 0.02, "gate_miss": 0.0}),
    # served at l1 with a near tie there; l2 agrees with the token
    ((0.02, 0.0), (1.6, 1.2), {"gap_exit": 0.02, "gate_miss": 0.0}),
    # a token neither exit would serve reads its smaller contradiction
    ((2.0, 3.0), (1.6, 0.5), {"gap_exit": 2.0, "gate_miss": 0.0}),
])
def test_edge_served_first_token_is_read_at_its_consistent_exit(
        gaps, confs, want):
    theta = 0.01
    ref = _one_token(*gaps, *(c * theta for c in confs))
    where = {}
    got = check.numbers(ref, np.array([["exit"]], dtype=object), 0,
                        np.array([[True]]), theta, LIMITS, where)
    assert got["gap_cloud"] == 0.0
    for k, v in want.items():
        assert got[k] == pytest.approx(v, abs=1e-9)
    assert where["gap_exit"][:3] == [0, 0, "exit"]
    assert where["gap_exit"][4:] == pytest.approx(list(confs))


def test_program_weights_are_the_reference_weights():
    from repro.models.transformer import Model
    cfg = _tiny.CONFIG
    model = Model(model_config(cfg), param_dtype=jnp.bfloat16)
    key = weights.base_key(2 ** 33 + 1)
    params = build_params(model, cfg, key)
    seg = model.segments[2]               # layers 3 and 4 (after the exits)
    assert seg.length == 2
    got = params["segments"][2]["mlp"]["w_up"][1]
    want = weights.tensor(cfg, key, "mlp/w_up", seg.start + 1)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    assert np.array_equal(
        np.asarray(params["exit_norms"]["2"], np.float32),
        np.asarray(weights.tensor(cfg, key, "exit_norm", 2), np.float32))
    assert jax.tree.structure(params) == jax.tree.structure(
        model.param_specs())
