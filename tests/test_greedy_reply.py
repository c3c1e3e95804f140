"""Greedy cloud replies come back as tokens: the served ``cloud_step_masked``
program returns each row's argmax beside its logits, and the reply drain
fetches those (B,) int32 tokens, not the (B, vocab) logits.  The tokens
are the ones a host ``np.argmax`` of the returned logits gives, ties
included; sampling still fetches the logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.collm import CoLLM, CollmConfig
from repro.models.registry import build_model
from repro.serving.engine import BatchScheduler, ServingSystem
from repro.serving.mesh_exec import jit_step
from test_mla_moe import _cfg as _mla_moe_cfg
from test_phases import _host_spans

SLOTS = 3
MAX_NEW = 6


def _dense_cfg() -> ModelConfig:
    return ModelConfig(name="tiny-ee", arch_type="dense", n_layers=4,
                       d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                       vocab_size=256, tie_embeddings=False,
                       exit_layers=(1, 2)).validate()


CONFIGS = {"dense": _dense_cfg, "mla_moe": _mla_moe_cfg}


def _model(name):
    model = build_model(CONFIGS[name]())
    return model, model.init(jax.random.PRNGKey(3))


def _prompts(vocab, n=4):
    rng = np.random.default_rng(5)
    return [rng.integers(0, vocab, 5 + 2 * i).astype(np.int32)
            for i in range(n)]


def _host_argmax(self, rep) -> int:
    """The drain as it was: fetch the wave's logits, argmax on the host."""
    group, row = rep.reply
    if group.get("host") is None:
        if group["logits"] is None:
            group["flush"]()
        group["host"] = np.argmax(np.asarray(group["logits"]), axis=-1)
    return int(group["host"][row])


# random weights: no exit reaches 0.8, so nearly every token is a reply
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_greedy_replies_are_the_host_argmax_of_the_logits(
        name, monkeypatch, tmp_path):
    model, params = _model(name)
    prompts = _prompts(model.cfg.vocab_size)

    def serve():
        return ServingSystem(model, params, CollmConfig(theta=0.8)).generate(
            prompts, MAX_NEW, mode="collm", num_slots=SLOTS)

    jax.profiler.start_trace(str(tmp_path))
    got = serve()
    jax.profiler.stop_trace()
    with monkeypatch.context() as m:
        m.setattr(BatchScheduler, "_reply_token", _host_argmax)
        ref = serve()
    assert got["tokens"] == ref["tokens"]
    assert got["stats"].cloud_requests > 0

    fetches = got["phases"]["engine.cloud_fetch"]["calls"]
    assert got["reply_fetch_bytes"] == 4 * SLOTS * fetches
    spans = [stats for *_, name, stats in _host_spans(str(tmp_path))
             if name == "engine.cloud_fetch"]
    assert len(spans) == fetches
    assert all(s["bytes"] == 4 * SLOTS for s in spans), spans


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_sampling_still_fetches_the_logits(name, monkeypatch):
    model, params = _model(name)
    prompts = _prompts(model.cfg.vocab_size)

    def serve():
        return ServingSystem(model, params, CollmConfig(theta=0.8)).generate(
            prompts, MAX_NEW, mode="collm", num_slots=SLOTS,
            sampler="temperature", temperature=1.0, seed=4)

    got = serve()
    fetches = got["phases"]["engine.cloud_fetch"]["calls"]
    assert fetches > 0
    itemsize = jnp.dtype(model.compute_dtype).itemsize
    assert got["reply_fetch_bytes"] == (itemsize * SLOTS
                                        * model.cfg.vocab_size * fetches)

    def old_drain(self, rep):
        group, row = rep.reply
        if group["np"] is None:
            if group["logits"] is None:
                group["flush"]()
            group["np"] = np.asarray(
                self._pick(np.asarray(group["logits"])))
        return int(group["np"][row])

    with monkeypatch.context() as m:
        m.setattr(BatchScheduler, "_reply_token", old_drain)
        ref = serve()
    assert got["tokens"] == ref["tokens"]


def _tied(params, collm):
    """Two rows of the head set equal, both the best of row 0's logits at
    some step: that row's maximum is an exact tie."""
    b, d = 4, collm.model.cfg.d_model
    upload = {"data": jax.random.normal(jax.random.PRNGKey(9), (b, 1, d),
                                        jnp.float16)}

    def step(p):
        logits, tokens, _ = jit_step(collm, "cloud_step_masked")(
            p, upload, collm.init_cloud_cache(b, 16),
            jnp.full((b,), 3, jnp.int32), jnp.ones((b,), bool), None)
        return np.asarray(logits), np.asarray(tokens)

    best = int(step(params)[0][0].argmax())
    lo, hi = sorted((best, 0 if best else 1))
    head = params["lm_head"]
    params = {**params, "lm_head": head.at[lo].set(head[best])
              .at[hi].set(head[best])}
    logits, tokens = step(params)
    assert logits[0, lo] == logits[0, hi] == logits[0].max()
    assert tokens[0] == lo
    np.testing.assert_array_equal(tokens, np.argmax(logits, axis=-1))
    return params, hi


@pytest.mark.parametrize("tie", [False, True])
def test_shared_cloud_batcher_replies_are_the_host_argmax(tie, monkeypatch):
    model, params = _model("dense")
    vocab = model.cfg.vocab_size
    prompts = _prompts(vocab)
    if tie:
        params, hi = _tied(params, CoLLM(model, CollmConfig()))

    def serve():
        return ServingSystem(model, params, CollmConfig(theta=0.8)
                             ).generate_multi(prompts, MAX_NEW, mode="collm")

    got = serve()
    with monkeypatch.context() as m:
        m.setattr(BatchScheduler, "_reply_token", _host_argmax)
        ref = serve()
    assert got["tokens"] == ref["tokens"]
    assert got["stats"].cloud_requests > 0
    # one engine per client; the batcher's wave spans every client's row
    fetches = got["phases"]["engine.cloud_fetch"]["calls"]
    assert got["reply_fetch_bytes"] == 4 * len(prompts) * fetches
    if tie:
        # every head reads the tied rows: the upper one is never the first
        # maximum, so never served
        assert not any(hi in toks for toks in got["tokens"])
