"""Per-architecture smoke tests (assignment requirement): a REDUCED variant
of each family (2 layers, d_model<=512, <=4 experts) runs one forward and
one train step on CPU with correct shapes and no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ASSIGNED, get_smoke_config
from repro.models.registry import build_model
from repro.training.optim import AdamWConfig, init_adamw
from repro.training.train_step import make_train_step


def _batch(cfg, rng, b=2, s=16):
    batch = {"tokens": jax.random.randint(rng, (b, s), 0, cfg.vocab_size),
             "labels": jax.random.randint(rng, (b, s), 0, cfg.vocab_size),
             "mask": jnp.ones((b, s), jnp.float32)}
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            rng, (b, cfg.encoder_seq, cfg.d_model)) * 0.1
    if cfg.vision_tokens:
        batch["patches"] = jax.random.normal(
            rng, (b, cfg.vision_tokens, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.num_experts <= 4
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    b, s = 2, 16
    batch = _batch(cfg, rng, b, s)

    out = model.forward_train(params, batch)
    want_s = s + (cfg.vision_tokens or 0)
    assert out["logits"].shape == (b, want_s, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(out["logits"])))
    for l, xl in out["exit_logits"].items():
        assert xl.shape == (b, want_s, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(xl)))

    step = make_train_step(model, AdamWConfig(lr=1e-3, warmup_steps=2,
                                              total_steps=10))
    opt = init_adamw(params)
    params2, opt2, mets = step(params, opt, batch)
    assert bool(jnp.isfinite(mets["loss"]))
    assert bool(jnp.isfinite(mets["grad_norm"]))
    # parameters actually moved
    moved = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(a - b_))),
                         params, params2)
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_decode_matches_teacher_forcing(arch):
    cfg = get_smoke_config(arch)
    if cfg.moe:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    model = build_model(cfg)
    rng = jax.random.PRNGKey(1)
    params = model.init(rng)
    b, s = 2, 12
    batch = _batch(cfg, rng, b, s)
    batch.pop("labels"), batch.pop("mask")
    out = model.forward_train(params, batch)
    ref = out["logits"][:, -1]

    caches = model.init_cache(b, 64)
    pb = dict(batch)
    pb["tokens"] = batch["tokens"][:, :-1]
    _, _, caches, _ = model.prefill(params, pb, caches)
    pos = jnp.asarray((cfg.vision_tokens or 0) + s - 1, jnp.int32)
    xh, _, _, _ = model.decode_step(params, batch["tokens"][:, -1:], caches,
                                    pos)
    got = model.logits(params, xh)[:, 0]
    assert float(jnp.max(jnp.abs(got - ref))) < 2e-4
