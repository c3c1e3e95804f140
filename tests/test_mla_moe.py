"""DeepSeek-V2's mechanisms in the program at a small size, float32:
absorbed latent-attention decode against the expanded form, the served
expert layer (dropless at any routing skew; a held share of the experts
adds up to the whole layer), YaRN's closed form, the paged layout's
refusal of a latent cache, and the held-expert routing counter."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnScaling
from repro.core.collm import CoLLM, CollmConfig
from repro.models.attention import (init_mla, init_mla_cache, mla_decode,
                                    mla_forward, mla_softmax_scale)
from repro.models.common import apply_rope, yarn_freqs
from repro.models.moe import _swiglu, init_moe, moe_served, route
from repro.models.transformer import Model
from repro.serving.engine import ServingSystem

served = jax.jit(moe_served, static_argnums=1)
YARN = YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                   beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                   mscale_all_dim=0.707)


def _cfg(**moe) -> ModelConfig:
    """Four layers at width 64: a dense first layer, then expert layers of
    16 experts, top 6, two shared, probabilities not renormalised."""
    kw = dict(num_experts=16, top_k=6, expert_d_ff=32, n_shared_experts=2,
              norm_topk_prob=False, first_dense_layers=1)
    kw.update(moe)
    return ModelConfig(
        name="tiny-v2", arch_type="moe", n_layers=4, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=96, vocab_size=128, norm_eps=1e-6,
        rope_scaling=dataclasses.replace(
            YARN, factor=4.0, original_max_position_embeddings=16),
        mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                      qk_rope_head_dim=4, v_head_dim=8),
        moe=MoEConfig(**kw), exit_layers=(2, 3)).validate()


# float32 logits of a width-64 model agree to a few ulps of their scale;
# bf16 rounding alone moves them by ~1e-2 of it
TOL = 2e-5


def _close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b))) <= tol * float(np.max(np.abs(b)))


@pytest.mark.parametrize("masked", [False, True])
def test_absorbed_decode_matches_expanded_attention(masked):
    """Decode of the last position through the cached latent (absorbed)
    gives the expanded full-sequence attention's output there.  With a
    write mask the masked rows' latent stays as it was."""
    cfg = _cfg()
    p = init_mla(jax.random.PRNGKey(0), cfg)
    b, s = 3, 11
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model))
    forward = jax.jit(mla_forward, static_argnums=1)
    want, _ = forward(p, cfg, x)
    cache = init_mla_cache(cfg, b, 16)
    _, cache = forward(p, cfg, x[:, :-1], cache=cache)
    mask = jnp.array([True, False, True]) if masked else None
    got, new = jax.jit(mla_decode, static_argnums=1)(
        p, cfg, x[:, -1:], cache, jnp.full((b,), s - 1), write_mask=mask)
    rows = [0, 2] if masked else [0, 1, 2]
    assert _close(got[rows, 0], want[rows, -1])
    if masked:
        for k in ("ckv", "kpe", "pos"):
            np.testing.assert_array_equal(np.asarray(new[k][1]),
                                          np.asarray(cache[k][1]))


def _dense_moe(params, cfg, x):
    """Each token through its chosen held experts one at a time, plus the
    shared experts: the layer's definition, with nothing sorted or
    grouped."""
    moe = cfg.moe
    first, n = moe.held_range
    xf = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    top_p, top_e = (np.asarray(a) for a in route(params, cfg, jnp.asarray(
        xf, jnp.float32)))
    silu = lambda v: v / (1 + np.exp(-v))
    w = {k: np.asarray(v, np.float64) for k, v in params.items()
         if k != "router" and k != "shared"}
    out = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        for j in range(moe.top_k):
            e = int(top_e[t, j]) - first
            if 0 <= e < n:
                h = silu(xf[t] @ w["w_gate"][e]) * (xf[t] @ w["w_up"][e])
                out[t] += top_p[t, j] * (h @ w["w_down"][e])
    if "shared" in params:
        sh = {k: np.asarray(v, np.float64)
              for k, v in params["shared"].items()}
        out += (silu(xf @ sh["w_gate"]) * (xf @ sh["w_up"])) @ sh["w_down"]
    return out.reshape(x.shape)


@pytest.mark.parametrize("skew", [0.0, 3.0, 30.0])
@pytest.mark.parametrize("held", [None, (4, 8)])
def test_served_moe_drops_no_token(skew, held):
    """At any skew, up to every token choosing the same experts, each
    (token, held expert) pair is computed: the layer equals its per-token
    definition and counts every pair that landed on a held expert."""
    cfg = _cfg(held=held)
    params = init_moe(jax.random.PRNGKey(2), cfg)
    bias = jnp.zeros((cfg.moe.num_experts,)).at[4:10].set(skew)
    params["router"] = params["router"] + bias       # pulls tokens to 4..9
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.d_model))
    out, counts = served(params, cfg, x)
    assert _close(out, _dense_moe(params, cfg, x))
    _, top_e = route(params, cfg, x.reshape(-1, cfg.d_model))
    first, n = cfg.moe.held_range
    held_pairs = ((top_e >= first) & (top_e < first + n)).reshape(2, -1)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(held_pairs.sum(-1)))


@pytest.mark.parametrize("layer", [0, 2])
def test_a_layer_of_a_stack_reads_its_own_experts(layer):
    """A scanned layer is handed its segment's whole stack of expert
    weights and its index: the layer equals the same layer handed its own
    experts, and the grouped matmul over the stack's groups (megablox's
    kernel, interpreted here) gives the layer's groups' rows."""
    from repro.models.moe import _grouped
    cfg = _cfg(held=(4, 8))
    layers = [init_moe(jax.random.PRNGKey(10 + i), cfg) for i in range(3)]
    names = ("w_gate", "w_up", "w_down")
    stack = {k: jnp.stack([p[k] for p in layers]) for k in names}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, cfg.d_model))
    own, own_n = served(layers[layer], cfg, x)
    got, got_n = served({**layers[layer], **stack,
                         "layer": jnp.int32(layer)}, cfg, x)
    assert _close(got, own)
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(own_n))
    sizes = jnp.array([3, 0, 5, 2, 0, 4, 1, 0], jnp.int32)
    xs = jax.random.normal(jax.random.PRNGKey(7), (20, cfg.d_model))
    flat = stack["w_gate"].reshape(-1, *stack["w_gate"].shape[2:])
    at = jnp.zeros((flat.shape[0],), jnp.int32).at[
        layer * 8:layer * 8 + 8].set(sizes)
    want = jax.lax.ragged_dot(xs, stack["w_gate"][layer], sizes)
    kern = _grouped(xs, flat, at, interpret=True)
    rows = int(sizes.sum())
    assert _close(kern[:rows], want[:rows])


def test_four_held_shares_add_up_to_the_whole_layer():
    """Four devices of an expert-parallel layer, each holding a quarter of
    the experts: their routed parts, with the shared experts counted once,
    are the layer computed with every expert."""
    whole = _cfg()
    params = init_moe(jax.random.PRNGKey(4), whole)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, whole.d_model))
    want, want_n = served(params, whole, x)
    no_shared = {k: v for k, v in params.items() if k != "shared"}
    routed, n = 0.0, 0
    for first in range(0, 16, 4):
        cfg = dataclasses.replace(whole, moe=dataclasses.replace(
            whole.moe, held=(first, 4), n_shared_experts=0))
        share = {**no_shared, **{k: params[k][first:first + 4]
                                 for k in ("w_gate", "w_up", "w_down")}}
        out, cnt = served(share, cfg, x)
        routed, n = routed + out, n + cnt
    shared = _swiglu(params["shared"], x)
    assert _close(routed + shared, want)
    np.testing.assert_array_equal(np.asarray(n), np.asarray(want_n))
    assert int(want_n.sum()) == 2 * 9 * 6


def test_yarn_matches_its_closed_form():
    """DeepSeek-V2-Lite's rotary settings (64 dims, theta 1e4, factor 40
    over 4096 positions, beta 32 and 1, mscale 0.707 twice): pair i keeps
    its base frequency theta^(-2i/64) up to the dimension where it turns
    32 times over the original context, takes it over 40 from where it
    turns once, and blends linearly in between; cos/sin keep magnitude 1;
    the softmax scale is 192^-1/2 (0.1 * 0.707 * ln 40 + 1)^2."""
    dim, base, ctx = 64, 1e4, 4096

    def boundary(turns):
        return dim * math.log(ctx / (turns * 2 * math.pi)) / (2 * math.log(
            base))

    lo, hi = math.floor(boundary(32)), math.ceil(boundary(1))
    assert (lo, hi) == (10, 23)
    freq = base ** (-np.arange(32) * 2 / dim)
    ramp = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    want = freq * (1 - ramp) + freq / 40 * ramp
    np.testing.assert_allclose(np.asarray(yarn_freqs(dim, base, YARN)), want,
                               rtol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 50, 2, dim))
    y = apply_rope(x, jnp.arange(50) * 97, base, YARN)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    cfg = dataclasses.replace(_cfg(), rope_scaling=YARN, mla=MLAConfig(
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128))
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                   rel=1e-12)


def test_a_paged_pool_refuses_latent_attention():
    model = Model(_cfg())
    with pytest.raises(ValueError, match="latent"):
        CoLLM(model, CollmConfig(kv_layout="paged"))
    with pytest.raises(ValueError, match="latent"):
        model.init_paged_cache(2, 8, 4)


def test_held_expert_tokens_count_the_held_share():
    """Served through the scheduler with a quarter of 64 experts held, on
    a router that spreads tokens about evenly: the counter reads about
    16/64 of the decoded tokens x the edge's expert layers x top 6."""
    cfg = _cfg(num_experts=64, held=(0, 16))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(4)]
    r = ServingSystem(model, params, CollmConfig(theta=0.5)).generate(
        prompts, 17, mode="collm")
    st = r["stats"]
    decoded = sum(len(t) - 1 for t in r["tokens"])
    edge_moe_layers = cfg.exit_layers[-1] - cfg.moe.first_dense_layers
    expect = decoded * edge_moe_layers * 6 * 16 / 64
    assert decoded == 4 * 16
    assert 0.75 * expect <= st.held_expert_tokens <= 1.25 * expect
