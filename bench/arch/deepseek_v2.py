"""DeepSeek-V2's block (arXiv:2405.04434; Hugging Face ``deepseek_v2``):
multi-head latent attention (MLA) with YaRN rotary positions, then a
SwiGLU MLP in the first ``first_k_dense_replace`` layers and a
mixture of experts in the others.

Attention, per head: a query of ``qk_nope_head_dim + qk_rope_head_dim``
(no q-LoRA); the keys and values come from one latent ``c_kv`` of
``kv_lora_rank`` values (RMSNorm'd), expanded by ``kv_b_proj`` into each
head's ``qk_nope`` key part and its value; the key's rotary part is one
``qk_rope_head_dim`` vector shared by every head.  The softmax scale is
qk_head_dim^-1/2 times YaRN's mscale(mscale_all_dim) squared.

Experts: a float32 softmax router over every routed expert, its top
``num_experts_per_tok`` kept as they are (``norm_topk_prob`` false;
times ``routed_scaling_factor``, which must be 1), each a SwiGLU of
``moe_intermediate_size``; plus ``n_shared_experts`` shared experts,
which are one SwiGLU of their joint width, on every token.

The configuration holds ``n_routed_experts`` of the published count (its
``published`` group), experts ``held_experts`` = [first, first + count):
one chip's share of an expert-parallel layer.  The router keeps the
published width; what the experts held elsewhere would add is left out
here as in the program.

Departures from the published code, none of which changes the model's
function class: the rotary dimensions of ``q_pe`` and ``k_pe`` rotate as
two halves, where the published code de-interleaves even and odd
dimensions first (a fixed permutation of those weights' output columns,
which are random here); norm weights are 1 + a seeded delta; the
latent's norm uses ``rms_norm_eps``.

This reference computes the expanded attention and every held expert on
every token (weighted by the router's share, zero where not chosen).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference import mm, rms, w

LEAVES = {("attn", "wq"): "attn/wq", ("attn", "wkv_a"): "attn/wkv_a",
          ("attn", "kv_norm"): "attn/kv_norm",
          ("attn", "wkv_b"): "attn/wkv_b", ("attn", "wo"): "attn/wo",
          ("ln1_scale",): "ln1", ("ln2_scale",): "ln2",
          ("mlp", "w_gate"): "mlp/w_gate", ("mlp", "w_up"): "mlp/w_up",
          ("mlp", "w_down"): "mlp/w_down",
          ("moe", "router"): "moe/router", ("moe", "w_gate"): "moe/w_gate",
          ("moe", "w_up"): "moe/w_up", ("moe", "w_down"): "moe/w_down",
          ("moe", "shared", "w_gate"): "moe/shared/w_gate",
          ("moe", "shared", "w_up"): "moe/shared/w_up",
          ("moe", "shared", "w_down"): "moe/shared/w_down"}


# settings of the family this adapter and the program do not carry
SUPPORTED = {"q_lora_rank": None, "scoring_func": "softmax",
             "topk_method": "greedy", "moe_layer_freq": 1,
             "routed_scaling_factor": 1}


def dims(cfg: Dict) -> Dict[str, int]:
    """Sizes: ``experts`` is the router's (published) width, ``held`` the
    experts this chip holds from ``first``."""
    for k, v in SUPPORTED.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"{cfg['name']}: {k}={cfg[k]!r} is not "
                             f"supported (only {v!r})")
    first, stop = cfg["held_experts"]
    if stop - first != int(cfg["n_routed_experts"]):
        raise ValueError(f"{cfg['name']}: held_experts {cfg['held_experts']} "
                         f"holds other than n_routed_experts")
    return {"d": int(cfg["hidden_size"]),
            "h": int(cfg["num_attention_heads"]),
            "rank": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "v": int(cfg["v_head_dim"]),
            "ff": int(cfg["intermediate_size"]),
            "eff": int(cfg["moe_intermediate_size"]),
            "experts": int(cfg["published"]["n_routed_experts"]),
            "held": int(cfg["n_routed_experts"]), "first": int(first),
            "top_k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg["n_shared_experts"]),
            "dense_layers": int(cfg["first_k_dense_replace"]),
            "vocab": int(cfg["vocab_size"]),
            "layers": int(cfg["num_hidden_layers"])}


def model_config(cfg: Dict):
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    YarnScaling)
    m = dims(cfg)
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], arch_type="moe", n_layers=m["layers"],
        d_model=m["d"], n_heads=m["h"], n_kv_heads=m["h"], d_ff=m["ff"],
        vocab_size=m["vocab"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        mla=MLAConfig(kv_lora_rank=m["rank"], qk_nope_head_dim=m["nope"],
                      qk_rope_head_dim=m["rope"], v_head_dim=m["v"]),
        moe=MoEConfig(num_experts=m["experts"], top_k=m["top_k"],
                      expert_d_ff=m["eff"], n_shared_experts=m["shared"],
                      norm_topk_prob=bool(cfg["norm_topk_prob"]),
                      held=(m["first"], m["held"]),
                      first_dense_layers=m["dense_layers"]),
        exit_layers=tuple(cfg["assumed"]["exit_layers"]),
        source=cfg["source"]).validate()


def tensors(cfg: Dict) -> Dict:
    """Attention and norms in every block; ``mlp/*`` in the dense first
    blocks, ``moe/*`` in the others."""
    m = dims(cfg)
    d, h = m["d"], m["h"]
    std = float(cfg["initializer_range"])
    fs = m["shared"] * m["eff"]
    return {
        "attn/wq": ((d, h * (m["nope"] + m["rope"])), std),
        "attn/wkv_a": ((d, m["rank"] + m["rope"]), std),
        "attn/kv_norm": ((m["rank"],), W.NORM_STD),
        "attn/wkv_b": ((m["rank"], h * (m["nope"] + m["v"])), std),
        "attn/wo": ((h * m["v"], d), std),
        "ln1": ((d,), W.NORM_STD), "ln2": ((d,), W.NORM_STD),
        "mlp/w_gate": ((d, m["ff"]), std), "mlp/w_up": ((d, m["ff"]), std),
        "mlp/w_down": ((m["ff"], d), std),
        "moe/router": ((d, m["experts"]), std),
        "moe/w_gate": ((m["held"], d, m["eff"]), std),
        "moe/w_up": ((m["held"], d, m["eff"]), std),
        "moe/w_down": ((m["held"], m["eff"], d), std),
        "moe/shared/w_gate": ((d, fs), std), "moe/shared/w_up": ((d, fs), std),
        "moe/shared/w_down": ((fs, d), std)}


# ---------------------------------------------------------------------------
# the reference block
# ---------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: Dict) -> np.ndarray:
    """YaRN's inverse frequencies of the rotary dimensions, as the
    published ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    rs, dim = cfg["rope_scaling"], int(cfg["qk_rope_head_dim"])
    base, factor = float(cfg["rope_theta"]), float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def softmax_scale(cfg: Dict) -> float:
    rs = cfg["rope_scaling"]
    scale = (int(cfg["qk_nope_head_dim"])
             + int(cfg["qk_rope_head_dim"])) ** -0.5
    if rs.get("mscale_all_dim"):
        m = yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
        scale *= m * m
    return scale


def _rope(x: jax.Array, cfg: Dict) -> jax.Array:
    """x (B, S, H, D) at positions 0..S-1; rotates the two halves."""
    rs = cfg["rope_scaling"]
    s, half = x.shape[1], x.shape[-1] // 2
    mag = (yarn_mscale(float(rs["factor"]), float(rs["mscale"]))
           / yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"])))
    ang = np.arange(s)[:, None] * yarn_inv_freq(cfg)[None, :]  # (S, D/2)
    cos = jnp.asarray(np.cos(ang) * mag, jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang) * mag, jnp.float32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, key, layer, x, keymask, lowp):
    """The residual after MLA, expanded."""
    m = dims(cfg)
    b, s, _ = x.shape
    h, nope, rope = m["h"], m["nope"], m["rope"]
    eps = float(cfg["rms_norm_eps"])
    hx = rms(x, w(cfg, key, "ln1", layer), eps)
    q = mm(hx, w(cfg, key, "attn/wq", layer), lowp).reshape(
        b, s, h, nope + rope)
    kv = mm(hx, w(cfg, key, "attn/wkv_a", layer), lowp)
    c = rms(kv[..., :m["rank"]], w(cfg, key, "attn/kv_norm", layer), eps)
    up = mm(c, w(cfg, key, "attn/wkv_b", layer), lowp).reshape(
        b, s, h, nope + m["v"])
    k_pe = _rope(kv[..., m["rank"]:][:, :, None, :], cfg)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg)], -1)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST)
    logits = logits * softmax_scale(cfg)
    idx = jnp.arange(s)
    causal = idx[None, :] <= idx[:, None]                      # (q, k)
    ok = causal[None] & (keymask[:, None, :] | jnp.eye(s, dtype=bool)[None])
    logits = jnp.where(ok[:, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", p, up[..., nope:],
                     precision=jax.lax.Precision.HIGHEST)
    return x + mm(att.reshape(b, s, -1), w(cfg, key, "attn/wo", layer), lowp)


def _swiglu(cfg, key, layer, x, names, lowp, e=None):
    wg, wu, wd = (w(cfg, key, n, layer) for n in names)
    if e is not None:
        wg, wu, wd = wg[e], wu[e], wd[e]
    return mm(jax.nn.silu(mm(x, wg, lowp)) * mm(x, wu, lowp), wd, lowp)


def routed_share(cfg, key, layer, h, lowp) -> jax.Array:
    """(B, S, experts): the router's weight of each expert for each token,
    zero where it is not among the top k."""
    m = dims(cfg)
    probs = jax.nn.softmax(mm(h, w(cfg, key, "moe/router", layer), lowp), -1)
    top_p, top_e = jax.lax.top_k(probs, m["top_k"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    else:
        top_p = top_p * float(cfg["routed_scaling_factor"])
    return jnp.einsum("bsk,bske->bse", top_p,
                      jax.nn.one_hot(top_e, m["experts"]))


def moe(cfg, key, layer, h, lowp) -> jax.Array:
    """The held experts' routed part plus the shared experts."""
    m = dims(cfg)
    share = routed_share(cfg, key, layer, h, lowp)
    out = _swiglu(cfg, key, layer, h, ("moe/shared/w_gate", "moe/shared/w_up",
                                       "moe/shared/w_down"), lowp)
    names = ("moe/w_gate", "moe/w_up", "moe/w_down")
    for e in range(m["held"]):
        g = m["first"] + e
        out = out + share[..., g:g + 1] * _swiglu(cfg, key, layer, h, names,
                                                  lowp, e)
    return out


@functools.partial(jax.jit, static_argnums=(0, 5))
def _dense_block(cfg, key, layer, x, keymask, lowp):
    x = attention(cfg, key, layer, x, keymask, lowp)
    h = rms(x, w(cfg, key, "ln2", layer), float(cfg["rms_norm_eps"]))
    return x + _swiglu(cfg, key, layer, h,
                       ("mlp/w_gate", "mlp/w_up", "mlp/w_down"), lowp)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _moe_block(cfg, key, layer, x, keymask, lowp):
    x = attention(cfg, key, layer, x, keymask, lowp)
    h = rms(x, w(cfg, key, "ln2", layer), float(cfg["rms_norm_eps"]))
    return x + moe(cfg, key, layer, h, lowp)


def block(cfg, key, layer, x, keymask, lowp):
    """Block ``layer`` (a Python int): the dense first blocks and the
    expert blocks are two compiled programs of a traced layer."""
    fn = _dense_block if layer < dims(cfg)["dense_layers"] else _moe_block
    return fn(cfg, key, layer, x, keymask, lowp)


# ---------------------------------------------------------------------------
# parameters, operations, bytes
# ---------------------------------------------------------------------------
def uniform(cfg: Dict) -> bool:
    return False


def attn_params(m: Dict[str, int]) -> int:
    """MLA's weights and the latent's norm."""
    d, h = m["d"], m["h"]
    return (d * h * (m["nope"] + m["rope"]) + d * (m["rank"] + m["rope"])
            + m["rank"] + m["rank"] * h * (m["nope"] + m["v"])
            + h * m["v"] * d)


def moe_params(m: Dict[str, int]) -> int:
    """The router, the held experts and the shared experts."""
    expert = 3 * m["d"] * m["eff"]
    return (m["d"] * m["experts"] + m["held"] * expert
            + m["shared"] * expert)


def _dense(m: Dict[str, int], layer: int) -> bool:
    return layer < m["dense_layers"]


def layer_params(m: Dict[str, int], layer: int) -> int:
    """Every parameter of a block, its two norms with them; a decode step
    reads them all (at these loads each held expert sees several tokens
    a step)."""
    ffn = 3 * m["d"] * m["ff"] if _dense(m, layer) else moe_params(m)
    return attn_params(m) + ffn + 2 * m["d"]


def moe_token_flops(m: Dict[str, int]) -> float:
    """One token through an expert layer: the router, the shared experts
    and, by expectation, its top k's share of held experts."""
    expert = 2.0 * 3 * m["d"] * m["eff"]
    return (2.0 * m["d"] * m["experts"] + m["shared"] * expert
            + m["top_k"] * m["held"] / m["experts"] * expert)


def mla_token_flops(m: Dict[str, int], ctx: int) -> float:
    """One decoded token's absorbed MLA over ``ctx`` keys: the query, the
    latent and its rotary key, q_nope through W_UK, scores against the
    latent and the rotary keys, the weighted latent, W_UV and ``wo``."""
    d, h = m["d"], m["h"]
    proj = (d * h * (m["nope"] + m["rope"]) + d * (m["rank"] + m["rope"])
            + h * m["nope"] * m["rank"] + h * m["rank"] * m["v"]
            + h * m["v"] * d)
    return 2.0 * proj + 2.0 * h * ctx * (2 * m["rank"] + m["rope"])


def token_flops(m: Dict[str, int], layer: int, ctx: int) -> float:
    ffn = (2.0 * 3 * m["d"] * m["ff"] if _dense(m, layer)
           else moe_token_flops(m))
    return mla_token_flops(m, ctx) + ffn


def prompt_flops(m: Dict[str, int], layer: int, prompt: int) -> float:
    """A causal prefill, expanded: projections and ``wkv_b`` per token,
    then queries of nope + rope against keys, values of ``v``."""
    d, h = m["d"], m["h"]
    proj = (d * h * (m["nope"] + m["rope"]) + d * (m["rank"] + m["rope"])
            + m["rank"] * h * (m["nope"] + m["v"]) + h * m["v"] * d)
    ffn = (2.0 * 3 * m["d"] * m["ff"] if _dense(m, layer)
           else moe_token_flops(m))
    attn = h * (m["nope"] + m["rope"] + m["v"]) * prompt * (prompt + 1)
    return (2.0 * proj + ffn) * prompt + attn


def cache_bytes(m: Dict[str, int], layer: int, itemsize: int) -> int:
    """The latent and the rotary key of one token."""
    return (m["rank"] + m["rope"]) * itemsize


# ---------------------------------------------------------------------------
# the two kernels' work, for their rooflines
# ---------------------------------------------------------------------------
def moe_layers(m: Dict[str, int], layers: Sequence[int]) -> int:
    return sum(1 for l in layers if not _dense(m, l))


def moe_work(m: Dict[str, int], rows: float, itemsize: int = 2
             ) -> Tuple[float, float]:
    """(operations, bytes) of one expert layer of a decode step over
    ``rows`` rows (a mean may be fractional): each row's token through the
    router, the shared experts and its expected held share; the router,
    the shared and the held experts read once."""
    return rows * moe_token_flops(m), float(moe_params(m) * itemsize)


def mla_decode_work(m: Dict[str, int], ctxs: Sequence[int],
                    calls: int = 1, itemsize: int = 2
                    ) -> Tuple[float, float]:
    """(operations, bytes) of one MLA layer of a decode step, on average
    over ``calls`` steps that decoded rows of contexts ``ctxs`` between
    them: the absorbed projections and scores; the attention weights once
    a step and each row's latent cache over its context."""
    flops = sum(mla_token_flops(m, c) for c in ctxs)
    return flops / calls, float(attn_params(m) * itemsize
                                + sum(ctxs) * cache_bytes(m, 0, itemsize)
                                / calls)
