"""The llama family's block: a dense pre-RMSNorm decoder layer.

Rotary attention with grouped KV heads and no bias, then a SwiGLU MLP,
each behind an RMSNorm of its own, as ``models/blocks.py`` computes it
(arch_type "dense").  The configuration's keys are Hugging Face's
``LlamaConfig`` names.  Parameter arithmetic follows the program's
``roofline/analyze.count_params``: a block is attention plus a gated MLP,
plus its two norms.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference import mm, rms, w

# program parameter leaves under a segment's layer stack -> tensor names
ATTN_LEAVES = {("attn", "wq"): "attn/wq", ("attn", "wk"): "attn/wk",
               ("attn", "wv"): "attn/wv", ("attn", "wo"): "attn/wo",
               ("ln1_scale",): "ln1", ("ln2_scale",): "ln2"}
LEAVES = {**ATTN_LEAVES,
          ("mlp", "w_gate"): "mlp/w_gate", ("mlp", "w_up"): "mlp/w_up",
          ("mlp", "w_down"): "mlp/w_down"}


def dims(cfg: Dict) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"d": d, "h": h, "kv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "ff": int(cfg["intermediate_size"]),
            "vocab": int(cfg["vocab_size"]),
            "layers": int(cfg["num_hidden_layers"])}


def model_config(cfg: Dict, **kw):
    """The program's ``ModelConfig``; ``kw`` overrides fields (an adapter
    that shares this attention sets its own block)."""
    from repro.configs.base import ModelConfig
    m = dims(cfg)
    fields = dict(
        name=cfg["name"], arch_type="dense", n_layers=m["layers"],
        d_model=m["d"], n_heads=m["h"], n_kv_heads=m["kv"],
        head_dim=m["hd"], d_ff=m["ff"], vocab_size=m["vocab"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        exit_layers=tuple(cfg["assumed"]["exit_layers"]),
        source=cfg["source"])
    fields.update(kw)
    return ModelConfig(**fields).validate()


def attn_tensors(cfg: Dict) -> Dict:
    """The attention's tensors and the block's two norms."""
    m = dims(cfg)
    d, hq, hk = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"]
    std = float(cfg["initializer_range"])
    return {"attn/wq": ((d, hq), std), "attn/wk": ((d, hk), std),
            "attn/wv": ((d, hk), std), "attn/wo": ((hq, d), std),
            "ln1": ((d,), W.NORM_STD), "ln2": ((d,), W.NORM_STD)}


def tensors(cfg: Dict) -> Dict:
    m = dims(cfg)
    d, ff = m["d"], m["ff"]
    std = float(cfg["initializer_range"])
    return {**attn_tensors(cfg), "mlp/w_gate": ((d, ff), std),
            "mlp/w_up": ((d, ff), std), "mlp/w_down": ((ff, d), std)}


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x (B, S, H, D) at positions 0..S-1; rotates the two halves."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs     # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, key, layer, x, keymask, lowp):
    """The residual after the block's attention sub-layer."""
    m = dims(cfg)
    b, s, _ = x.shape
    g = m["h"] // m["kv"]
    eps = float(cfg["rms_norm_eps"])
    h = rms(x, w(cfg, key, "ln1", layer), eps)
    q = mm(h, w(cfg, key, "attn/wq", layer), lowp).reshape(
        b, s, m["h"], m["hd"])
    k = mm(h, w(cfg, key, "attn/wk", layer), lowp).reshape(
        b, s, m["kv"], m["hd"])
    v = mm(h, w(cfg, key, "attn/wv", layer), lowp).reshape(
        b, s, m["kv"], m["hd"])
    theta = float(cfg["rope_theta"])
    q, k = _rope(q, theta), _rope(k, theta)
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST)
    logits = logits / np.sqrt(m["hd"])
    idx = jnp.arange(s)
    causal = idx[None, :] <= idx[:, None]                      # (q, k)
    ok = causal[None] & (keymask[:, None, :] | jnp.eye(s, dtype=bool)[None])
    logits = jnp.where(ok[:, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     precision=jax.lax.Precision.HIGHEST)
    return x + mm(att.reshape(b, s, -1), w(cfg, key, "attn/wo", layer), lowp)


@functools.partial(jax.jit, static_argnums=(0, 5))
def block(cfg, key, layer, x, keymask, lowp):
    x = attention(cfg, key, layer, x, keymask, lowp)
    h2 = rms(x, w(cfg, key, "ln2", layer), float(cfg["rms_norm_eps"]))
    gate = jax.nn.silu(mm(h2, w(cfg, key, "mlp/w_gate", layer), lowp))
    up = mm(h2, w(cfg, key, "mlp/w_up", layer), lowp)
    return x + mm(gate * up, w(cfg, key, "mlp/w_down", layer), lowp)


def attn_params(m: Dict[str, int]) -> int:
    """Weights of the attention that multiply an activation."""
    d, hd = m["d"], m["hd"]
    return d * hd * (m["h"] + 2 * m["kv"]) + m["h"] * hd * d


def layer_matmul_params(m: Dict[str, int]) -> int:
    """Weights of one block that multiply an activation."""
    return attn_params(m) + 3 * m["d"] * m["ff"]


def uniform(cfg: Dict) -> bool:
    return True


def layer_params(m: Dict[str, int], layer: int) -> int:
    """Every parameter of a block, its two norms with them; a decode step
    reads them all."""
    return layer_matmul_params(m) + 2 * m["d"]


def attn_flops(m: Dict[str, int], ctx: int) -> float:
    """QK^T and PV of one query over ``ctx`` keys."""
    return 4.0 * m["h"] * m["hd"] * ctx


def token_flops(m: Dict[str, int], layer: int, ctx: int) -> float:
    return 2.0 * layer_matmul_params(m) + attn_flops(m, ctx)


def causal_attn_flops(m: Dict[str, int], prompt: int) -> float:
    """QK^T and PV of a causal prefill: query i attends over i + 1 keys."""
    return 2.0 * m["h"] * m["hd"] * prompt * (prompt + 1)


def prompt_flops(m: Dict[str, int], layer: int, prompt: int) -> float:
    return 2.0 * layer_matmul_params(m) * prompt + causal_attn_flops(
        m, prompt)


def cache_bytes(m: Dict[str, int], layer: int, itemsize: int) -> int:
    """Keys and values of one token."""
    return 2 * m["kv"] * m["hd"] * itemsize
