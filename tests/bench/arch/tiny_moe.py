"""A sparse-expert block that lives only with the tests: the program's
``moe`` block (``models/moe.py``) behind llama's attention.  Its router
takes a softmax over the experts, keeps the ``num_experts_per_tok``
largest and renormalises them; each expert is a SwiGLU MLP.  The
program's prefill drops tokens over an expert's capacity, so the
configuration sets the capacity factor to experts / top-k, at which none
is dropped, and the reference is dropless.

Found by the registry once the tests put this directory on
``bench.arch.SEARCH``; nothing under ``bench/`` names it.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench import arch
from bench.reference import mm, rms, w

llama = arch.load("llama")

LEAVES = {**llama.ATTN_LEAVES,
          ("moe", "router"): "moe/router", ("moe", "w_gate"): "moe/w_gate",
          ("moe", "w_up"): "moe/w_up", ("moe", "w_down"): "moe/w_down"}


def dims(cfg: Dict) -> Dict[str, int]:
    """llama's sizes (``ff`` is an expert's width) and the experts."""
    return {**llama.dims(cfg), "experts": int(cfg["num_local_experts"]),
            "top_k": int(cfg["num_experts_per_tok"])}


def model_config(cfg: Dict):
    from repro.configs.base import MoEConfig
    m = dims(cfg)
    return llama.model_config(
        cfg, arch_type="moe",
        moe=MoEConfig(num_experts=m["experts"], top_k=m["top_k"],
                      expert_d_ff=m["ff"],
                      capacity_factor=m["experts"] / m["top_k"]))


def tensors(cfg: Dict) -> Dict:
    m = dims(cfg)
    d, e, ff = m["d"], m["experts"], m["ff"]
    std = float(cfg["initializer_range"])
    return {**llama.attn_tensors(cfg), "moe/router": ((d, e), std),
            "moe/w_gate": ((e, d, ff), std), "moe/w_up": ((e, d, ff), std),
            "moe/w_down": ((e, ff, d), std)}


@functools.partial(jax.jit, static_argnums=(0, 5))
def block(cfg, key, layer, x, keymask, lowp):
    m = dims(cfg)
    x = llama.attention(cfg, key, layer, x, keymask, lowp)
    h = rms(x, w(cfg, key, "ln2", layer), float(cfg["rms_norm_eps"]))
    probs = jax.nn.softmax(mm(h, w(cfg, key, "moe/router", layer), lowp), -1)
    top_p, top_e = jax.lax.top_k(probs, m["top_k"])
    top_p = top_p / top_p.sum(-1, keepdims=True)
    share = jnp.einsum("bsk,bske->bse", top_p,
                       jax.nn.one_hot(top_e, m["experts"]))
    wg, wu, wd = (w(cfg, key, f"moe/{n}", layer)
                  for n in ("w_gate", "w_up", "w_down"))
    for e in range(m["experts"]):
        y = mm(jax.nn.silu(mm(h, wg[e], lowp)) * mm(h, wu[e], lowp), wd[e],
               lowp)
        x = x + share[..., e:e + 1] * y
    return x


def _routed(m: Dict[str, int]) -> int:
    """Weights a token multiplies outside attention: the router and its
    top-k experts."""
    return m["d"] * m["experts"] + m["top_k"] * 3 * m["d"] * m["ff"]


def uniform(cfg: Dict) -> bool:
    return True


def layer_params(m: Dict[str, int], layer: int) -> int:
    """Every parameter of a block: a decode step of several rows is
    counted as reading every expert."""
    return (llama.attn_params(m) + m["d"] * m["experts"]
            + m["experts"] * 3 * m["d"] * m["ff"] + 2 * m["d"])


def token_flops(m: Dict[str, int], layer: int, ctx: int) -> float:
    return 2.0 * (llama.attn_params(m) + _routed(m)) + llama.attn_flops(
        m, ctx)


def prompt_flops(m: Dict[str, int], layer: int, prompt: int) -> float:
    return (2.0 * (llama.attn_params(m) + _routed(m)) * prompt
            + llama.causal_attn_flops(m, prompt))


cache_bytes = llama.cache_bytes
