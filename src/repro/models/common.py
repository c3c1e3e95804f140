"""Shared low-level layers: norms, rotary embeddings, initializers."""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(head_dim: int, theta: float, scaling) -> jax.Array:
    """YaRN inverse frequencies (DeepSeek-V2's ``YarnRotaryEmbedding``):
    each pair keeps its base frequency above ``beta_fast`` rotations over
    the original context, takes it divided by ``factor`` below
    ``beta_slow``, and blends the two linearly in between."""
    half = head_dim // 2
    base = theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    extra, inter = 1.0 / base, 1.0 / (scaling.factor * base)

    def dim_of(rotations: float) -> float:
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(scaling.beta_fast)), 0)
    high = min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                      # 1 where the base frequency stays
    return inter * (1.0 - keep) + extra * keep


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S).  With a
    ``YarnScaling`` the frequencies are YaRN's and cos/sin carry its
    ``mscale / mscale_all_dim`` ratio."""
    head_dim = x.shape[-1]
    if scaling is None:
        freqs, mag = rope_freqs(head_dim, theta), 1.0        # (D/2,)
    else:
        freqs = yarn_freqs(head_dim, theta, scaling)
        mag = (yarn_mscale(scaling.factor, scaling.mscale)
               / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                             # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if mag != 1.0:
        cos, sin = cos * mag, sin * mag
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq, d_model: int) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings.

    ``seq`` may be an int (returns (seq, d)) or a positions array
    (returns (*seq.shape, d)) — the latter avoids materializing huge tables
    for long decode positions."""
    half = d_model // 2
    log_timescale = jnp.log(10000.0) / max(half - 1, 1)
    inv = jnp.exp(-log_timescale * jnp.arange(half, dtype=jnp.float32))
    pos = (jnp.arange(seq, dtype=jnp.float32) if isinstance(seq, int)
           else jnp.asarray(seq, jnp.float32))
    scaled = pos[..., None] * inv
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=-1)


# ---------------------------------------------------------------------------
# Initializers (explicit rng threading; shapes only when used via eval_shape)
# ---------------------------------------------------------------------------
def dense_init(rng: jax.Array, d_in: int, d_out: int,
               dtype=jnp.float32) -> jax.Array:
    scale = 1.0 / jnp.sqrt(jnp.asarray(d_in, jnp.float32))
    return (jax.random.normal(rng, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(rng: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(rng, (vocab, d), jnp.float32) * 0.02).astype(dtype)


@dataclasses.dataclass(frozen=True)
class RunDtypes:
    param: Any = jnp.float32
    compute: Any = jnp.float32

    @staticmethod
    def bf16() -> "RunDtypes":
        return RunDtypes(param=jnp.bfloat16, compute=jnp.bfloat16)


def split_rngs(rng: jax.Array, n: int):
    return list(jax.random.split(rng, n))
