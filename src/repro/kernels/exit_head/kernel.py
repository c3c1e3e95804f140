"""Fused early-exit confidence head — Pallas TPU kernel.

The paper evaluates an exit head for EVERY token at EVERY exit on the
resource-poor edge tier; for the assigned archs the unembedding reaches
V=262k, so a naive ``softmax(h @ W^T)`` materializes a (B, 262k) logit row
in HBM *twice* (logits + softmax).  This kernel streams W through VMEM in
(block_v, d) tiles and keeps only running (max, logsumexp, argmax) —
O(B) output, never materializing logits.

Grid: (B/block_b, V/block_v); the V axis is the minormost (sequential on
TPU), so VMEM scratch carries the running statistics across V tiles.
MXU work per step: (block_b, d) @ (d, block_v).  Per-row outputs and
statistics are (block_b, 1) columns, so every block is (8, 128)-legal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def exit_stats_step(h_ref, w_ref, ns_ref, m_scr, l_scr, a_scr, *, eps: float,
                    block_v: int):
    """One V tile of the running (max, sum-exp, argmax) over the rms-normed
    hidden block; shared with ``kernels/exit_quant``."""
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        a_scr[...] = jnp.zeros_like(a_scr)

    # rms-norm the hidden block (full d is resident)
    h = h_ref[...].astype(jnp.float32)                     # (bb, d)
    var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
    ns = ns_ref[...].astype(jnp.float32)                   # (1, d)
    hn = h * jax.lax.rsqrt(var + eps) * (1.0 + ns)

    w = w_ref[...].astype(jnp.float32)                     # (bv, d)
    logits = jax.lax.dot_general(hn, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    tile_max = jnp.max(logits, axis=-1, keepdims=True)     # (bb, 1)
    tile_arg = (jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
                + vi * block_v)

    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, tile_max)
    corr = jnp.exp(m_old - m_new)
    l_scr[...] = (l_scr[...] * corr
                  + jnp.sum(jnp.exp(logits - m_new), axis=-1, keepdims=True))
    a_scr[...] = jnp.where(tile_max > m_old, tile_arg, a_scr[...])
    m_scr[...] = m_new


def exit_stats_finish(conf_ref, tok_ref, lse_ref, m_scr, l_scr, a_scr, *,
                      n_v: int):
    @pl.when(pl.program_id(1) == n_v - 1)
    def _finish():
        m = m_scr[...]
        lse = m + jnp.log(l_scr[...])
        conf_ref[...] = jnp.exp(m - lse)
        tok_ref[...] = a_scr[...]
        lse_ref[...] = lse


def exit_grid(b: int, d: int, v: int, block_b: int, block_v: int):
    """Grid, hidden/weight/norm in_specs, per-row out_specs/shapes and
    scratch shared by the exit kernels."""
    assert b % block_b == 0 and v % block_v == 0, (b, v, block_b, block_v)
    row = pl.BlockSpec((block_b, 1), lambda i, j: (i, 0))
    return dict(
        grid=(b // block_b, v // block_v),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
        ],
        out_specs=[row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, 1), jnp.float32),
            pltpu.VMEM((block_b, 1), jnp.float32),
            pltpu.VMEM((block_b, 1), jnp.int32),
        ],
    )


def _exit_head_kernel(h_ref, w_ref, ns_ref, conf_ref, tok_ref, lse_ref,
                      m_scr, l_scr, a_scr, *, eps: float, block_v: int,
                      n_v: int):
    exit_stats_step(h_ref, w_ref, ns_ref, m_scr, l_scr, a_scr, eps=eps,
                    block_v=block_v)
    exit_stats_finish(conf_ref, tok_ref, lse_ref, m_scr, l_scr, a_scr,
                      n_v=n_v)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_v", "eps", "interpret"))
def exit_head_pallas(hidden: jax.Array, weight: jax.Array,
                     norm_scale: jax.Array, *, block_b: int, block_v: int,
                     eps: float = 1e-5, interpret: bool = False):
    """hidden: (B, d); weight: (V, d) -> (conf (B,), tok (B,), lse (B,)).
    ``block_b``/``block_v`` must divide B/V (``ops.exit_confidence``
    picks them)."""
    b, d = hidden.shape
    v = weight.shape[0]
    g = exit_grid(b, d, v, block_b, block_v)
    kernel = functools.partial(_exit_head_kernel, eps=eps, block_v=block_v,
                               n_v=g["grid"][1])
    conf, tok, lse = pl.pallas_call(
        kernel, interpret=interpret, **g,
    )(hidden, weight, norm_scale.reshape(1, d))
    return conf[:, 0], tok[:, 0], lse[:, 0]
