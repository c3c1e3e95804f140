"""Fused exit-head confidence + int8 wire quantization — Pallas TPU kernel.

A below-θ decode row today costs TWO launches over the same (B, d) hidden
tile: the exit-head confidence pass (``kernels/exit_head``) and a separate
``kernels/quantize`` launch producing the int8 packet it uploads.  Both
read the identical hidden from HBM.  This kernel fuses them: while the
V-axis grid streams the unembedding through VMEM for the running
(max, logsumexp, argmax), the first V step quantizes the resident raw
hidden tile in place — one pass over the hidden, one launch, and the int8
wire packet (data + per-row scale) drops out alongside the exit decision.

Grid: (B/block_b, V/block_v) like ``exit_head``, whose V-tile step this
kernel shares; the quantized outputs are written once at ``vi == 0``
(their blocks only depend on the B index).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.exit_head.kernel import (exit_grid, exit_stats_finish,
                                            exit_stats_step)


def _exit_quant_kernel(h_ref, w_ref, ns_ref, conf_ref, tok_ref, lse_ref,
                       q_ref, s_ref, m_scr, l_scr, a_scr, *, eps: float,
                       block_v: int, n_v: int):
    @pl.when(pl.program_id(1) == 0)
    def _quantize():
        # quantize the resident RAW hidden (pre-norm: the wire carries the
        # activation, not the exit-head's normalized view) — same per-row
        # absmax scaling as the transport quantizer
        xf = h_ref[...].astype(jnp.float32)                # (bb, d)
        scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-12)
        s_ref[...] = scale
        q_ref[...] = jnp.clip(jnp.round(xf / scale),
                              -127, 127).astype(jnp.int8)

    exit_stats_step(h_ref, w_ref, ns_ref, m_scr, l_scr, a_scr, eps=eps,
                    block_v=block_v)
    exit_stats_finish(conf_ref, tok_ref, lse_ref, m_scr, l_scr, a_scr,
                      n_v=n_v)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_v", "eps", "interpret"))
def exit_quant_pallas(hidden: jax.Array, weight: jax.Array,
                      norm_scale: jax.Array, *, block_b: int, block_v: int,
                      eps: float = 1e-5, interpret: bool = False):
    """hidden: (B, d); weight: (V, d) ->
    (conf (B,), tok (B,), lse (B,), q int8 (B, d), scale fp32 (B, 1)).
    ``block_b``/``block_v`` must divide B/V (``ops.exit_quant`` picks
    them)."""
    b, d = hidden.shape
    v = weight.shape[0]
    g = exit_grid(b, d, v, block_b, block_v)
    g["out_specs"] += [pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
                       pl.BlockSpec((block_b, 1), lambda i, j: (i, 0))]
    g["out_shape"] += [jax.ShapeDtypeStruct((b, d), jnp.int8),
                       jax.ShapeDtypeStruct((b, 1), jnp.float32)]
    kernel = functools.partial(_exit_quant_kernel, eps=eps, block_v=block_v,
                               n_v=g["grid"][1])
    conf, tok, lse, q, s = pl.pallas_call(
        kernel, interpret=interpret, **g,
    )(hidden, weight, norm_scale.reshape(1, d))
    return conf[:, 0], tok[:, 0], lse[:, 0], q, s
