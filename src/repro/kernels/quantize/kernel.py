"""Per-row int8 wire-format quantizer — Pallas TPU kernel.

Fuses absmax-reduce + scale + round + clip in one VMEM pass over (block_n, d)
tiles of the hidden-state upload buffer, producing the int8 payload and the
fp32 per-row scales that cross the pod boundary (beyond-paper transport
format; paper uses fp16)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0,
                        1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def quantize_int8_pallas(x: jax.Array, *, block_n: int,
                         interpret: bool = False):
    """x: (N, d) -> (q int8 (N, d), scale fp32 (N, 1)); ``block_n`` must
    divide N (``ops.quantize_int8`` picks it)."""
    n, d = x.shape
    assert n % block_n == 0, (n, block_n)
    q, s = pl.pallas_call(
        _quantize_kernel,
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((block_n, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_n, d), lambda i: (i, 0)),
                   pl.BlockSpec((block_n, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, d), jnp.int8),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        interpret=interpret,
    )(x)
    return q, s
