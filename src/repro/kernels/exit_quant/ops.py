"""jit'd public wrapper for the fused exit-head + quantize kernel."""
from __future__ import annotations

import jax

from repro.kernels.exit_head.ops import exit_blocks
from repro.kernels.exit_quant.kernel import exit_quant_pallas
from repro.kernels.exit_quant.ref import exit_quant_ref


def exit_quant(hidden: jax.Array, weight: jax.Array, norm_scale: jax.Array,
               *, block_b: int = 8, block_v: int = 512, eps: float = 1e-5,
               interpret: bool = False, use_kernel: bool = True):
    """(B,d) hidden + (V,d) unembedding ->
    (confidence, token, logsumexp, q int8 (B,d), scale fp32 (B,1)).

    One launch for the below-θ hot path: the exit decision AND the int8
    wire packet of the same hidden tile.  ``use_kernel=False`` runs the
    jnp oracle (the reference semantics)."""
    if not use_kernel:
        return exit_quant_ref(hidden, weight, norm_scale, eps)
    bb, bv = exit_blocks(*hidden.shape, weight.shape[0], block_b, block_v)
    return exit_quant_pallas(hidden, weight, norm_scale, block_b=bb,
                             block_v=bv, eps=eps, interpret=interpret)
