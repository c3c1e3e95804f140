"""jit'd public wrapper for the fused exit-head confidence kernel."""
from __future__ import annotations

import jax

from repro.kernels.exit_head.kernel import exit_head_pallas
from repro.kernels.exit_head.ref import exit_head_ref
from repro.kernels.tiling import VMEM_TILE_BUDGET, pick_block


def exit_blocks(b: int, d: int, v: int, block_b: int, block_v: int):
    """(block_b, block_v) for the exit kernels: B rows in multiples of 8,
    V in multiples of 128 small enough that the double-buffered weight
    tile plus its float32 copy (8*d bytes a row) fit the VMEM budget —
    V=32000 at d=4096 gets 256."""
    bb = pick_block(b, max(block_b, 8), 8, what="B")
    cap_v = min(block_v, VMEM_TILE_BUDGET // (8 * d))
    bv = pick_block(v, max(cap_v, 128), 128, what="V")
    return bb, bv


def exit_confidence(hidden: jax.Array, weight: jax.Array,
                    norm_scale: jax.Array, *, block_b: int = 8,
                    block_v: int = 512, interpret: bool = False,
                    use_kernel: bool = True):
    """(B,d) hidden + (V,d) unembedding -> (confidence, token, logsumexp).
    ``use_kernel=False`` runs the jnp oracle (the reference semantics)."""
    if not use_kernel:
        return exit_head_ref(hidden, weight, norm_scale)
    bb, bv = exit_blocks(*hidden.shape, weight.shape[0], block_b, block_v)
    return exit_head_pallas(hidden, weight, norm_scale, block_b=bb,
                            block_v=bv, interpret=interpret)
