"""jit'd public wrapper for the int8 transport quantizer."""
from __future__ import annotations

import jax

from repro.kernels.quantize.kernel import quantize_int8_pallas
from repro.kernels.quantize.ref import dequantize_int8_ref, quantize_int8_ref
from repro.kernels.tiling import pick_block


def quantize_int8(x: jax.Array, *, block_n: int = 256,
                  interpret: bool = False, use_kernel: bool = True):
    """(N,d) -> (int8 payload, fp32 per-row scale).  Row tiles are
    multiples of 8 that divide N (N itself when N <= ``block_n``)."""
    if not use_kernel:
        return quantize_int8_ref(x)
    bn = pick_block(x.shape[0], max(block_n, 8), 8, what="N")
    return quantize_int8_pallas(x, block_n=bn, interpret=interpret)


dequantize_int8 = dequantize_int8_ref
