"""jit'd public wrappers for GQA flash-decode attention (ring + paged)."""
from __future__ import annotations

import jax

from repro.kernels.decode_attn.kernel import (decode_attn_paged_pallas,
                                              decode_attn_pallas)
from repro.kernels.decode_attn.ref import (decode_attn_paged_ref,
                                           decode_attn_ref)
from repro.kernels.tiling import VMEM_TILE_BUDGET, pick_block


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 pos_ids: jax.Array, cur_pos, *, window: int = 0,
                 block_s: int = 512, interpret: bool = False,
                 use_kernel: bool = True) -> jax.Array:
    """q: (B,H,d) one new token; k/v: (B,S,KV,d) ring cache -> (B,H,d).

    The S tile is the largest multiple of 128 that divides S, is at most
    ``block_s``, and keeps the double-buffered K and V tiles inside the
    VMEM budget (S itself when S fits whole)."""
    if not use_kernel:
        return decode_attn_ref(q, k, v, pos_ids, cur_pos, window=window)
    s, kvh, d = k.shape[1:]
    row_bytes = kvh * d * k.dtype.itemsize
    cap = min(block_s, VMEM_TILE_BUDGET // (4 * row_bytes))
    bs = pick_block(s, max(cap, 128), 128, what="S")
    return decode_attn_pallas(q, k, v, pos_ids, cur_pos, block_s=bs,
                              window=window, interpret=interpret)


def flash_decode_paged(q: jax.Array, kp: jax.Array, vp: jax.Array,
                       pos_pages: jax.Array, block_tbl: jax.Array, cur_pos,
                       *, k_scale=None, v_scale=None, window: int = 0,
                       interpret: bool = False,
                       use_kernel: bool = True) -> jax.Array:
    """q: (B,H,d) one new token; kp/vp: (P,page_size,KV,d) page pool;
    block_tbl: (B,n_lp) per-row physical page ids -> (B,H,d).  The Pallas
    path DMAs one physical page (all KV heads) per grid step through a
    scalar-prefetched block table.

    int8 pools pass ``k_scale``/``v_scale`` (P,page_size,KV) fp32 per-row
    scales; dequantization then happens inside the kernel, after the page
    DMA, so the HBM read stays int8-sized."""
    if not use_kernel:
        return decode_attn_paged_ref(q, kp, vp, pos_pages, block_tbl,
                                     cur_pos, window=window,
                                     k_scale=k_scale, v_scale=v_scale)
    return decode_attn_paged_pallas(q, kp, vp, pos_pages, block_tbl, cur_pos,
                                    k_scale=k_scale, v_scale=v_scale,
                                    window=window, interpret=interpret)
