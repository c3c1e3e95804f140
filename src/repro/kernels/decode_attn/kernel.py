"""GQA flash-decode attention — Pallas TPU kernels (ring + paged).

One new query token attends over a long KV cache: the cloud tier's
per-token hot loop.  KV is streamed HBM->VMEM one (block_s, KV*d) tile per
grid step — every KV head of a span of positions at once, so the block's
last two dims are (rows, full lane width) and satisfy the TPU's (8, 128)
tiling rule at any head count.  Online-softmax statistics live in VMEM
scratch; the (H, d) output tile is written once at the last S tile.

Grid: (B, S/block_s) — S minormost (sequential), so scratch carries
(acc, m, l) across KV tiles.  Inside a step a static loop walks the KV
heads: the G = H/KV query heads of one group ride together through the
MXU, (G, d) @ (d, block_s), against the head's lane-aligned d-wide slice
of the tile.

K/V arrive as (…, S, KV, d) caches and are viewed as (…, S, KV*d) — a
free row-major reshape — so the served (P, page_size, KV, d) pool layout
is kept as is.  The current position is a scalar-prefetch operand (SMEM).

  * ``decode_attn_pallas`` — dense (possibly ring-buffered) (B, S) cache;
    the S tile index maps straight into the row's cache.
  * ``decode_attn_paged_pallas`` — block-paged cache (P, page_size): the
    per-row block table rides in as a second scalar-prefetch operand so
    the k/v/pos BlockSpec index maps can look up, per (row, logical-page)
    grid point, WHICH physical page to DMA — the vLLM PagedAttention
    trick, no gather materialization.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attend_tile(q_ref, k_ref, v_ref, ks_ref, vs_ref, valid, acc_scr, m_scr,
                 l_scr, *, kvh: int, g: int, d: int, scale: float):
    """Online-softmax update of every query head against one KV tile.

    k_ref/v_ref blocks are (1, bs, KV*d); ks_ref/vs_ref (1, bs, KV) int8
    dequantization scales or None; ``valid`` (1, bs) bool."""
    for h in range(kvh):
        rows = pl.ds(h * g, g)
        cols = pl.ds(h * d, d)
        q = q_ref[0, rows, :].astype(jnp.float32)          # (G, d)
        k = k_ref[0, :, cols].astype(jnp.float32)          # (bs, d)
        v = v_ref[0, :, cols].astype(jnp.float32)
        if ks_ref is not None:
            # int8 pages: dequantize in-VMEM with the per-row absmax
            # scales that rode in next to the page DMA
            k = k * ks_ref[0, :, pl.ds(h, 1)]              # (bs, 1) bcast
            v = v * vs_ref[0, :, pl.ds(h, 1)]
        logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        logits = jnp.where(valid, logits * scale, NEG_INF)  # (G, bs)
        m_old = m_scr[rows, :]                             # (G, 1)
        m_new = jnp.maximum(m_old, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_old - m_new)
        acc_scr[rows, :] = (acc_scr[rows, :] * corr
                            + jax.lax.dot_general(
                                p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
        l_scr[rows, :] = l_scr[rows, :] * corr + jnp.sum(p, axis=-1,
                                                         keepdims=True)
        m_scr[rows, :] = m_new


def _init_scratch(acc_scr, m_scr, l_scr):
    acc_scr[...] = jnp.zeros_like(acc_scr)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)


def _write_out(o_ref, acc_scr, l_scr):
    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _scratch(h: int, d: int):
    return [pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32)]


def _decode_attn_kernel(cur_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                        acc_scr, m_scr, l_scr, *, n_s: int, window: int,
                        kvh: int, g: int, d: int, scale: float):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        _init_scratch(acc_scr, m_scr, l_scr)

    cur = cur_ref[0]
    pos = pos_ref[0]                               # (1, bs)
    valid = (pos >= 0) & (pos <= cur)
    if window:
        valid &= (cur - pos) < window
    _attend_tile(q_ref, k_ref, v_ref, None, None, valid, acc_scr, m_scr,
                 l_scr, kvh=kvh, g=g, d=d, scale=scale)

    @pl.when(si == n_s - 1)
    def _finish():
        _write_out(o_ref, acc_scr, l_scr)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "window", "interpret"))
def decode_attn_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                       pos_ids: jax.Array, cur_pos: jax.Array, *,
                       block_s: int, window: int = 0,
                       interpret: bool = False) -> jax.Array:
    """q: (B,H,d); k/v: (B,S,KV,d); pos_ids: (B,S); cur_pos: () int32.
    ``block_s`` must divide S (``ops.flash_decode`` picks it)."""
    b, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    assert s % block_s == 0, (s, block_s)
    n_s = s // block_s
    cur = jnp.reshape(jnp.asarray(cur_pos, jnp.int32), (1,))

    kernel = functools.partial(_decode_attn_kernel, n_s=n_s, window=window,
                               kvh=kvh, g=h // kvh, d=d,
                               scale=1.0 / math.sqrt(d))
    kv_spec = pl.BlockSpec((1, block_s, kvh * d),
                           lambda bi, si, cur_ref: (bi, si, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_s),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, si, cur_ref: (bi, 0, 0)),
            kv_spec, kv_spec,
            pl.BlockSpec((1, 1, block_s),
                         lambda bi, si, cur_ref: (bi, 0, si)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bi, si, cur_ref: (bi, 0, 0)),
        scratch_shapes=_scratch(h, d),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(cur, q, k.reshape(b, s, kvh * d), v.reshape(b, s, kvh * d),
      pos_ids.reshape(b, 1, s))


def _decode_attn_paged_kernel(tbl_ref, cur_ref, q_ref, k_ref, v_ref, *rest,
                              n_lp: int, window: int, kvh: int, g: int,
                              d: int, scale: float, quantized: bool):
    if quantized:
        ks_ref, vs_ref, pos_ref, o_ref, acc_scr, m_scr, l_scr = rest
    else:
        pos_ref, o_ref, acc_scr, m_scr, l_scr = rest
        ks_ref = vs_ref = None
    bi = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        _init_scratch(acc_scr, m_scr, l_scr)

    cur = cur_ref[bi]
    pos = pos_ref[0]                               # (1, ps)
    mapped = tbl_ref[bi, pi] >= 0                  # unallocated -> all invalid
    valid = (pos >= 0) & (pos <= cur) & mapped
    if window:
        valid &= (cur - pos) < window
    _attend_tile(q_ref, k_ref, v_ref, ks_ref, vs_ref, valid, acc_scr, m_scr,
                 l_scr, kvh=kvh, g=g, d=d, scale=scale)

    @pl.when(pi == n_lp - 1)
    def _finish():
        _write_out(o_ref, acc_scr, l_scr)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def decode_attn_paged_pallas(q: jax.Array, kp: jax.Array, vp: jax.Array,
                             pos_pages: jax.Array, block_tbl: jax.Array,
                             cur_pos: jax.Array, *, k_scale=None,
                             v_scale=None, window: int = 0,
                             interpret: bool = False) -> jax.Array:
    """q: (B,H,d); kp/vp: (P,page_size,KV,d); pos_pages: (P,page_size);
    block_tbl: (B,n_lp) int32 (-1 = unallocated); cur_pos: scalar or (B,).

    The KV tile of grid point (b, pi) — every KV head of one page — is
    DMA'd from physical page ``block_tbl[b, pi]`` via scalar-prefetch index
    maps; unmapped pages read page 0 and are masked out.

    With int8 pages, pass ``k_scale``/``v_scale`` (P,page_size,KV) fp32:
    the per-row absmax scales ride through the SAME block-table index maps
    as the pages and dequantization happens in-kernel, after the DMA — the
    HBM read per token shrinks ~4x instead of being re-expanded in XLA."""
    b, h, d = q.shape
    n_pages, ps, kvh = kp.shape[0], kp.shape[1], kp.shape[2]
    n_lp = block_tbl.shape[1]
    cur = jnp.broadcast_to(jnp.asarray(cur_pos, jnp.int32), (b,))
    tbl = block_tbl.astype(jnp.int32)
    quantized = k_scale is not None

    def page(bi, pi, tbl_ref, cur_ref):
        return (jnp.maximum(tbl_ref[bi, pi], 0), 0, 0)

    kernel = functools.partial(_decode_attn_paged_kernel, n_lp=n_lp,
                               window=window, kvh=kvh, g=h // kvh, d=d,
                               scale=1.0 / math.sqrt(d), quantized=quantized)
    kv_spec = pl.BlockSpec((1, ps, kvh * d), page)
    in_specs = [
        pl.BlockSpec((1, h, d), lambda bi, pi, tbl_ref, cur_ref: (bi, 0, 0)),
        kv_spec, kv_spec,
    ]
    operands = [q, kp.reshape(n_pages, ps, kvh * d),
                vp.reshape(n_pages, ps, kvh * d)]
    if quantized:
        in_specs += [pl.BlockSpec((1, ps, kvh), page)] * 2
        operands += [k_scale, v_scale]
    in_specs.append(pl.BlockSpec((1, 1, ps), page))
    operands.append(pos_pages.reshape(n_pages, 1, ps))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_lp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d),
                               lambda bi, pi, tbl_ref, cur_ref: (bi, 0, 0)),
        scratch_shapes=_scratch(h, d),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(tbl, cur, *operands)
