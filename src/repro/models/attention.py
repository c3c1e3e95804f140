"""Multi-head attention: GQA, optional bias, RoPE, sliding window,
prefix-LM masks, cross-attention, chunked (flash-style) long-sequence path,
banded path for sliding windows, and single-token decode over two KV cache
layouts:

  * **dense rings** (``init_attn_cache`` / ``decode_attention``) — one
    ``(B, S)`` ring per layer, writes at ``pos % S``; memory is
    ``B x max_seq`` whatever the streams actually use;
  * **block pages** (``init_paged_attn_cache`` / ``decode_attention_paged``)
    — K/V live in ``(num_pages, page_size)`` pages shared by all rows and
    are addressed through a per-row block table (see
    ``repro.core.paging``); logical slot ``s`` always holds position ``s``
    (no wrap), unmapped rows write to the trash page.

Multi-head latent attention (DeepSeek-V2: ``init_mla`` / ``mla_forward`` /
``mla_decode``) caches one latent per position in a dense ring
(``init_mla_cache``): prefill expands each head's keys and values from it,
decode absorbs the expansion into the query and the output.

Pure functions over explicit parameter pytrees.  The Pallas flash-decode
kernels in ``repro.kernels.decode_attn`` mirror ``decode_attention`` (ring)
and the paged gather (block table) and are validated against them.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (apply_rope, dense_init, rms_norm, split_rngs,
                                 yarn_mscale)

Params = Dict[str, Any]

_DIRECT_LIMIT = 1 << 22   # Sq*Sk above this -> chunked path
_Q_CHUNK = 512
_KV_CHUNK = 1024


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_attention(rng: jax.Array, cfg: ModelConfig, *, cross: bool = False,
                   dtype=jnp.float32) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    rngs = split_rngs(rng, 4)
    p: Params = {
        "wq": dense_init(rngs[0], d, h * hd, dtype),
        "wk": dense_init(rngs[1], d, kv * hd, dtype),
        "wv": dense_init(rngs[2], d, kv * hd, dtype),
        "wo": dense_init(rngs[3], h * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    del cross  # same parameter structure; kv source differs at call time
    return p


def _project_qkv(params: Params, cfg: ModelConfig, x: jax.Array,
                 kv_src: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    hd, h, kv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = jnp.einsum("bsd,de->bse", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,de->bse", kv_src, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,de->bse", kv_src, params["wv"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(*q.shape[:2], h, hd)
    k = k.reshape(*k.shape[:2], kv, hd)
    v = v.reshape(*v.shape[:2], kv, hd)
    return q, k, v


# ---------------------------------------------------------------------------
# Masked softmax attention cores
# ---------------------------------------------------------------------------
def _mask_logits(logits: jax.Array, qpos: jax.Array, kpos: jax.Array,
                 causal: bool, window: int, prefix_len: int) -> jax.Array:
    """logits: (..., Sq, Sk); qpos: (Sq,), kpos: (Sk,)."""
    ok = jnp.ones(logits.shape[-2:], bool)
    if causal:
        allowed = kpos[None, :] <= qpos[:, None]
        if prefix_len:
            allowed = allowed | (kpos[None, :] < prefix_len)
        ok &= allowed
    if window:
        ok &= (qpos[:, None] - kpos[None, :]) < window
    return jnp.where(ok, logits, -jnp.inf)


def _direct_attention(q, k, v, qpos, kpos, *, causal, window, prefix_len,
                      scale) -> jax.Array:
    """q: (B,Sq,H,D), k/v: (B,Sk,KV,D) -> (B,Sq,H,D)."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = _mask_logits(logits, qpos, kpos, causal, window, prefix_len)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)          # fully-masked rows
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def _chunked_attention(q, k, v, qpos, kpos, *, causal, window, prefix_len,
                       scale, q_chunk=_Q_CHUNK, kv_chunk=_KV_CHUNK) -> jax.Array:
    """Two-level online-softmax scan; memory O(q_chunk * kv_chunk)."""
    b, sq, h, d = q.shape
    dv = v.shape[-1]
    sk = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    q_chunk = math.gcd(sq, q_chunk)
    kv_chunk = math.gcd(sk, kv_chunk)
    nq, nk = sq // q_chunk, sk // kv_chunk

    qg = q.reshape(b, nq, q_chunk, kvh, g, d)
    qpos_c = qpos.reshape(nq, q_chunk)
    kc = k.reshape(b, nk, kv_chunk, kvh, d)
    vc = v.reshape(b, nk, kv_chunk, kvh, d)
    kpos_c = kpos.reshape(nk, kv_chunk)

    # flash-style memory discipline: checkpoint both scan bodies so the
    # backward pass RECOMPUTES the per-chunk probability tiles instead of
    # saving the full O(S^2) f32 attention matrix (measured 16 GB/device
    # per layer for command-r train_4k — EXPERIMENTS.md §Perf iteration 2).
    @jax.checkpoint
    def q_body(_, qi):
        qblk, qp = qi                             # (b,qc,kvh,g,d), (qc,)
        acc0 = jnp.zeros((b, kvh, g, q_chunk, dv), jnp.float32)
        m0 = jnp.full((b, kvh, g, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)

        @jax.checkpoint
        def kv_body(carry, ki):
            acc, m, l = carry
            kblk, vblk, kp = ki
            logits = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk,
                                preferred_element_type=jnp.float32) * scale
            logits = _mask_logits(logits, qp, kp, causal, window, prefix_len)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(logits - m_safe[..., None])
            p = jnp.where(jnp.isfinite(logits), p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            l = l * corr + jnp.sum(p, axis=-1)
            return (acc, m_new, l), None

        (acc, _, l), _ = jax.lax.scan(
            kv_body, (acc0, m0, l0),
            (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), kpos_c))
        out = acc / jnp.maximum(l[..., None], 1e-30)   # (b,kvh,g,qc,d)
        return None, out

    _, outs = jax.lax.scan(q_body, None,
                           (jnp.moveaxis(qg, 1, 0), qpos_c))
    # outs: (nq, b, kvh, g, qc, d)
    out = jnp.transpose(outs, (1, 0, 4, 2, 3, 5)).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


def _banded_attention(q, k, v, qpos, kpos, *, window, scale,
                      q_chunk=_Q_CHUNK) -> jax.Array:
    """Sliding-window causal attention with exact O(S*window) cost: each query
    chunk attends only to the kv band [chunk_start - window, chunk_end)."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q_chunk = math.gcd(sq, q_chunk)
    nq = sq // q_chunk
    band = window + q_chunk
    # pad kv on the left so every band slice is in range
    pad = band
    kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    kpos_p = jnp.pad(kpos, (pad, 0), constant_values=-10 ** 9)

    qg = q.reshape(b, nq, q_chunk, kvh, g, d)
    qpos_c = qpos.reshape(nq, q_chunk)
    starts = jnp.arange(nq) * q_chunk          # band end = start + q_chunk

    @jax.checkpoint
    def body(_, xs):
        qblk, qp, start = xs
        kb = jax.lax.dynamic_slice_in_dim(kp, start + pad + q_chunk - band, band, 1)
        vb = jax.lax.dynamic_slice_in_dim(vp, start + pad + q_chunk - band, band, 1)
        kpb = jax.lax.dynamic_slice_in_dim(kpos_p, start + pad + q_chunk - band,
                                           band, 0)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kb,
                            preferred_element_type=jnp.float32) * scale
        logits = _mask_logits(logits, qp, kpb, True, window, 0)
        w = jax.nn.softmax(logits, axis=-1)
        w = jnp.where(jnp.isnan(w), 0.0, w)
        out = jnp.einsum("bkgqs,bskd->bkgqd", w.astype(vb.dtype), vb,
                         preferred_element_type=jnp.float32)
        return None, out

    _, outs = jax.lax.scan(body, None,
                           (jnp.moveaxis(qg, 1, 0), qpos_c, starts))
    out = jnp.transpose(outs, (1, 0, 4, 2, 3, 5)).reshape(b, sq, h, d)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------
def init_attn_cache(cfg: ModelConfig, batch: int, max_seq: int, *,
                    window: int = 0, kv_len: Optional[int] = None,
                    dtype=jnp.float32) -> Params:
    s = kv_len if kv_len is not None else (min(max_seq, window) if window
                                           else max_seq)
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, s, kvh, hd), dtype),
        "v": jnp.zeros((batch, s, kvh, hd), dtype),
        "pos": jnp.full((batch, s), -1, jnp.int32),
    }


def is_ring(cache) -> bool:
    """True for a dense ring cache node: keys and values, or MLA's latent."""
    return (isinstance(cache, dict) and "pos" in cache
            and ("k" in cache or "ckv" in cache))


def _cache_write(cache: Params, k: jax.Array, v: jax.Array,
                 positions: jax.Array, offset) -> Params:
    """Write S new kv entries at ring positions (offset..offset+S-1) % size."""
    return _ring_write(cache, {"k": k, "v": v}, positions, offset)


def _ring_write(cache: Params, new: Params, positions: jax.Array,
                offset) -> Params:
    """Write S new entries of each leaf of ``new`` ((B, S, ...) each) at
    ring positions (offset..offset+S-1) % size."""
    b, s = next(iter(new.values())).shape[:2]
    size = cache["pos"].shape[1]
    if s == size and isinstance(offset, int) and offset == 0:
        pos = jnp.broadcast_to(positions[None, :], cache["pos"].shape)
        out = {n: x.astype(cache[n].dtype) for n, x in new.items()}
        return {**out, "pos": pos.astype(jnp.int32)}
    if s > size:
        # only the last `size` entries survive in the ring
        new = {n: x[:, s - size:] for n, x in new.items()}
        positions = positions[s - size:]
        s = size
    idx = (positions % size).astype(jnp.int32)
    out = {n: cache[n].at[:, idx].set(x.astype(cache[n].dtype))
           for n, x in new.items()}
    out["pos"] = cache["pos"].at[:, idx].set(
        jnp.broadcast_to(positions[None, :], (b, s)).astype(jnp.int32))
    return out


# ---------------------------------------------------------------------------
# Paged caches (block tables; see repro.core.paging)
# ---------------------------------------------------------------------------
def quantize_kv_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-row int8 quantization of K/V entries: one absmax scale per
    ``(..., kv_head)`` row over ``head_dim`` — the same scaling as the
    transport quantizer (``repro.kernels.quantize``).

    x: (..., KV, d) -> (q int8 (..., KV, d), scale fp32 (..., KV))."""
    from repro.kernels.quantize.ref import quantize_int8_ref
    q, s = quantize_int8_ref(x)
    return q, s[..., 0]


def init_paged_attn_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                          *, dtype=jnp.float32,
                          kv_dtype: str = "float32") -> Params:
    """Page-pool KV storage for ONE layer.  Physical page 0 is the trash
    page (writes of unmapped rows land there); ``pos = -1`` marks an empty
    page slot, so a freshly (re)allocated page is invisible to attention
    until it is written.

    ``kv_dtype="int8"`` stores pages quantized per page-row: ``kp``/``vp``
    become int8 and per-row absmax scales ride alongside as ``ks``/``vs``
    ``(P+1, page_size, KV)`` float32 — page axis 0 like ``kp``, so every
    page-axis consumer (gather/scatter/swap) handles them generically."""
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    p = num_pages + 1                              # + trash page
    if kv_dtype == "int8":
        return {
            "kp": jnp.zeros((p, page_size, kvh, hd), jnp.int8),
            "vp": jnp.zeros((p, page_size, kvh, hd), jnp.int8),
            "ks": jnp.zeros((p, page_size, kvh), jnp.float32),
            "vs": jnp.zeros((p, page_size, kvh), jnp.float32),
            "pos": jnp.full((p, page_size), -1, jnp.int32),
        }
    if kv_dtype != "float32":
        raise ValueError(f"kv_dtype must be 'float32' or 'int8', "
                         f"got {kv_dtype!r}")
    return {
        "kp": jnp.zeros((p, page_size, kvh, hd), dtype),
        "vp": jnp.zeros((p, page_size, kvh, hd), dtype),
        "pos": jnp.full((p, page_size), -1, jnp.int32),
    }


def paged_scatter_prefill(cache: Params, row: Params,
                          pages: jax.Array) -> Params:
    """Scatter a single-row dense prefill cache into physical pages.

    ``row``: dense cache {"k": (1, L, KV, d), ...} as produced by prefill
    on one stream (ring wide enough that slot ``s`` holds position ``s``).
    ``pages``: (ceil(L / page_size),) physical page ids; entries ``< 0``
    redirect to the trash page (right-pad positions beyond the pages the
    allocator actually granted — their ``pos`` is already -1)."""
    ps = cache["kp"].shape[1]
    n_lp = pages.shape[0]
    dest = jnp.where(pages >= 0, pages, 0).astype(jnp.int32)

    def tiles(x, fill):
        x = x[0][:n_lp * ps]                       # drop batch axis, trim ring
        pad = n_lp * ps - x.shape[0]
        if pad:
            cfgpad = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
            x = jnp.pad(x, cfgpad, constant_values=fill)
        return x.reshape((n_lp, ps) + x.shape[1:])

    out = {"pos": cache["pos"].at[dest].set(tiles(row["pos"], -1).astype(
        jnp.int32))}
    if "ks" in cache:                              # int8 pages + scales
        qk, sk = quantize_kv_rows(row["k"])
        qv, sv = quantize_kv_rows(row["v"])
        out["kp"] = cache["kp"].at[dest].set(tiles(qk, 0))
        out["vp"] = cache["vp"].at[dest].set(tiles(qv, 0))
        out["ks"] = cache["ks"].at[dest].set(tiles(sk, 0.0))
        out["vs"] = cache["vs"].at[dest].set(tiles(sv, 0.0))
    else:
        out["kp"] = cache["kp"].at[dest].set(tiles(row["k"], 0).astype(
            cache["kp"].dtype))
        out["vp"] = cache["vp"].at[dest].set(tiles(row["v"], 0).astype(
            cache["vp"].dtype))
    return out


def paged_reset_pages(cache: Params, pages: jax.Array) -> Params:
    """Invalidate the given physical pages (``pos = -1``) so a page freed
    from a retired stream never leaks stale K/V once reallocated.  Entries
    ``< 0`` redirect to the trash page (already invalid)."""
    dest = jnp.where(pages >= 0, pages, 0).astype(jnp.int32)
    return {**cache, "pos": cache["pos"].at[dest].set(-1)}


def paged_gather(cache: Params, block_tbl: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Materialize the logical (B, max_logical*page_size) K/V view of a
    paged cache through the block table (unmapped pages read the trash page
    and are masked via ``pos = -1``)."""
    b, n_lp = block_tbl.shape
    ps = cache["kp"].shape[1]
    phys = jnp.where(block_tbl >= 0, block_tbl, 0)
    k, v = cache["kp"][phys], cache["vp"][phys]
    if "ks" in cache:                              # dequantize int8 pages
        k = k.astype(jnp.float32) * cache["ks"][phys][..., None]
        v = v.astype(jnp.float32) * cache["vs"][phys][..., None]
    k = k.reshape(b, n_lp * ps, *k.shape[3:])
    v = v.reshape(b, n_lp * ps, *v.shape[3:])
    kpos = jnp.where(block_tbl[:, :, None] >= 0, cache["pos"][phys],
                     -1).reshape(b, n_lp * ps)
    return k, v, kpos


# ---------------------------------------------------------------------------
# Public forwards
# ---------------------------------------------------------------------------
def attention_forward(params: Params, cfg: ModelConfig, x: jax.Array, *,
                      positions: Optional[jax.Array] = None,
                      enc_out: Optional[jax.Array] = None,
                      causal: bool = True,
                      window: int = 0,
                      prefix_len: int = 0,
                      use_rope: bool = True,
                      cache: Optional[Params] = None,
                      cache_offset: int = 0) -> Tuple[jax.Array, Optional[Params]]:
    """Full-sequence attention (training / prefill).

    ``enc_out`` switches to cross-attention (no mask, no rope on kv).
    Returns (output, updated_cache_or_None)."""
    b, s, _ = x.shape
    kv_src = enc_out if enc_out is not None else x
    sk = kv_src.shape[1]
    q, k, v = _project_qkv(params, cfg, x, kv_src)
    if positions is None:
        positions = jnp.arange(s)
    kpos = jnp.arange(sk) if enc_out is not None else positions
    if use_rope and enc_out is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    cross = enc_out is not None

    if cross:
        out = (_direct_attention if s * sk <= _DIRECT_LIMIT else
               _chunked_attention)(q, k, v, positions, kpos, causal=False,
                                   window=0, prefix_len=0, scale=scale)
    elif window and s > window:
        out = _banded_attention(q, k, v, positions, kpos, window=window,
                                scale=scale)
    elif s * sk <= _DIRECT_LIMIT:
        out = _direct_attention(q, k, v, positions, kpos, causal=causal,
                                window=window, prefix_len=prefix_len,
                                scale=scale)
    else:
        out = _chunked_attention(q, k, v, positions, kpos, causal=causal,
                                 window=window, prefix_len=prefix_len,
                                 scale=scale)

    new_cache = None
    if cache is not None:
        with jax.named_scope("kv_write"):
            new_cache = _cache_write(cache, k, v, kpos, cache_offset)
    y = jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1),
                   params["wo"].astype(x.dtype))
    return y, new_cache


def decode_attention(params: Params, cfg: ModelConfig, x: jax.Array,
                     cache: Params, pos: jax.Array, *,
                     window: int = 0, use_rope: bool = True,
                     cross: bool = False,
                     update_cache: bool = True,
                     write_mask: Optional[jax.Array] = None,
                     layer: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Params]:
    """Single-token decode.  x: (B,1,d); pos: scalar int32 position or a
    per-row (B,) position vector (continuous batching: every row decodes at
    its own sequence offset).  For ``cross=True`` the cache holds precomputed
    encoder kv (no update).

    ``write_mask`` (B,) bool drops the KV write of every row where it is
    False: its slot index is sent out of range and the scatter drops it, so
    that row's ``k``/``v``/``pos`` stay bit-for-bit as they were (the masked
    cloud step needs no cache merge afterwards).  ``layer`` makes ``cache``
    a whole stacked segment cache (leaves ``(L, B, ...)``): the new entries
    go in at ``[layer, row, slot]`` and attention reads layer ``layer`` of
    it, so a layer loop can carry the stack and update it in place."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q = jnp.einsum("bsd,de->bse", x, params["wq"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
    q = q.reshape(b, 1, h, hd)
    if use_rope and not cross:
        q = apply_rope(q, pos_b[:, None], cfg.rope_theta)

    new_cache = cache
    if not cross:
        knew = jnp.einsum("bsd,de->bse", x, params["wk"].astype(x.dtype))
        vnew = jnp.einsum("bsd,de->bse", x, params["wv"].astype(x.dtype))
        if "bk" in params:
            knew = knew + params["bk"].astype(x.dtype)
            vnew = vnew + params["bv"].astype(x.dtype)
        knew = knew.reshape(b, 1, kvh, hd)
        vnew = vnew.reshape(b, 1, kvh, hd)
        if use_rope:
            knew = apply_rope(knew, pos_b[:, None], cfg.rope_theta)
        if update_cache:
            with jax.named_scope("kv_write"):
                size = cache["k"].shape[-3]
                slot = (pos_b % size).astype(jnp.int32)
                if write_mask is not None:
                    slot = jnp.where(write_mask, slot, size)
                bidx = jnp.arange(b)
                idx = (bidx, slot) if layer is None else (layer, bidx, slot)
                ck = cache["k"].at[idx].set(
                    knew[:, 0].astype(cache["k"].dtype), mode="drop")
                cv = cache["v"].at[idx].set(
                    vnew[:, 0].astype(cache["v"].dtype), mode="drop")
                cp = cache["pos"].at[idx].set(pos_b, mode="drop")
            new_cache = {"k": ck, "v": cv, "pos": cp}
    k, v, kpos = new_cache["k"], new_cache["v"], new_cache["pos"]
    if layer is not None:
        k, v, kpos = (jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
                      for a in (k, v, kpos))

    g = h // kvh
    qg = q.reshape(b, kvh, g, hd)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    valid = kpos >= 0
    if not cross:
        valid &= kpos <= pos_b[:, None]
        if window:
            valid &= (pos_b[:, None] - kpos) < window
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v.astype(jnp.float32))
    out = out.reshape(b, 1, h * hd).astype(x.dtype)
    y = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    return y, new_cache


def _paged_write(cache: Params, dest: jax.Array, slot: jax.Array,
                 ok: jax.Array, pos: jax.Array, k: jax.Array,
                 v: jax.Array) -> Params:
    """Write new K/V entries (``k``/``v``: ``dest.shape + (KV, d)``) at
    page ``dest``, slot ``slot``; entries with ``ok`` False carry
    ``pos = -1`` (the callers point them at the trash page)."""
    with jax.named_scope("kv_write"):
        new_cache = {"pos": cache["pos"].at[dest, slot].set(
            jnp.where(ok, pos, -1))}
        if "ks" in cache:                          # quantize on write
            qk, sk = quantize_kv_rows(k)           # int8 entries, scales
            qv, sv = quantize_kv_rows(v)
            new_cache["kp"] = cache["kp"].at[dest, slot].set(qk)
            new_cache["vp"] = cache["vp"].at[dest, slot].set(qv)
            new_cache["ks"] = cache["ks"].at[dest, slot].set(sk)
            new_cache["vs"] = cache["vs"].at[dest, slot].set(sv)
        else:
            new_cache["kp"] = cache["kp"].at[dest, slot].set(
                k.astype(cache["kp"].dtype))
            new_cache["vp"] = cache["vp"].at[dest, slot].set(
                v.astype(cache["vp"].dtype))
    return new_cache


def decode_attention_paged(params: Params, cfg: ModelConfig, x: jax.Array,
                           cache: Params, pos: jax.Array,
                           block_tbl: jax.Array, *,
                           window: int = 0, use_rope: bool = True,
                           write_mask: Optional[jax.Array] = None
                           ) -> Tuple[jax.Array, Params]:
    """Single-token decode over a block-paged KV cache.

    x: (B,1,d); pos: scalar or per-row (B,) positions; block_tbl:
    (B, max_logical) physical page ids (-1 = unallocated).  Each row writes
    its new K/V at page ``block_tbl[b, pos // page_size]``, slot
    ``pos % page_size``; rows without a mapping there — inactive slots, or
    rows excluded by ``write_mask`` (masked cloud step) — are redirected to
    the trash page with ``pos = -1``, so no cache merge is needed
    afterwards.  Attention then gathers the logical K/V view through the
    table and masks exactly like the dense ring path."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    ps = cache["kp"].shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q = jnp.einsum("bsd,de->bse", x, params["wq"].astype(x.dtype))
    knew = jnp.einsum("bsd,de->bse", x, params["wk"].astype(x.dtype))
    vnew = jnp.einsum("bsd,de->bse", x, params["wv"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        knew = knew + params["bk"].astype(x.dtype)
        vnew = vnew + params["bv"].astype(x.dtype)
    q = q.reshape(b, 1, h, hd)
    knew = knew.reshape(b, 1, kvh, hd)
    vnew = vnew.reshape(b, 1, kvh, hd)
    if use_rope:
        q = apply_rope(q, pos_b[:, None], cfg.rope_theta)
        knew = apply_rope(knew, pos_b[:, None], cfg.rope_theta)

    page = block_tbl[jnp.arange(b), pos_b // ps]        # (B,)
    ok = page >= 0
    if write_mask is not None:
        ok &= write_mask
    dest = jnp.where(ok, page, 0)
    slot = (pos_b % ps).astype(jnp.int32)
    cache = _paged_write(cache, dest, slot, ok, pos_b, knew[:, 0],
                         vnew[:, 0])

    k, v, kpos = paged_gather(cache, block_tbl)
    g = h // kvh
    qg = q.reshape(b, kvh, g, hd)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    valid = (kpos >= 0) & (kpos <= pos_b[:, None])
    if window:
        valid &= (pos_b[:, None] - kpos) < window
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v.astype(jnp.float32))
    out = out.reshape(b, 1, h * hd).astype(x.dtype)
    y = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    return y, cache


def chunk_attention_paged(params: Params, cfg: ModelConfig, x: jax.Array,
                          cache: Params, pos: jax.Array,
                          block_tbl: jax.Array, *,
                          window: int = 0, use_rope: bool = True,
                          write_mask: Optional[jax.Array] = None
                          ) -> Tuple[jax.Array, Params]:
    """Multi-token chunk decode over a block-paged KV cache — the compute
    path of chunked prefill (``decode_attention_paged`` generalized from
    one token to a page-sized chunk).

    x: (B,C,d); pos: scalar or per-row (B,) FIRST position of each row's
    chunk (token ``i`` sits at ``pos + i``); block_tbl: (B, max_logical).
    Each token writes its K/V at page ``block_tbl[b, (pos+i) // ps]``, slot
    ``(pos+i) % ps``; tokens without a mapping or excluded by
    ``write_mask`` ((B,C) per-token, or (B,) per-row) go to the trash page
    with ``pos = -1`` — this is how a right-padded final chunk keeps its
    pad positions invisible.  Writes land before the gather, so tokens of
    the same chunk attend to each other through the pages, and the causal
    ``kpos <= qpos`` mask plays the same role as in the dense prefill."""
    b, c, _ = x.shape
    hd = cfg.resolved_head_dim
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    ps = cache["kp"].shape[1]
    pos0 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    pos_bc = pos0[:, None] + jnp.arange(c, dtype=jnp.int32)     # (B,C)
    q = jnp.einsum("bsd,de->bse", x, params["wq"].astype(x.dtype))
    knew = jnp.einsum("bsd,de->bse", x, params["wk"].astype(x.dtype))
    vnew = jnp.einsum("bsd,de->bse", x, params["wv"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        knew = knew + params["bk"].astype(x.dtype)
        vnew = vnew + params["bv"].astype(x.dtype)
    q = q.reshape(b, c, h, hd)
    knew = knew.reshape(b, c, kvh, hd)
    vnew = vnew.reshape(b, c, kvh, hd)
    if use_rope:
        q = apply_rope(q, pos_bc, cfg.rope_theta)
        knew = apply_rope(knew, pos_bc, cfg.rope_theta)

    bidx = jnp.arange(b)[:, None]
    page = block_tbl[bidx, pos_bc // ps]                        # (B,C)
    ok = page >= 0
    if write_mask is not None:
        wm = write_mask if write_mask.ndim == 2 else write_mask[:, None]
        ok &= wm
    dest = jnp.where(ok, page, 0)
    slot = (pos_bc % ps).astype(jnp.int32)
    cache = _paged_write(cache, dest, slot, ok, pos_bc, knew, vnew)

    k, v, kpos = paged_gather(cache, block_tbl)
    g = h // kvh
    qg = q.reshape(b, c, kvh, g, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    valid = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= pos_bc[..., None])
    if window:
        valid &= (pos_bc[..., None] - kpos[:, None, :]) < window
    logits = jnp.where(valid[:, None, None, :, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    out = out.reshape(b, c, h * hd).astype(x.dtype)
    y = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    return y, cache


def build_cross_cache(params: Params, cfg: ModelConfig,
                      enc_out: jax.Array, dtype=None) -> Params:
    """Precompute encoder kv for cross-attention decode."""
    b, sk, _ = enc_out.shape
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    k = jnp.einsum("bsd,de->bse", enc_out, params["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,de->bse", enc_out, params["wv"].astype(enc_out.dtype))
    if "bk" in params:
        k = k + params["bk"].astype(enc_out.dtype)
        v = v + params["bv"].astype(enc_out.dtype)
    dt = dtype or enc_out.dtype
    return {"k": k.reshape(b, sk, kvh, hd).astype(dt),
            "v": v.reshape(b, sk, kvh, hd).astype(dt),
            "pos": jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32)[None], (b, sk))}


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434)
# ---------------------------------------------------------------------------
def init_mla(rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """``wq`` gives each head a query of ``qk_nope + qk_rope``; ``wkv_a``
    gives the latent ``c_kv`` and the shared rotary key; ``kv_norm`` is the
    latent's RMSNorm; ``wkv_b`` expands the latent into each head's key
    (``qk_nope``) and value (``v_head_dim``)."""
    mla, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    r = split_rngs(rng, 4)
    return {
        "wq": dense_init(r[0], d, h * mla.qk_head_dim, dtype),
        "wkv_a": dense_init(r[1], d, mla.kv_lora_rank + mla.qk_rope_head_dim,
                            dtype),
        "kv_norm": jnp.zeros((mla.kv_lora_rank,), dtype),
        "wkv_b": dense_init(r[2], mla.kv_lora_rank,
                            h * (mla.qk_nope_head_dim + mla.v_head_dim),
                            dtype),
        "wo": dense_init(r[3], h * mla.v_head_dim, d, dtype),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """qk_head_dim^-1/2, times YaRN's mscale(mscale_all_dim) squared."""
    scale = cfg.mla.qk_head_dim ** -0.5
    sc = cfg.rope_scaling
    if sc is not None and sc.mscale_all_dim:
        m = yarn_mscale(sc.factor, sc.mscale_all_dim)
        scale *= m * m
    return scale


def init_mla_cache(cfg: ModelConfig, batch: int, max_seq: int,
                   dtype=jnp.float32) -> Params:
    """A dense ring of the latent: the normed ``c_kv`` and the rotated
    shared key ``k_pe`` of each position, all heads' keys and values."""
    mla = cfg.mla
    return {"ckv": jnp.zeros((batch, max_seq, mla.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, max_seq, mla.qk_rope_head_dim), dtype),
            "pos": jnp.full((batch, max_seq), -1, jnp.int32)}


def _mla_project(params: Params, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array):
    """x (B, S, d) -> q_nope (B, S, H, nope), rotated q_pe (B, S, H, rope),
    normed latent (B, S, rank) and rotated shared key (B, S, rope)."""
    mla, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, params["wq"].astype(x.dtype))
    q = q.reshape(b, s, h, mla.qk_head_dim)
    q_nope, q_pe = q[..., :mla.qk_nope_head_dim], q[..., mla.qk_nope_head_dim:]
    kv = jnp.einsum("bsd,de->bse", x, params["wkv_a"].astype(x.dtype))
    ckv = rms_norm(kv[..., :mla.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    kpe = kv[..., mla.kv_lora_rank:][:, :, None, :]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta, cfg.rope_scaling)
    kpe = apply_rope(kpe, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_pe, ckv, kpe[:, :, 0]


def _mla_up(params: Params, cfg: ModelConfig, dtype):
    """``wkv_b`` split per head: W_UK (rank, H, nope), W_UV (rank, H, v)."""
    mla = cfg.mla
    w = params["wkv_b"].astype(dtype).reshape(
        mla.kv_lora_rank, cfg.n_heads, mla.qk_nope_head_dim + mla.v_head_dim)
    return w[..., :mla.qk_nope_head_dim], w[..., mla.qk_nope_head_dim:]


def mla_forward(params: Params, cfg: ModelConfig, x: jax.Array, *,
                positions: Optional[jax.Array] = None,
                cache: Optional[Params] = None,
                cache_offset: int = 0) -> Tuple[jax.Array, Optional[Params]]:
    """Causal full-sequence MLA (training / prefill), expanded: each head's
    keys and values come from the latent through ``wkv_b``, and its key
    gains the shared rotary part.  A ``cache`` receives the latent."""
    mla, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q_nope, q_pe, ckv, kpe = _mla_project(params, cfg, x, positions)
    w_uk, w_uv = _mla_up(params, cfg, x.dtype)
    k_nope = jnp.einsum("bsc,chn->bshn", ckv, w_uk)
    v = jnp.einsum("bsc,chv->bshv", ckv, w_uv)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kpe[:, :, None, :], (b, s, h, mla.qk_rope_head_dim))], -1)
    core = _direct_attention if s * s <= _DIRECT_LIMIT else _chunked_attention
    out = core(q, k, v, positions, positions, causal=True, window=0,
               prefix_len=0, scale=mla_softmax_scale(cfg))
    new_cache = None
    if cache is not None:
        with jax.named_scope("kv_write"):
            new_cache = _ring_write(cache, {"ckv": ckv, "kpe": kpe},
                                    positions, cache_offset)
    y = jnp.einsum("bse,ed->bsd", out.reshape(b, s, h * mla.v_head_dim),
                   params["wo"].astype(x.dtype))
    return y, new_cache


def mla_decode(params: Params, cfg: ModelConfig, x: jax.Array,
               cache: Params, pos: jax.Array, *,
               write_mask: Optional[jax.Array] = None,
               layer: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Params]:
    """Single-token MLA decode, absorbed: ``q_nope`` is taken through
    W_UK into the latent space and scored against the cached ``c_kv``
    (plus ``q_pe`` against the cached rotary keys); the weighted latent
    then goes through W_UV and ``wo``.  No head's key or value is
    expanded, so each row reads its latent once for all heads.
    ``pos``/``write_mask``/``layer`` as in ``decode_attention``."""
    mla, h = cfg.mla, cfg.n_heads
    b = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q_nope, q_pe, ckv, kpe = _mla_project(params, cfg, x, pos_b[:, None])
    with jax.named_scope("kv_write"):
        size = cache["ckv"].shape[-2]
        slot = (pos_b % size).astype(jnp.int32)
        if write_mask is not None:
            slot = jnp.where(write_mask, slot, size)
        bidx = jnp.arange(b)
        idx = (bidx, slot) if layer is None else (layer, bidx, slot)
        new_cache = {
            "ckv": cache["ckv"].at[idx].set(
                ckv[:, 0].astype(cache["ckv"].dtype), mode="drop"),
            "kpe": cache["kpe"].at[idx].set(
                kpe[:, 0].astype(cache["kpe"].dtype), mode="drop"),
            "pos": cache["pos"].at[idx].set(pos_b, mode="drop")}
    c, kr, kpos = new_cache["ckv"], new_cache["kpe"], new_cache["pos"]
    if layer is not None:
        c, kr, kpos = (jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
                       for a in (c, kr, kpos))
    w_uk, w_uv = _mla_up(params, cfg, x.dtype)
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32)
    logits = (jnp.einsum("bhc,bsc->bhs", q_lat, c.astype(jnp.float32))
              + jnp.einsum("bhr,bsr->bhs", q_pe[:, 0].astype(jnp.float32),
                           kr.astype(jnp.float32)))
    logits = logits * mla_softmax_scale(cfg)
    valid = (kpos >= 0) & (kpos <= pos_b[:, None])
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    o_lat = jnp.einsum("bhs,bsc->bhc", w, c.astype(jnp.float32))
    o = jnp.einsum("bhc,chv->bhv", o_lat.astype(x.dtype), w_uv)
    y = jnp.einsum("bse,ed->bsd", o.reshape(b, 1, h * mla.v_head_dim),
                   params["wo"].astype(x.dtype))
    return y, new_cache
