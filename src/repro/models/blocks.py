"""Per-layer blocks with a uniform interface used by the stack assembler.

Interface (kind in {dense, moe, mlstm, slstm, mamba2, shared_attn}):

    init_block(rng, cfg, kind)                     -> params for ONE layer
    block_forward(params, cfg, kind, x, ctx)       -> (x, aux, new_cache)
    block_decode(params, cfg, kind, x, cache, ctx) -> (x, new_cache, held)
    init_block_cache(cfg, kind, batch, meta)       -> cache for ONE layer

``ctx`` carries positions / encoder output / layer meta (window, cross-attn)
so stacked-scan callers can slice per-layer values.

With ``cfg.mla`` the attention of dense and moe blocks is multi-head
latent attention (``models/attention.py``; its cache is the latent) under
the named scope ``mla``; a moe block's FFN runs under ``moe``, a dense
one's under ``mlp``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (DENSE, MAMBA2, MLSTM, MOE, SHARED_ATTN, SLSTM,
                                ModelConfig)
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models import xlstm
from repro.models.attention import (attention_forward, build_cross_cache,
                                    chunk_attention_paged, decode_attention,
                                    decode_attention_paged, init_attn_cache,
                                    init_mla, init_mla_cache,
                                    init_paged_attn_cache, mla_decode,
                                    mla_forward)
from repro.models.common import dense_init, layer_norm, rms_norm, split_rngs
from repro.launch.sharding import constrain_residual

Params = Dict[str, Any]


@dataclasses.dataclass
class BlockCtx:
    positions: Optional[jax.Array] = None   # (S,) absolute positions
    enc_out: Optional[jax.Array] = None     # encoder output (enc-dec only)
    prefix_len: int = 0                     # VLM prefix-LM boundary
    window: int = 0                         # sliding window for this layer
    causal: bool = True
    pos: Any = None                         # decode position: scalar or (B,)
    max_seq: int = 0                        # cache capacity (decode)
    cache_offset: int = 0                   # prefill write offset
    block_tbl: Optional[jax.Array] = None   # (B, max_logical) paged KV table
    write_mask: Optional[jax.Array] = None  # (B,) rows allowed to write KV
    dtype: Any = jnp.float32


def _norm(x, params, cfg, key):
    if cfg.norm_type == "layernorm":
        return layer_norm(x, params[key + "_scale"], params[key + "_bias"],
                          cfg.norm_eps)
    return rms_norm(x, params[key + "_scale"], cfg.norm_eps)


def _init_norm(cfg, d, dtype):
    if cfg.norm_type == "layernorm":
        return {"_scale": jnp.ones((d,), dtype), "_bias": jnp.zeros((d,), dtype)}
    return {"_scale": jnp.zeros((d,), dtype)}


def _mlp_init(rng, cfg, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    r = split_rngs(rng, 3)
    if cfg.mlp_kind == "gelu":
        return {"w1": dense_init(r[0], d, f, dtype),
                "b1": jnp.zeros((f,), dtype),
                "w2": dense_init(r[1], f, d, dtype),
                "b2": jnp.zeros((d,), dtype)}
    return {"w_gate": dense_init(r[0], d, f, dtype),
            "w_up": dense_init(r[1], d, f, dtype),
            "w_down": dense_init(r[2], f, d, dtype)}


def _mlp(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.mlp_kind == "gelu":
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, params["w1"].astype(x.dtype))
                        + params["b1"].astype(x.dtype))
        return jnp.einsum("bsf,fd->bsd", h, params["w2"].astype(x.dtype)) \
            + params["b2"].astype(x.dtype)
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(x.dtype)))
    u = jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", g * u, params["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_block(rng: jax.Array, cfg: ModelConfig, kind: str,
               dtype=jnp.float32, with_cross: Optional[bool] = None) -> Params:
    from repro.models.attention import init_attention
    if with_cross is None:
        with_cross = cfg.is_encdec
    r = split_rngs(rng, 4)
    attn = (init_mla(r[0], cfg, dtype) if cfg.mla is not None
            else init_attention(r[0], cfg, dtype=dtype))
    if kind in (DENSE, SHARED_ATTN):
        p: Params = {"attn": attn, "mlp": _mlp_init(r[1], cfg, dtype)}
        for k, v in _init_norm(cfg, cfg.d_model, dtype).items():
            p["ln1" + k] = v
            p["ln2" + k] = v
        if with_cross:
            p["cross"] = init_attention(r[2], cfg, cross=True, dtype=dtype)
            for k, v in _init_norm(cfg, cfg.d_model, dtype).items():
                p["lnx" + k] = v
        return p
    if kind == MOE:
        p = {"attn": attn, "moe": moe_mod.init_moe(r[1], cfg, dtype)}
        for k, v in _init_norm(cfg, cfg.d_model, dtype).items():
            p["ln1" + k] = v
            p["ln2" + k] = v
        return p
    if kind == MLSTM:
        return xlstm.init_mlstm_block(r[0], cfg, dtype)
    if kind == SLSTM:
        return xlstm.init_slstm_block(r[0], cfg, dtype)
    if kind == MAMBA2:
        return m2.init_mamba2_block(r[0], cfg, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                     window: int, dtype=jnp.float32) -> Params:
    if kind in (DENSE, SHARED_ATTN, MOE) and cfg.mla is not None:
        return {"self": init_mla_cache(cfg, batch, max_seq, dtype=dtype)}
    if kind in (DENSE, SHARED_ATTN, MOE):
        c: Params = {"self": init_attn_cache(cfg, batch, max_seq,
                                             window=window, dtype=dtype)}
        if cfg.is_encdec and kind != MOE:
            c["cross"] = init_attn_cache(cfg, batch, cfg.encoder_seq,
                                         kv_len=cfg.encoder_seq, dtype=dtype)
        return c
    return _init_recurrent_cache(cfg, kind, batch, dtype)


def init_block_cache_paged(cfg: ModelConfig, kind: str, batch: int,
                           num_pages: int, page_size: int,
                           dtype=jnp.float32,
                           kv_dtype: str = "float32") -> Params:
    """Paged variant: self-attention K/V lives in the shared page pool
    (no batch axis — rows address it through their block table); cross-attn
    and recurrent state stay dense per-row (fixed size, nothing to page).
    ``kv_dtype="int8"`` stores the pages quantized with per-row scales."""
    if cfg.mla is not None:
        raise ValueError(f"{cfg.name}: latent attention (MLA) caches its "
                         f"latent in the dense layout only; use "
                         f'kv_layout="dense"')
    if kind in (DENSE, SHARED_ATTN, MOE):
        c: Params = {"self": init_paged_attn_cache(cfg, num_pages, page_size,
                                                   dtype=dtype,
                                                   kv_dtype=kv_dtype)}
        if cfg.is_encdec and kind != MOE:
            c["cross"] = init_attn_cache(cfg, batch, cfg.encoder_seq,
                                         kv_len=cfg.encoder_seq, dtype=dtype)
        return c
    return _init_recurrent_cache(cfg, kind, batch, dtype)


def _init_recurrent_cache(cfg: ModelConfig, kind: str, batch: int,
                          dtype) -> Params:
    if kind == MLSTM:
        return xlstm.init_mlstm_cache(cfg, batch, dtype)
    if kind == SLSTM:
        return xlstm.init_slstm_cache(cfg, batch, dtype)
    if kind == MAMBA2:
        return m2.init_mamba2_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# the block's second half: a dense MLP or the experts
# ---------------------------------------------------------------------------
def _ffn(params: Params, cfg: ModelConfig, kind: str, x: jax.Array
         ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The served FFN after ``ln2``: (y, held pairs per row or None)."""
    if kind == MOE:
        with jax.named_scope("moe"):
            return moe_mod.moe_served(params["moe"], cfg,
                                      _norm(x, params, cfg, "ln2"))
    with jax.named_scope("mlp"):
        return _mlp(params["mlp"], cfg, _norm(x, params, cfg, "ln2")), None


def _ffn_forward(params: Params, cfg: ModelConfig, kind: str, x: jax.Array,
                 served: bool) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence FFN: (y, aux loss).  A prefill (``served``) runs the
    served, dropless experts; training the capacity form."""
    aux = jnp.zeros((), jnp.float32)
    if kind == MOE and not served:
        with jax.named_scope("moe"):
            return moe_mod.moe_forward(params["moe"], cfg,
                                       _norm(x, params, cfg, "ln2"))
    return _ffn(params, cfg, kind, x)[0], aux


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------
def block_forward(params: Params, cfg: ModelConfig, kind: str, x: jax.Array,
                  ctx: BlockCtx, cache: Optional[Params] = None
                  ) -> Tuple[jax.Array, jax.Array, Optional[Params]]:
    """Returns (x, aux_loss, new_cache)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in (DENSE, SHARED_ATTN, MOE) and cfg.mla is not None:
        self_cache = cache.get("self") if cache else None
        with jax.named_scope("mla"):
            att, new_self = mla_forward(
                params["attn"], cfg, _norm(x, params, cfg, "ln1"),
                positions=ctx.positions, cache=self_cache,
                cache_offset=ctx.cache_offset)
        x = constrain_residual(x + att)
        y, aux = _ffn_forward(params, cfg, kind, x, served=cache is not None)
        return x + y, aux, (None if cache is None else {"self": new_self})
    if kind in (DENSE, SHARED_ATTN, MOE):
        self_cache = cache.get("self") if cache else None
        with jax.named_scope("attention"):
            h = _norm(x, params, cfg, "ln1")
            att, new_self = attention_forward(
                params["attn"], cfg, h, positions=ctx.positions,
                causal=ctx.causal, window=ctx.window,
                prefix_len=ctx.prefix_len, use_rope=cfg.use_rope,
                cache=self_cache, cache_offset=ctx.cache_offset)
        # mid-block sequence-parallel point (active ShardingPolicy only):
        # the residual re-enters its (batch, "model", None) layout between
        # the attention and MLP sub-layers instead of drifting to whatever
        # layout the attention output propagated
        x = constrain_residual(x + att)
        new_cache: Optional[Params] = None
        if cache is not None:
            new_cache = dict(cache)
            new_cache["self"] = new_self
        if "cross" in params and ctx.enc_out is not None:
            hx = _norm(x, params, cfg, "lnx")
            catt, _ = attention_forward(params["cross"], cfg, hx,
                                        positions=ctx.positions,
                                        enc_out=ctx.enc_out, causal=False,
                                        use_rope=False)
            x = x + catt
            if cache is not None and "cross" in cache:
                new_cache["cross"] = build_cross_cache(
                    params["cross"], cfg, ctx.enc_out,
                    dtype=cache["cross"]["k"].dtype)
        y, aux = _ffn_forward(params, cfg, kind, x, served=cache is not None)
        return x + y, aux, new_cache
    if kind == MLSTM:
        if cache is not None:
            out, st = xlstm.mlstm_forward(params, cfg, x, state=cache,
                                          return_state=True)
            return out, aux, st
        return xlstm.mlstm_forward(params, cfg, x), aux, None
    if kind == SLSTM:
        if cache is not None:
            out, st = xlstm.slstm_forward(params, cfg, x, state=cache,
                                          return_state=True)
            return out, aux, st
        return xlstm.slstm_forward(params, cfg, x), aux, None
    if kind == MAMBA2:
        if cache is not None:
            out, st = m2.mamba2_forward(params, cfg, x, state=cache,
                                        return_state=True)
            return out, aux, st
        return m2.mamba2_forward(params, cfg, x), aux, None
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# single-token decode
# ---------------------------------------------------------------------------
def block_decode(params: Params, cfg: ModelConfig, kind: str, x: jax.Array,
                 cache: Params, ctx: BlockCtx,
                 layer: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Params, Optional[jax.Array]]:
    """Single-token decode; paged caches also accept a multi-token chunk
    (``x``: (B,C,d) with ``ctx.pos`` the chunk's first position and
    ``ctx.write_mask`` optionally (B,C)) — the chunked-prefill path.
    ``layer`` (dense attention caches only) makes ``cache`` the whole
    stacked segment cache, read and written in place at that layer.
    ``held`` is a moe block's (B,) count of each row's (token, expert)
    pairs that landed on held experts, and None for other blocks."""
    if kind in (DENSE, SHARED_ATTN, MOE) and cfg.mla is not None:
        with jax.named_scope("mla"):
            att, new_self = mla_decode(
                params["attn"], cfg, _norm(x, params, cfg, "ln1"),
                cache["self"], ctx.pos, write_mask=ctx.write_mask,
                layer=layer)
        x = constrain_residual(x + att)
        y, held = _ffn(params, cfg, kind, x)
        return x + y, {**cache, "self": new_self}, held
    if kind in (DENSE, SHARED_ATTN, MOE):
        with jax.named_scope("attention"):
            h = _norm(x, params, cfg, "ln1")
            if "kp" in cache["self"] and x.shape[1] > 1:
                att, new_self = chunk_attention_paged(
                    params["attn"], cfg, h, cache["self"], ctx.pos,
                    ctx.block_tbl, window=ctx.window, use_rope=cfg.use_rope,
                    write_mask=ctx.write_mask)
            elif "kp" in cache["self"]:
                att, new_self = decode_attention_paged(
                    params["attn"], cfg, h, cache["self"], ctx.pos,
                    ctx.block_tbl, window=ctx.window, use_rope=cfg.use_rope,
                    write_mask=ctx.write_mask)
            else:
                att, new_self = decode_attention(params["attn"], cfg, h,
                                                 cache["self"], ctx.pos,
                                                 window=ctx.window,
                                                 use_rope=cfg.use_rope,
                                                 write_mask=ctx.write_mask,
                                                 layer=layer)
        # same mid-block sequence-parallel point as block_forward (no-op
        # for S=1 decode; load-bearing for page-sized prefill chunks)
        x = constrain_residual(x + att)
        new_cache = dict(cache)
        new_cache["self"] = new_self
        if "cross" in params and "cross" in cache:
            hx = _norm(x, params, cfg, "lnx")
            catt, _ = decode_attention(params["cross"], cfg, hx,
                                       cache["cross"], ctx.pos, cross=True,
                                       use_rope=False, layer=layer)
            x = x + catt
        y, held = _ffn(params, cfg, kind, x)
        return x + y, new_cache, held
    if kind == MLSTM:
        return (*xlstm.mlstm_decode(params, cfg, x, cache), None)
    if kind == SLSTM:
        return (*xlstm.slstm_decode(params, cfg, x, cache), None)
    if kind == MAMBA2:
        return (*m2.mamba2_decode(params, cfg, x, cache), None)
    raise ValueError(kind)
