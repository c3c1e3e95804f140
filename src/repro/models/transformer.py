"""Stack assembler: composes per-layer blocks into full models with
early-exit heads and edge/cloud partitions (the paper's technique).

Layers are grouped into *segments* — maximal runs of identical
(kind, window) — and each segment's parameters are stacked along a leading
layer axis and driven by ``lax.scan`` (small HLO, production meshes compile
fast).  Segments are additionally cut at every early-exit layer, so the
paper's partition boundaries (``l_ee1``, ``l_ee2``) are always segment
boundaries and edge/cloud partitions are segment subsets.

Zamba2's shared attention block is represented as length-1 segments whose
parameters all alias ``params["shared"]``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DENSE, MOE, SHARED_ATTN, ModelConfig
from repro.launch import sharding as shardlib
from repro.models.blocks import (BlockCtx, block_decode, block_forward,
                                 init_block, init_block_cache,
                                 init_block_cache_paged)
from repro.models.common import (embed_init, layer_norm, rms_norm,
                                 sinusoidal_positions, split_rngs)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SegmentSpec:
    kind: str
    window: int
    start: int          # 0-based first layer index
    length: int
    shared: bool = False

    @property
    def end(self) -> int:          # exclusive
        return self.start + self.length


def build_segments(cfg: ModelConfig) -> Tuple[SegmentSpec, ...]:
    kinds = cfg.block_kinds()
    windows = cfg.layer_windows()
    cuts = {l for l in cfg.exit_layers}          # cut AFTER 1-based layer l
    segs: List[SegmentSpec] = []
    start = 0
    for i in range(1, cfg.n_layers + 1):
        boundary = (
            i == cfg.n_layers
            or kinds[i] != kinds[i - 1]
            or windows[i] != windows[i - 1]
            or i in cuts
            or kinds[i - 1] == SHARED_ATTN       # shared blocks stand alone
            or kinds[i] == SHARED_ATTN
        )
        if boundary:
            segs.append(SegmentSpec(kind=kinds[start], window=windows[start],
                                    start=start, length=i - start,
                                    shared=kinds[start] == SHARED_ATTN))
            start = i
    return tuple(segs)


def _stack(trees: Sequence[Params]) -> Params:
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def _init_stacked(rng: jax.Array, n: int, init_one) -> Params:
    """``n`` layers' params stacked on a leading axis, built by one vmapped
    init: under ``jax.jit`` only the stacked arrays are materialized (no
    per-layer copies to stack) and the layer body is traced once.  The
    values equal stacking ``init_one(r)`` over ``split(rng, n)``."""
    return jax.vmap(init_one)(jax.random.split(rng, n))


class Model:
    """Pure-function model wrapper; all methods take explicit params."""

    def __init__(self, cfg: ModelConfig, param_dtype=jnp.float32,
                 compute_dtype=None):
        self.cfg = cfg.validate()
        self.segments = build_segments(cfg)
        self.param_dtype = param_dtype
        self.compute_dtype = compute_dtype or param_dtype

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        cfg, dt = self.cfg, self.param_dtype
        n_rngs = len(self.segments) + 8
        rngs = split_rngs(rng, n_rngs)
        params: Params = {
            "embed": embed_init(rngs[0], cfg.vocab_size, cfg.d_model, dt),
        }
        seg_params = []
        for si, seg in enumerate(self.segments):
            if seg.shared:
                seg_params.append({})           # alias of params["shared"]
                continue
            seg_params.append(_init_stacked(
                rngs[1 + si], seg.length,
                lambda r, kind=seg.kind: init_block(r, cfg, kind, dt)))
        params["segments"] = tuple(seg_params)
        if any(s.shared for s in self.segments):
            params["shared"] = init_block(rngs[-6], cfg, SHARED_ATTN, dt,
                                          with_cross=False)
        params["final_norm"] = jnp.zeros((cfg.d_model,), dt) \
            if cfg.norm_type == "rms" else {
                "scale": jnp.ones((cfg.d_model,), dt),
                "bias": jnp.zeros((cfg.d_model,), dt)}
        if not cfg.tie_embeddings:
            params["lm_head"] = embed_init(rngs[-5], cfg.vocab_size,
                                           cfg.d_model, dt)
        # per-exit read-out norms (heads share the unembedding — EE-Tuning
        # style tied heads; see DESIGN.md)
        params["exit_norms"] = {
            str(l): jnp.zeros((cfg.d_model,), dt) for l in cfg.exit_layers}
        if cfg.is_encdec:
            params["encoder"] = {
                "layers": _init_stacked(
                    rngs[-4], cfg.encoder_layers,
                    lambda r: init_block(r, cfg, DENSE, dt,
                                         with_cross=False)),
                "norm": jnp.zeros((cfg.d_model,), dt)
                if cfg.norm_type == "rms" else {
                    "scale": jnp.ones((cfg.d_model,), dt),
                    "bias": jnp.zeros((cfg.d_model,), dt)},
            }
        if cfg.vision_tokens:
            params["vis_proj"] = (
                jax.random.normal(rngs[-3], (cfg.d_model, cfg.d_model))
                / jnp.sqrt(jnp.asarray(cfg.d_model, jnp.float32))).astype(dt)
        return params

    def param_specs(self) -> Params:
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        return jax.eval_shape(self.init, rng)

    # ------------------------------------------------------------------
    # norms / heads
    # ------------------------------------------------------------------
    def _final_norm(self, params: Params, x: jax.Array) -> jax.Array:
        if self.cfg.norm_type == "layernorm":
            fn = params["final_norm"]
            return layer_norm(x, fn["scale"], fn["bias"], self.cfg.norm_eps)
        return rms_norm(x, params["final_norm"], self.cfg.norm_eps)

    def unembed_weight(self, params: Params) -> jax.Array:
        """(V, d) read-out weight (tied or separate)."""
        return params.get("lm_head", params["embed"])

    def logits(self, params: Params, x: jax.Array) -> jax.Array:
        w = self.unembed_weight(params)
        out = jnp.einsum("bsd,vd->bsv", self._final_norm(params, x),
                         w.astype(x.dtype))
        return shardlib.constrain_logits(out)

    def exit_logits(self, params: Params, layer: int,
                    x: jax.Array) -> jax.Array:
        scale = params["exit_norms"][str(layer)]
        h = rms_norm(x, scale, self.cfg.norm_eps)
        w = self.unembed_weight(params)
        out = jnp.einsum("bsd,vd->bsv", h, w.astype(x.dtype))
        return shardlib.constrain_logits(out)

    # ------------------------------------------------------------------
    # embedding front-ends
    # ------------------------------------------------------------------
    def embed_tokens(self, params: Params, tokens: jax.Array,
                     pos_offset: Any = 0) -> jax.Array:
        """``pos_offset`` may be a scalar or a per-row (B,) position vector
        (continuous batching: rows decode at independent offsets)."""
        x = params["embed"][tokens].astype(self.compute_dtype)
        if not self.cfg.use_rope:
            s = tokens.shape[1]
            off = jnp.asarray(pos_offset)
            if off.ndim == 1:                      # (B,) -> (B,S) positions
                idx = off[:, None] + jnp.arange(s)
            else:
                idx = off + jnp.arange(s)
            x = x + sinusoidal_positions(idx, self.cfg.d_model).astype(x.dtype)
        return x

    def encode(self, params: Params, frames: jax.Array) -> jax.Array:
        """Whisper encoder over precomputed conv-frontend frames (B,Se,d)."""
        cfg = self.cfg
        x = frames.astype(self.compute_dtype)
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
        ctx = BlockCtx(positions=jnp.arange(x.shape[1]), causal=False,
                       dtype=self.compute_dtype)

        def body(h, p):
            h, _, _ = block_forward(p, cfg, DENSE, h, ctx)
            return shardlib.constrain_residual(h), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x,
                            params["encoder"]["layers"])
        if cfg.norm_type == "layernorm":
            n = params["encoder"]["norm"]
            return layer_norm(x, n["scale"], n["bias"], cfg.norm_eps)
        return rms_norm(x, params["encoder"]["norm"], cfg.norm_eps)

    def embed_inputs(self, params: Params, batch: Dict[str, jax.Array]
                     ) -> Tuple[jax.Array, BlockCtx]:
        """Training/prefill front-end: returns (x, ctx)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        enc_out = None
        prefix = 0
        if cfg.is_encdec:
            enc_out = self.encode(params, batch["frames"])
            x = self.embed_tokens(params, tokens)
        elif cfg.vision_tokens:
            vis = jnp.einsum("bpd,de->bpe",
                             batch["patches"].astype(self.compute_dtype),
                             params["vis_proj"].astype(self.compute_dtype))
            x = jnp.concatenate([vis, self.embed_tokens(params, tokens)],
                                axis=1)
            prefix = vis.shape[1]
        else:
            x = self.embed_tokens(params, tokens)
        ctx = BlockCtx(positions=jnp.arange(x.shape[1]), enc_out=enc_out,
                       prefix_len=prefix, dtype=self.compute_dtype)
        return x, ctx

    # ------------------------------------------------------------------
    # segment execution
    # ------------------------------------------------------------------
    def _seg_params(self, params: Params, si: int) -> Params:
        seg = self.segments[si]
        return params["shared"] if seg.shared else params["segments"][si]

    def run_segments(self, params: Params, x: jax.Array, ctx: BlockCtx,
                     seg_indices: Sequence[int],
                     caches: Optional[Dict[int, Params]] = None,
                     collect_exits: bool = True, remat: bool = False):
        """Full-seq execution of the given segments.

        Returns (x, exit_hiddens {1-based layer: hidden}, aux, new_caches)."""
        cfg = self.cfg
        exit_set = set(cfg.exit_layers) if collect_exits else set()
        exit_hiddens: Dict[int, jax.Array] = {}
        aux_total = jnp.zeros((), jnp.float32)
        new_caches: Dict[int, Params] = {}
        x = shardlib.constrain_residual(x)
        for si in seg_indices:
            seg = self.segments[si]
            sctx = dataclasses.replace(ctx, window=seg.window)
            p = self._seg_params(params, si)
            cache = caches.get(si) if caches is not None else None
            if seg.shared:
                x, aux, nc = block_forward(p, cfg, seg.kind, x, sctx,
                                           cache=cache)
                x = shardlib.constrain_residual(x)
                aux_total = aux_total + aux
            else:
                def body(h, inp):
                    lp, lc = inp
                    h, aux, nc = block_forward(lp, cfg, seg.kind, h, sctx,
                                               cache=lc)
                    return shardlib.constrain_residual(h), (aux, nc)

                if remat:
                    body = jax.checkpoint(body)
                x, (auxs, nc) = jax.lax.scan(body, x, (p, cache))
                aux_total = aux_total + jnp.sum(auxs)
            if cache is not None:
                new_caches[si] = nc
            if seg.end in exit_set:
                exit_hiddens[seg.end] = x
        return x, exit_hiddens, aux_total, new_caches

    def decode_segments(self, params: Params, x: jax.Array, ctx: BlockCtx,
                        seg_indices: Sequence[int], caches: Dict[int, Params],
                        collect_exits: bool = True):
        """Single-token execution.  Returns (x, exit_hiddens, new_caches)."""
        cfg = self.cfg
        exit_set = set(cfg.exit_layers) if collect_exits else set()
        exit_hiddens: Dict[int, jax.Array] = {}
        new_caches: Dict[int, Params] = {}
        # sequence-parallel constraint under an active ShardingPolicy:
        # a no-op for single-token decode (S=1 can't split), load-bearing
        # for the chunked-prefill path that decodes page-sized chunks
        x = shardlib.constrain_residual(x)
        for si in seg_indices:
            seg = self.segments[si]
            sctx = dataclasses.replace(ctx, window=seg.window)
            p = self._seg_params(params, si)
            cache = caches[si]
            if seg.shared:
                x, nc = block_decode(p, cfg, seg.kind, x, cache, sctx)
                x = shardlib.constrain_residual(x)
            else:
                def body(h, inp):
                    lp, lc = inp
                    h, nc = block_decode(lp, cfg, seg.kind, h, lc, sctx)
                    return shardlib.constrain_residual(h), nc

                x, nc = jax.lax.scan(body, x, (p, cache))
            new_caches[si] = nc
            if seg.end in exit_set:
                exit_hiddens[seg.end] = x
        return x, exit_hiddens, new_caches

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int,
                   seg_indices: Optional[Sequence[int]] = None,
                   dtype=None) -> Dict[int, Params]:
        cfg = self.cfg
        dt = dtype or self.compute_dtype
        seg_indices = (range(len(self.segments)) if seg_indices is None
                       else seg_indices)
        caches: Dict[int, Params] = {}
        for si in seg_indices:
            seg = self.segments[si]
            per_layer = [init_block_cache(cfg, seg.kind, batch, max_seq,
                                          seg.window, dt)
                         for _ in range(seg.length)]
            caches[si] = _stack(per_layer) if not seg.shared else per_layer[0]
        return caches

    def init_paged_cache(self, batch: int, num_pages: int, page_size: int,
                         seg_indices: Optional[Sequence[int]] = None,
                         dtype=None, kv_dtype: str = "float32"
                         ) -> Dict[int, Params]:
        """Block-paged caches: self-attention K/V is pooled across rows in
        ``num_pages`` pages of ``page_size`` tokens (plus a trash page) and
        addressed through a per-row block table passed to ``decode_step``;
        cross-attention / recurrent state stays dense per row.
        ``kv_dtype="int8"`` stores pages quantized with per-row scales."""
        cfg = self.cfg
        dt = dtype or self.compute_dtype
        seg_indices = (range(len(self.segments)) if seg_indices is None
                       else seg_indices)
        caches: Dict[int, Params] = {}
        for si in seg_indices:
            seg = self.segments[si]
            per_layer = [init_block_cache_paged(cfg, seg.kind, batch,
                                                num_pages, page_size, dt,
                                                kv_dtype=kv_dtype)
                         for _ in range(seg.length)]
            caches[si] = _stack(per_layer) if not seg.shared else per_layer[0]
        return caches

    def attention_only(self, seg_indices: Optional[Sequence[int]] = None
                       ) -> bool:
        """True when every segment is attention-style (KV-cached).  Such
        partitions tolerate right-padded prefill: pad positions are causally
        invisible to real tokens and their cache entries can be invalidated
        afterwards.  Recurrent (SSM/xLSTM) segments cannot — their state
        advances through pad tokens irreversibly."""
        seg_indices = (range(len(self.segments)) if seg_indices is None
                       else seg_indices)
        return all(self.segments[si].kind in (DENSE, SHARED_ATTN, MOE)
                   for si in seg_indices)

    def invalidate_cache_after(self, caches: Dict[int, Params],
                               true_len: Any) -> Dict[int, Params]:
        """Mark self-attention cache entries at ring slots >= true_len as
        invalid (pos = -1).  Used after a right-padded prefill so the pad
        positions never participate in decode attention; decode overwrites
        each slot before reading it, so the row stays correct as generation
        advances past ``true_len``."""
        def fix(c: Params) -> Params:
            if not isinstance(c, dict):
                return c
            if "pos" in c and "k" in c:            # self-attn ring cache
                s = c["pos"].shape[-1]
                keep = jnp.arange(s) < true_len
                return {**c, "pos": jnp.where(keep, c["pos"], -1)}
            return {k: (fix(v) if k != "cross" else v) for k, v in c.items()}
        return {si: fix(c) for si, c in caches.items()}

    def cache_specs(self, batch: int, max_seq: int,
                    seg_indices: Optional[Sequence[int]] = None,
                    dtype=None):
        return jax.eval_shape(
            functools.partial(self.init_cache, batch, max_seq, seg_indices,
                              dtype))

    # ------------------------------------------------------------------
    # public entry points
    # ------------------------------------------------------------------
    def all_segments(self) -> Tuple[int, ...]:
        return tuple(range(len(self.segments)))

    def edge_segments(self, l_ee2: Optional[int] = None) -> Tuple[int, ...]:
        l_ee2 = l_ee2 or (self.cfg.exit_layers[-1] if self.cfg.exit_layers
                          else self.cfg.n_layers)
        return tuple(i for i, s in enumerate(self.segments) if s.end <= l_ee2)

    def cloud_segments(self, l_ee1: Optional[int] = None) -> Tuple[int, ...]:
        l_ee1 = l_ee1 or (self.cfg.exit_layers[0] if self.cfg.exit_layers
                          else 0)
        return tuple(i for i, s in enumerate(self.segments)
                     if s.start >= l_ee1)

    def forward_train(self, params: Params, batch: Dict[str, jax.Array]
                      ) -> Dict[str, Any]:
        """Full forward with all exit logits (multi-exit training)."""
        x, ctx = self.embed_inputs(params, batch)
        x, exit_hiddens, aux, _ = self.run_segments(
            params, x, ctx, self.all_segments(), remat=True)
        out = {
            "logits": self.logits(params, x),
            "exit_logits": {l: self.exit_logits(params, l, h)
                            for l, h in exit_hiddens.items()},
            "aux_loss": aux,
            "prefix_len": ctx.prefix_len,
        }
        return out

    def forward_train_hiddens(self, params: Params,
                              batch: Dict[str, jax.Array]) -> Dict[str, Any]:
        """Training forward that stops at hidden states (no unembedding) —
        pairs with ``loss.multi_exit_loss_fused`` (chunked fused CE)."""
        x, ctx = self.embed_inputs(params, batch)
        x, exit_hiddens, aux, _ = self.run_segments(
            params, x, ctx, self.all_segments(), remat=True)
        return {"final": x, "exits": exit_hiddens, "aux_loss": aux,
                "prefix_len": ctx.prefix_len}

    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                caches: Dict[int, Params],
                seg_indices: Optional[Sequence[int]] = None):
        """Full-sequence pass that fills caches.  Returns
        (last-position hidden, exit_hiddens, new_caches, ctx-extras)."""
        seg_indices = seg_indices or self.all_segments()
        x, ctx = self.embed_inputs(params, batch)
        x, exit_hiddens, _, new_caches = self.run_segments(
            params, x, ctx, seg_indices, caches=caches)
        return x, exit_hiddens, new_caches, ctx

    def decode_step(self, params: Params, token: jax.Array,
                    caches: Dict[int, Params], pos: jax.Array,
                    seg_indices: Optional[Sequence[int]] = None,
                    collect_exits: bool = True,
                    block_tbl: Optional[jax.Array] = None,
                    write_mask: Optional[jax.Array] = None):
        """token: (B,1) -> (final hidden (B,1,d), exit_hiddens, caches).
        ``pos`` is a scalar or a per-row (B,) position vector.  Paged caches
        additionally need ``block_tbl`` (B, max_logical); ``write_mask``
        (B,) bool redirects masked rows' KV writes to the trash page."""
        seg_indices = seg_indices or self.all_segments()
        x = self.embed_tokens(params, token, pos_offset=pos)
        ctx = BlockCtx(pos=pos, block_tbl=block_tbl, write_mask=write_mask,
                       dtype=self.compute_dtype)
        return self.decode_segments(params, x, ctx, seg_indices, caches,
                                    collect_exits=collect_exits)

    def decode_from_hidden(self, params: Params, hidden: jax.Array,
                           caches: Dict[int, Params], pos: jax.Array,
                           seg_indices: Sequence[int],
                           block_tbl: Optional[jax.Array] = None,
                           write_mask: Optional[jax.Array] = None):
        """Cloud-partition decode: continue from an uploaded hidden state."""
        ctx = BlockCtx(pos=pos, block_tbl=block_tbl, write_mask=write_mask,
                       dtype=self.compute_dtype)
        return self.decode_segments(params, hidden, ctx, seg_indices, caches,
                                    collect_exits=False)
