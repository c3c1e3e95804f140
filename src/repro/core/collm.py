"""CE-CoLLM co-inference steps (paper §4.4, Algorithm 1).

Building blocks:

  * ``edge_step``        — edge partition (layers 1..l_ee2) with exits at
                           l_ee1/l_ee2; emits the quantized l_ee1 upload.
  * ``cloud_step``       — cloud partition (layers l_ee1+1..L) continuing
                           from an uploaded hidden state; supports lazy KV
                           *backfill* of early-exited tokens (see DESIGN.md).
  * ``standalone_step``  — paper's low-latency edge standalone mode (last
                           exit is the output head; no threshold).
  * ``full_step``        — undivided model (cloud-deployment baseline).
  * ``fused_step``       — single-graph adaptive step with per-row upload
                           rings and ``lax.cond``-gated cloud compute: the
                           TPU-native expression of "request cloud only on
                           low confidence".  θ=1.0 reproduces the full model
                           exactly (unit-tested invariant).

All decode steps accept ``pos`` as a scalar or a per-row (B,) vector, and
cloud compute is gated per row (``cloud_step_masked`` writes cache updates
only for below-θ rows) — the primitives behind the continuous-batching
scheduler in ``repro.serving.engine``.  Every step also takes an optional
``block_tbl`` for the block-paged KV layout
(``CollmConfig.kv_layout="paged"``): K/V then lives in a page pool shared
across rows and masked rows write to the trash page instead of being
merged (see docs/kv_paging.md).

Host-level multi-client serving (with the ContentManager and the network
simulator) lives in ``repro.serving.engine``; this module is pure JAX.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.exits import ExitDecision, evaluate_exit, first_confident_exit
from repro.core.transport import dequantize, quantize
from repro.models.attention import is_ring
from repro.models.transformer import Model

Params = Dict[str, Any]
Pytree = Any


@dataclasses.dataclass(frozen=True)
class CollmConfig:
    theta: float = 0.8
    wire_format: str = "float16"      # paper: float16; beyond-paper: int8
    max_pending: int = 4              # upload ring size (fused mode)
    # Latency hiding (paper §4.4): the cloud computes for EVERY row and the
    # edge commits a *provisional* exit-head token without waiting — the
    # fused step gates cloud compute on all rows, and the batched engine
    # reconciles the provisional token against the cloud reply when it
    # arrives (keep on match, rewind-and-replace on mismatch, keep on
    # deadline miss).  Requires greedy decoding + attention-only models in
    # the batched path (rewind re-decodes positions).
    speculative: bool = False
    # Draft length of the speculative path: a below-θ row keeps committing
    # up to ``spec_k`` provisional exit tokens into one *draft*, then ships
    # the whole draft as a single verification request; the cloud scores
    # all k positions in ONE masked ring pass and the engine accepts the
    # longest agreeing prefix (rewinding only the rejected suffix).
    # spec_k=1 is exactly the classic per-token speculative path.
    spec_k: int = 1
    # Paper-faithful: the content manager RELEASES hidden states of tokens
    # that exited early, so the cloud KV cache has gaps at those positions
    # (this is why Table 2 ROUGE-L < 1 for theta < 1).  backfill=True is the
    # beyond-paper fix: ringed uploads are run through the cloud partition on
    # the next request, keeping cloud KV exact at modest extra cloud compute.
    backfill: bool = False
    # KV layout of the batched serving engine: "dense" pins each slot to a
    # max_seq ring (memory B x max_seq); "paged" shares a block-paged pool
    # across slots (memory num_pages x page_size; see docs/kv_paging.md).
    # Release-mode gaps survive either way: a gapped position is simply a
    # page slot whose pos marker was never written.
    kv_layout: str = "dense"
    page_size: int = 16               # tokens per KV page (paged layout)
    # Storage dtype of the paged KV pool.  "int8" quantizes K/V per
    # page-row on write (one absmax scale per (token, kv_head) row, the
    # transport quantizer's scaling) and dequantizes at gather — in-kernel
    # for the Pallas paged flash-decode, so int8 pages cut decode HBM
    # traffic instead of being expanded in XLA first.  Swap snapshots and
    # admission scatters carry the quantized pages + scales verbatim, so
    # preemption swap bytes shrink by the same factor.  float32 stays
    # bit-identical to the dense layout; int8 trades bounded quantization
    # error (see docs/kv_paging.md §Quantized pages) for ~3.4x less KV
    # traffic.  Only meaningful with kv_layout="paged".
    kv_dtype: str = "float32"         # "float32" | "int8"
    # Paged-KV preemption (docs/kv_paging.md §Preemption).  "off" keeps the
    # conservative worst-case admission check (a stream admitted under
    # backpressure can always finish, but the pool is sized for worst
    # cases that rarely materialize).  Otherwise admission is optimistic —
    # only the prompt's pages need to fit — and a decode-time OutOfPages
    # preempts a victim stream: its stream state is checkpointed, its
    # pages freed, and it resumes later by "recompute" (re-prefill the KV
    # from its token prefix) or "swap" (pages round-trip through a
    # host-side SwapPool).  Preemption is invisible in output space:
    # greedy token streams are identical to an un-preempted run.
    preemption: str = "off"           # "off" | "recompute" | "swap"
    preempt_policy: str = "youngest"  # "youngest" | "fewest-pages" | "lru"
    # Chunked prefill admission (docs/serving.md): instead of one
    # monolithic padded prefill at admission, the prompt is prefilled in
    # page-sized chunks interleaved with decode ticks (a per-slot
    # ``prefill_remaining`` state machine), so a long prompt stops
    # monopolizing an engine tick.  Requires kv_layout="paged" and an
    # attention-only decoder-only model (the chunk step rides the paged
    # decode write path).  Chunked runs are token-identical to each other
    # but may differ from the monolithic path in float ulps (different
    # reduction order) — comparisons should hold the admission mode fixed.
    chunked_prefill: bool = False
    # Radix prefix sharing + copy-on-write (docs/kv_paging.md §Prefix
    # sharing): the PagePool keeps a trie of page-aligned prompt token
    # chunks so streams whose prompts share a prefix map the SAME physical
    # pages (refcounted); the first divergent write to a shared page
    # triggers a copy-on-write split.  Identical whole prompts additionally
    # cache their greedy first token, skipping prefill entirely.  Requires
    # chunked_prefill=True (suffix-only compute) and greedy sampling.
    prefix_share: bool = False
    # Cloud execution mesh (docs/sharding.md): a (data, model) device grid,
    # e.g. (2, 4), the cloud partition's jitted steps compile against —
    # params placed via role-based NamedShardings, the pooled batch-major
    # cloud KV via cache_shardings, residual/logits constraints baked into
    # the cloud traces.  None (the default) keeps the single-device path:
    # no mesh, no policy, plain jax.jit.  Needs prod(cloud_mesh) visible
    # devices (locally: XLA_FLAGS=--xla_force_host_platform_device_count=N).
    cloud_mesh: Optional[Tuple[int, int]] = None


class EdgeStepOut(NamedTuple):
    decisions: Dict[int, ExitDecision]
    token: jax.Array            # (B,) first-confident-exit token
    exited: jax.Array           # (B,) bool
    upload: Dict[str, jax.Array]   # quantized l_ee1 hidden (wire packet)
    caches: Dict[int, Pytree]
    # (B,) int32: each row's (token, expert) pairs that landed on held
    # experts over the edge's moe layers; None for a model without experts
    held: Optional[jax.Array] = None


def _where_rows(pred: jax.Array, a: jax.Array, b: jax.Array,
                axis: int) -> jax.Array:
    """Row-wise select: pred is (B,) and ``axis`` is the batch axis of a/b."""
    shape = [1] * a.ndim
    shape[axis] = pred.shape[0]
    return jnp.where(pred.reshape(shape), a, b)


class CoLLM:
    """Binds a Model to the paper's partition + gating machinery."""

    def __init__(self, model: Model, ccfg: CollmConfig = CollmConfig()):
        cfg = model.cfg
        if len(cfg.exit_layers) < 1:
            raise ValueError("CE-CoLLM requires at least one exit layer")
        if ccfg.kv_dtype not in ("float32", "int8"):
            raise ValueError(f"kv_dtype must be 'float32' or 'int8', "
                             f"got {ccfg.kv_dtype!r}")
        if ccfg.kv_dtype == "int8" and ccfg.kv_layout != "paged":
            raise ValueError('kv_dtype="int8" requires kv_layout="paged" '
                             "(dense rings stay full precision)")
        if ccfg.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {ccfg.spec_k}")
        if ccfg.spec_k > 1 and not ccfg.speculative:
            raise ValueError("spec_k > 1 requires speculative=True "
                             "(drafting generalizes the speculative path)")
        if cfg.mla is not None and ccfg.kv_layout != "dense":
            raise ValueError(f"{cfg.name}: latent attention (MLA) keeps its "
                             f"latent cache in the dense layout; "
                             f'kv_layout={ccfg.kv_layout!r} is not supported')
        if ccfg.chunked_prefill and ccfg.kv_layout != "paged":
            raise ValueError('chunked_prefill=True requires kv_layout='
                             '"paged" (chunks ride the paged write path)')
        if ccfg.prefix_share and not ccfg.chunked_prefill:
            raise ValueError("prefix_share=True requires chunked_prefill="
                             "True (suffix-only compute needs chunk-"
                             "granular admission)")
        if ccfg.cloud_mesh is not None:
            cm_ = tuple(ccfg.cloud_mesh)
            if len(cm_) != 2 or any(int(a) < 1 for a in cm_):
                raise ValueError(f"cloud_mesh must be a (data, model) pair "
                                 f"of positive ints, got "
                                 f"{ccfg.cloud_mesh!r}")
        self.model = model
        self.ccfg = ccfg
        self.l_ee1 = cfg.exit_layers[0]
        self.l_ee2 = cfg.exit_layers[-1]
        self.edge_segs = model.edge_segments(self.l_ee2)
        self.cloud_segs = model.cloud_segments(self.l_ee1)
        # segments strictly before l_ee1 (their output is the upload point)
        self.pre_segs = tuple(i for i, s in enumerate(model.segments)
                              if s.end <= self.l_ee1)

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def init_edge_cache(self, batch: int, max_seq: int, dtype=None):
        return self.model.init_cache(batch, max_seq, self.edge_segs,
                                     dtype=dtype)

    def init_cloud_cache(self, batch: int, max_seq: int, dtype=None):
        return self.model.init_cache(batch, max_seq, self.cloud_segs,
                                     dtype=dtype)

    def init_edge_cache_paged(self, batch: int, num_pages: int,
                              page_size: int, dtype=None):
        return self.model.init_paged_cache(batch, num_pages, page_size,
                                           self.edge_segs, dtype=dtype,
                                           kv_dtype=self.ccfg.kv_dtype)

    def init_cloud_cache_paged(self, batch: int, num_pages: int,
                               page_size: int, dtype=None):
        return self.model.init_paged_cache(batch, num_pages, page_size,
                                           self.cloud_segs, dtype=dtype,
                                           kv_dtype=self.ccfg.kv_dtype)

    # ------------------------------------------------------------------
    # prefill (prompt processing)
    # ------------------------------------------------------------------
    def edge_prefill(self, params: Params, batch: Dict[str, jax.Array],
                     caches: Dict[int, Pytree]):
        """Edge processes the prompt; returns (exit decisions at last pos,
        l_ee1 hidden sequence for upload, caches)."""
        x, exit_h, new_caches, ctx = self.model.prefill(
            params, batch, caches, self.edge_segs)
        h1_seq = exit_h[self.l_ee1]
        decisions = {l: evaluate_exit(
            self.model.exit_logits(params, l, h[:, -1:]))
            for l, h in exit_h.items()}
        return decisions, h1_seq, new_caches

    def cloud_prefill(self, params: Params, h1_seq: jax.Array,
                      caches: Dict[int, Pytree],
                      enc_out: Optional[jax.Array] = None):
        """Cloud builds its KV over the uploaded prompt hidden states."""
        from repro.models.blocks import BlockCtx
        ctx = BlockCtx(positions=jnp.arange(h1_seq.shape[1]), enc_out=enc_out,
                       dtype=self.model.compute_dtype)
        x, _, _, new_caches = self.model.run_segments(
            params, h1_seq, ctx, self.cloud_segs, caches=caches,
            collect_exits=False)
        logits = self.model.logits(params, x[:, -1:])
        return logits, new_caches

    # ------------------------------------------------------------------
    # right-padded prefill (shape-stable admission for the batch scheduler)
    # ------------------------------------------------------------------
    def edge_prefill_padded(self, params: Params, tokens: jax.Array,
                            true_len: jax.Array, caches: Dict[int, Pytree]):
        """Edge prefill over a right-padded prompt (tokens: (1, Lb)).

        Pad positions are causally invisible to real tokens, so the real
        activations are bit-identical to an unpadded prefill; pad cache slots
        are invalidated afterwards.  Exit decisions are evaluated at the TRUE
        last position.  Compiles once per length bucket, never per prompt."""
        x, exit_h, new_caches, _ = self.model.prefill(
            params, {"tokens": tokens}, caches, self.edge_segs)
        last = jnp.asarray(true_len, jnp.int32) - 1
        with jax.named_scope("exit_heads"):
            decisions = {l: evaluate_exit(self.model.exit_logits(
                params, l, jax.lax.dynamic_slice_in_dim(h, last, 1, axis=1)))
                for l, h in exit_h.items()}
        new_caches = self.model.invalidate_cache_after(new_caches, true_len)
        return decisions, exit_h[self.l_ee1], new_caches

    def cloud_prefill_padded(self, params: Params, h1_seq: jax.Array,
                             true_len: jax.Array, caches: Dict[int, Pytree],
                             enc_out: Optional[jax.Array] = None):
        """Cloud prefill over a right-padded prompt upload; logits taken at
        the true last position, pad cache slots invalidated."""
        from repro.models.blocks import BlockCtx
        ctx = BlockCtx(positions=jnp.arange(h1_seq.shape[1]), enc_out=enc_out,
                       dtype=self.model.compute_dtype)
        x, _, _, new_caches = self.model.run_segments(
            params, h1_seq, ctx, self.cloud_segs, caches=caches,
            collect_exits=False)
        last = jnp.asarray(true_len, jnp.int32) - 1
        with jax.named_scope("output_head"):
            logits = self.model.logits(
                params, jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1))
        new_caches = self.model.invalidate_cache_after(new_caches, true_len)
        return logits, new_caches

    def full_prefill_padded(self, params: Params, tokens: jax.Array,
                            true_len: jax.Array, caches: Dict[int, Pytree]):
        """Undivided-model prefill over a right-padded prompt (cloud
        baseline rows of the batch scheduler)."""
        x, _, new_caches, _ = self.model.prefill(
            params, {"tokens": tokens}, caches)
        last = jnp.asarray(true_len, jnp.int32) - 1
        logits = self.model.logits(
            params, jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1))
        new_caches = self.model.invalidate_cache_after(new_caches, true_len)
        return logits, new_caches

    # ------------------------------------------------------------------
    # chunked prefill (page-sized chunks interleaved with decode ticks)
    # ------------------------------------------------------------------
    def edge_prefill_chunk(self, params: Params, tokens: jax.Array,
                           pos0: jax.Array, chunk_len: jax.Array,
                           caches: Dict[int, Pytree],
                           block_tbl: jax.Array):
        """Edge prefill of ONE page-sized prompt chunk (tokens: (1, C),
        right-padded to the page size; ``pos0`` is the chunk's first
        absolute position, ``chunk_len`` its true token count).

        Rides the paged decode write path (``chunk_attention_paged``): KV
        rows land in the pages the block table maps, pad positions write to
        the trash page via the per-token write mask.  Shapes are fixed at
        (1, page_size) so every chunk of every stream compiles once and —
        crucially for prefix sharing — computes bit-identical page content
        for identical (tokens, pos0).  Returns (decisions at the chunk's
        true last position, l_ee1 hidden chunk for upload, caches)."""
        c = tokens.shape[1]
        wm = (jnp.arange(c, dtype=jnp.int32)[None, :]
              < jnp.asarray(chunk_len, jnp.int32))
        x, exit_h, new_caches, _ = self.model.decode_step(
            params, tokens, caches, pos0, self.edge_segs,
            block_tbl=block_tbl, write_mask=wm)
        last = jnp.asarray(chunk_len, jnp.int32) - 1
        decisions = {l: evaluate_exit(self.model.exit_logits(
            params, l, jax.lax.dynamic_slice_in_dim(h, last, 1, axis=1)))
            for l, h in exit_h.items()}
        return decisions, exit_h[self.l_ee1], new_caches

    def cloud_prefill_chunk(self, params: Params, h1: jax.Array,
                            pos0: jax.Array, chunk_len: jax.Array,
                            caches: Dict[int, Pytree],
                            block_tbl: jax.Array):
        """Cloud prefill of one uploaded hidden chunk (h1: (1, C, d));
        returns (logits at the chunk's true last position, caches).  The
        logits only matter for the prompt's final chunk — earlier chunks
        call this purely for the KV side effect."""
        c = h1.shape[1]
        wm = (jnp.arange(c, dtype=jnp.int32)[None, :]
              < jnp.asarray(chunk_len, jnp.int32))
        x, _, new_caches, _ = self.model.decode_from_hidden(
            params, h1, caches, pos0, self.cloud_segs,
            block_tbl=block_tbl, write_mask=wm)
        last = jnp.asarray(chunk_len, jnp.int32) - 1
        logits = self.model.logits(
            params, jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1))
        return logits[:, 0], new_caches

    # ------------------------------------------------------------------
    # decode steps
    # ------------------------------------------------------------------
    def edge_step(self, params: Params, token: jax.Array,
                  caches: Dict[int, Pytree], pos: jax.Array,
                  block_tbl: Optional[jax.Array] = None) -> EdgeStepOut:
        x, exit_h, new_caches, held = self.model.decode_step(
            params, token, caches, pos, self.edge_segs, block_tbl=block_tbl)
        decisions, tok, exited = self._exit_heads(params, exit_h)
        upload = quantize(exit_h[self.l_ee1], self.ccfg.wire_format)
        return EdgeStepOut(decisions, tok, exited, upload, new_caches, held)

    def _exit_heads(self, params: Params, exit_h: Dict[int, jax.Array]):
        """Every exit's decision over its hidden state, and the first
        confident one per row: (decisions, token, exited)."""
        with jax.named_scope("exit_heads"):
            decisions = {l: evaluate_exit(self.model.exit_logits(params, l, h))
                         for l, h in exit_h.items()}
            tok, exited, _ = first_confident_exit(decisions, self.ccfg.theta)
        return decisions, tok, exited

    def fused_exit_upload(self, params: Params, hidden: jax.Array, *,
                          interpret: bool = False,
                          use_kernel: bool = True):
        """TPU hot path for the l_ee1 exit + upload: ONE Pallas launch
        (``kernels/exit_quant``) over the hidden tile computes the exit
        decision (confidence + argmax token) AND the int8 wire packet,
        replacing the two-launch exit_logits -> evaluate_exit -> quantize
        sequence of ``edge_step`` when ``wire_format="int8"``.

        ``hidden``: (B, 1, d) or (B, d).  Returns (confidence (B,),
        token (B,), packet) where ``packet`` has exactly the layout of
        ``transport.quantize(hidden, "int8")`` — the cloud opens it with
        the unmodified ``dequantize``."""
        from repro.kernels.exit_quant.ops import exit_quant
        shape = hidden.shape
        h2 = hidden.reshape(shape[0], shape[-1])
        conf, tok, _, q, s = exit_quant(
            h2, self.model.unembed_weight(params),
            params["exit_norms"][str(self.l_ee1)],
            eps=self.model.cfg.norm_eps, interpret=interpret,
            use_kernel=use_kernel)
        return conf, tok, {"data": q.reshape(shape),
                           "scale": s.reshape(shape[:-1] + (1,))}

    def cloud_step(self, params: Params, upload: Dict[str, jax.Array],
                   caches: Dict[int, Pytree], pos: jax.Array,
                   block_tbl: Optional[jax.Array] = None,
                   write_mask: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Dict[int, Pytree]]:
        """One uploaded hidden -> final logits (paper Algorithm 1 lines 29-37).
        Also used for KV backfill of early-exited positions.  ``pos`` may be
        a scalar or a per-row (B,) position vector."""
        hidden = dequantize(upload, self.model.compute_dtype)
        x, _, new_caches, _ = self.model.decode_from_hidden(
            params, hidden, caches, pos, self.cloud_segs,
            block_tbl=block_tbl, write_mask=write_mask)
        with jax.named_scope("output_head"):
            logits = self.model.logits(params, x)[:, 0]
        return logits, new_caches

    def _caches_where_rows(self, mask: jax.Array, new: Dict[int, Pytree],
                           old: Dict[int, Pytree]) -> Dict[int, Pytree]:
        """Per-row cache merge: rows with mask=True take ``new``, others keep
        ``old``.  Stacked segments carry batch at axis 1, shared at axis 0.
        Attention K/V nodes are passed through untouched: the step dropped
        the masked rows' writes (dense rings) or sent them to the trash page
        (paged), so ``new`` is correct for every row without a merge.  Only
        recurrent state, which every row advances, is merged."""
        def merge(a: Pytree, b: Pytree, axis: int) -> Pytree:
            if isinstance(a, dict):
                if "kp" in a or is_ring(a):
                    return a
                return {k: merge(a[k], b[k], axis) for k in a}
            return _where_rows(mask, a, b, axis)

        out: Dict[int, Pytree] = {}
        for si in new:
            axis = 0 if self.model.segments[si].shared else 1
            out[si] = merge(new[si], old[si], axis)
        return out

    def cloud_step_masked(self, params: Params, upload: Dict[str, jax.Array],
                          caches: Dict[int, Pytree], pos: jax.Array,
                          mask: jax.Array,
                          block_tbl: Optional[jax.Array] = None
                          ) -> Tuple[jax.Array, Dict[int, Pytree]]:
        """Batched cloud step serving only the below-θ rows: rows with
        mask=False keep their caches untouched (their upload was not
        consumed), preserving the per-client release/gap semantics of the
        sequential path.  One call serves every needy row of a step.  The
        mask becomes the KV ``write_mask``: dense rings drop the masked
        rows' writes at the scatter (and the layer loop updates each
        stacked cache in place), paged caches send them to the trash
        page, and only recurrent state is merged.  Jitted through
        ``mesh_exec.jit_step`` the step donates ``caches``: take the
        returned caches and never reuse the ones passed in."""
        logits, new_caches = self.cloud_step(params, upload, caches, pos,
                                             block_tbl=block_tbl,
                                             write_mask=mask)
        return logits, self._caches_where_rows(mask, new_caches, caches)

    def edge_step_masked(self, params: Params, token: jax.Array,
                         caches: Dict[int, Pytree], pos: jax.Array,
                         run_mask: jax.Array,
                         block_tbl: Optional[jax.Array] = None) -> EdgeStepOut:
        """Batched edge step that leaves masked-out rows' caches untouched.

        The async scheduler keeps ticking the pool while some rows are
        stalled on an in-flight cloud reply; those rows flow through the
        batched graph as placeholders.  For attention caches a placeholder
        write is harmless (the slot is overwritten before it is read when
        the row resumes), but recurrent state would advance irreversibly —
        so rows with ``run_mask=False`` keep their caches bit-for-bit
        (through the KV ``write_mask`` dense rings drop their attention
        writes and paged ones send them to the trash page; recurrent
        state is merged per row)."""
        x, exit_h, new_caches, held = self.model.decode_step(
            params, token, caches, pos, self.edge_segs, block_tbl=block_tbl,
            write_mask=run_mask)
        decisions, tok, exited = self._exit_heads(params, exit_h)
        upload = quantize(exit_h[self.l_ee1], self.ccfg.wire_format)
        return EdgeStepOut(decisions, tok, exited, upload,
                           self._caches_where_rows(run_mask, new_caches,
                                                   caches), held)

    def invalidate_rows_after(self, caches: Dict[int, Pytree],
                              cut: jax.Array,
                              block_tbl: Optional[jax.Array] = None
                              ) -> Dict[int, Pytree]:
        """Per-row KV rollback: mark each row's self-attention entries at
        positions >= ``cut[row]`` invalid (pos = -1).

        The speculative decode path rewinds a row when the cloud reply
        disagrees with its provisionally-committed token; the row's *cloud*
        KV written for discarded positions must disappear (a position the
        re-decoded stream never cloud-serves again would otherwise read
        stale K/V — in blocking mode it would be a release-semantics gap).
        Edge KV needs no repair: decode overwrites a slot before reading
        it.  Dense rings match on the stored pos marker (wrap-safe); paged
        nodes scatter a per-page threshold through the block table.  Rows
        that are not being rewound pass ``cut = INT32_MAX``.  Cross-attn
        caches and recurrent state are untouched (speculation is gated to
        attention-only models)."""
        cut = jnp.asarray(cut, jnp.int32)
        big = jnp.iinfo(jnp.int32).max

        def fix_dense(c: Pytree) -> Pytree:
            p = c["pos"]
            shape = [1] * p.ndim
            shape[p.ndim - 2] = cut.shape[0]       # batch axis of the ring
            return {**c, "pos": jnp.where(p >= cut.reshape(shape), -1, p)}

        def fix_paged(c: Pytree) -> Pytree:
            def one(pos_arr):
                thr = jnp.full((pos_arr.shape[0],), big, jnp.int32)
                dest = jnp.where(block_tbl >= 0, block_tbl, 0).reshape(-1)
                vals = jnp.repeat(cut, block_tbl.shape[1])
                # trash page (id 0) may collect several rows' thresholds;
                # its markers are always -1, never >= a non-negative cut
                thr = thr.at[dest].set(vals)
                return jnp.where(pos_arr >= thr[:, None], -1, pos_arr)
            if c["kp"].ndim == 5:                  # stacked: (L, P, ps, ...)
                return {**c, "pos": jax.vmap(one)(c["pos"])}
            return {**c, "pos": one(c["pos"])}

        def go(c: Pytree) -> Pytree:
            if isinstance(c, dict):
                if "kp" in c:
                    return fix_paged(c)
                if is_ring(c):
                    return fix_dense(c)
                return {k: (go(v) if k != "cross" else v)
                        for k, v in c.items()}
            return c

        return {si: go(c) for si, c in caches.items()}

    def ring_cloud_steps(self, params: Params, ring: Dict[str, jax.Array],
                         ring_pos: jax.Array, ring_valid: jax.Array,
                         caches: Dict[int, Pytree],
                         block_tbl: Optional[jax.Array] = None
                         ) -> Tuple[jax.Array, Dict[int, Pytree]]:
        """Drain a per-row upload ring through the cloud partition in order.

        ring:       packet dict of stacked leaves, leading ring axis —
                    e.g. {"data": (k, B, 1, d)}.
        ring_pos:   (k, B) per-entry positions.
        ring_valid: (k, B) bool; invalid entries leave the row's cache and
                    logits untouched.
        Returns (per-row logits of each row's LAST valid entry (B, V) f32,
        new caches)."""
        b = ring_pos.shape[1]
        vocab = self.model.cfg.vocab_size

        def body(carry, xs):
            c, final = carry
            pkt_i, pos_i, valid_i = xs
            logits_i, c = self.cloud_step_masked(params, pkt_i, c, pos_i,
                                                 valid_i, block_tbl=block_tbl)
            final = jnp.where(valid_i[:, None],
                              logits_i.astype(jnp.float32), final)
            return (c, final), None

        (caches, final), _ = jax.lax.scan(
            body, (caches, jnp.zeros((b, vocab), jnp.float32)),
            (ring, ring_pos, ring_valid))
        return final, caches

    def ring_cloud_steps_all(self, params: Params, ring: Dict[str, jax.Array],
                             ring_pos: jax.Array, ring_valid: jax.Array,
                             caches: Dict[int, Pytree],
                             block_tbl: Optional[jax.Array] = None
                             ) -> Tuple[jax.Array, jax.Array,
                                        Dict[int, Pytree]]:
        """``ring_cloud_steps`` that also returns EVERY entry's logits.

        Multi-token draft verification scores all k draft positions of a
        row in one masked ring pass: the engine needs the per-position
        logits to find the longest agreeing prefix, not just the last
        entry's.  Returns (last-valid logits (B, V) f32 — same contract as
        ``ring_cloud_steps`` — all per-entry logits (k, B, V) f32 with
        invalid entries zeroed, new caches)."""
        b = ring_pos.shape[1]
        vocab = self.model.cfg.vocab_size

        def body(carry, xs):
            c, final = carry
            pkt_i, pos_i, valid_i = xs
            logits_i, c = self.cloud_step_masked(params, pkt_i, c, pos_i,
                                                 valid_i, block_tbl=block_tbl)
            step = jnp.where(valid_i[:, None],
                             logits_i.astype(jnp.float32), 0.0)
            final = jnp.where(valid_i[:, None],
                              logits_i.astype(jnp.float32), final)
            return (c, final), step

        (caches, final), all_logits = jax.lax.scan(
            body, (caches, jnp.zeros((b, vocab), jnp.float32)),
            (ring, ring_pos, ring_valid))
        return final, all_logits, caches

    def standalone_step(self, params: Params, token: jax.Array,
                        caches: Dict[int, Pytree], pos: jax.Array,
                        block_tbl: Optional[jax.Array] = None):
        """Edge standalone (low-latency) mode: last exit is the output."""
        x, exit_h, new_caches, _ = self.model.decode_step(
            params, token, caches, pos, self.edge_segs, block_tbl=block_tbl)
        d = evaluate_exit(self.model.exit_logits(params, self.l_ee2,
                                                 exit_h[self.l_ee2]))
        return d.token, d, new_caches

    def full_step(self, params: Params, token: jax.Array,
                  caches: Dict[int, Pytree], pos: jax.Array,
                  block_tbl: Optional[jax.Array] = None):
        """Undivided model — the cloud-deployment baseline."""
        x, _, new_caches, _ = self.model.decode_step(
            params, token, caches, pos, collect_exits=False,
            block_tbl=block_tbl)
        logits = self.model.logits(params, x)[:, 0]
        return jnp.argmax(logits, -1).astype(jnp.int32), logits, new_caches

    # ------------------------------------------------------------------
    # fused adaptive step (single-graph; TPU-native cond-gated cloud)
    # ------------------------------------------------------------------
    def init_fused_state(self, batch: int, max_seq: int, dtype=None):
        d = self.model.cfg.d_model
        k = self.ccfg.max_pending
        dt = dtype or self.model.compute_dtype
        state = {
            "ring_h": jnp.zeros((k, batch, 1, d), dt),
            "ring_pos": jnp.zeros((k, batch), jnp.int32),
            "count": jnp.zeros((batch,), jnp.int32),
        }
        if self.ccfg.kv_layout == "paged":
            # single-graph mode cannot consult a host allocator, so every
            # row gets a statically identity-mapped run of pages covering
            # max_seq — same memory as dense, but the whole step runs
            # through the paged write/gather path.
            ps = self.ccfg.page_size
            n_lp = -(-max_seq // ps)
            state["block_tbl"] = (1 + jnp.arange(batch * n_lp, dtype=jnp.int32)
                                  ).reshape(batch, n_lp)
            state["edge"] = self.init_edge_cache_paged(batch, batch * n_lp,
                                                       ps, dtype)
            state["cloud"] = self.init_cloud_cache_paged(batch, batch * n_lp,
                                                         ps, dtype)
        else:
            state["edge"] = self.init_edge_cache(batch, max_seq, dtype)
            state["cloud"] = self.init_cloud_cache(batch, max_seq, dtype)
        return state

    def fused_edge_phase(self, params: Params, token: jax.Array,
                         state: Pytree, pos: jax.Array):
        """Edge half of the fused step: decode, exit gating, and the ring
        push — NO cloud compute.  Returns ``(out, rings, need_rows)`` where
        ``rings`` is the updated {ring_h, ring_pos, count}.  A pipelined
        driver runs this for tick t+1 while tick t's
        ``fused_cloud_phase`` result is still in flight, committing each
        needy row's provisional exit-head token in the meantime
        (docs/async_transport.md)."""
        model, ccfg = self.model, self.ccfg
        b = token.shape[0]
        k = ccfg.max_pending if ccfg.backfill else 1
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        tbl = state.get("block_tbl")
        out = self.edge_step(params, token, state["edge"], pos_b, tbl)

        # simulate the wire: quantize -> dequantize
        h1 = dequantize(out.upload, model.compute_dtype)
        # paper-faithful (no backfill): only the newest upload is retained —
        # the content manager releases the rest (gapped cloud KV).
        idx = state["count"] if ccfg.backfill else jnp.zeros((b,), jnp.int32)
        bidx = jnp.arange(b)
        ring_h = state["ring_h"].at[idx, bidx].set(
            h1.astype(state["ring_h"].dtype))
        ring_pos = state["ring_pos"].at[idx, bidx].set(pos_b)
        count = idx + 1

        need_rows = ~out.exited
        if ccfg.backfill:
            need_rows = need_rows | (count >= k)     # ring full -> flush
        if ccfg.speculative:
            need_rows = jnp.ones((b,), bool)
        rings = {"ring_h": ring_h, "ring_pos": ring_pos, "count": count}
        return out, rings, need_rows

    def fused_cloud_phase(self, params: Params, cloud_caches: Pytree,
                          rings: Pytree, need_rows: jax.Array,
                          block_tbl: Optional[jax.Array] = None):
        """Cloud half of the fused step: ``lax.cond``-gated drain of the
        needy rows' upload rings.  Returns (cloud_caches, cloud_logits
        (B, V) f32, new_count)."""
        ccfg = self.ccfg
        b = need_rows.shape[0]
        k = ccfg.max_pending if ccfg.backfill else 1
        vocab = self.model.cfg.vocab_size
        need_cloud = jnp.any(need_rows)

        def run_cloud(operand):
            caches, rh, rp, cnt = operand
            valid = (jnp.arange(k)[:, None] < cnt[None, :]) & need_rows[None]
            logits, caches = self.ring_cloud_steps(
                params, {"data": rh[:k]}, rp[:k], valid, caches,
                block_tbl=block_tbl)
            return caches, logits, jnp.where(need_rows, 0, cnt)

        def skip_cloud(operand):
            caches, rh, rp, cnt = operand
            return caches, jnp.zeros((b, vocab), jnp.float32), cnt

        return jax.lax.cond(
            need_cloud, run_cloud, skip_cloud,
            (cloud_caches, rings["ring_h"], rings["ring_pos"],
             rings["count"]))

    def fused_step(self, params: Params, token: jax.Array, state: Pytree,
                   pos: jax.Array):
        """token: (B,1); pos: scalar or per-row (B,) position vector.
        Returns (next_token (B,), info, new_state).

        Semantics: every step each row pushes its l_ee1 hidden into its own
        upload ring (paper's parallel upload; per-row ring slots).  Cloud
        compute fires only when some row is below θ or its ring is full; it
        then drains the rings of exactly the needy rows in order —
        *backfilling* their cloud KV (beyond-paper exact-KV mode) while
        leaving confident rows' rings accumulating.  Without backfill each
        ring holds only the newest upload (paper's release semantics: the
        cloud KV keeps gaps at early-exited positions).

        Composed of ``fused_edge_phase`` + ``fused_cloud_phase`` so a
        pipelined driver can overlap the two across ticks; calling this
        fused composition keeps single-graph semantics bit-identical."""
        tbl = state.get("block_tbl")
        out, rings, need_rows = self.fused_edge_phase(params, token, state,
                                                      pos)
        cloud_caches, cloud_logits, new_count = self.fused_cloud_phase(
            params, state["cloud"], rings, need_rows, block_tbl=tbl)

        cloud_tok = jnp.argmax(cloud_logits, -1).astype(jnp.int32)
        next_token = jnp.where(out.exited, out.token, cloud_tok)

        new_state = {"edge": out.caches, "cloud": cloud_caches,
                     "ring_h": rings["ring_h"], "ring_pos": rings["ring_pos"],
                     "count": new_count}
        if tbl is not None:
            new_state["block_tbl"] = tbl
        info = {"exited": out.exited, "need_cloud": jnp.any(need_rows),
                "need_rows": need_rows, "cloud_logits": cloud_logits,
                "confidences": {l: d.confidence
                                for l, d in out.decisions.items()}}
        return next_token, info, new_state
