"""Cloud-side continuous batching across engines (paper §5, Fig 4).

The paper's central experiment is N edge clients sharing ONE cloud
server.  Up to PR 3 the cloud side still executed one cloud step per
client request — the shared-FIFO saturation knee existed only inside the
``netsim`` simulator.  The **CloudBatcher** makes it real: it is the cloud
service point's *compute* half.

  * every co-inference client stream owns one row of a pooled,
    batch-major cloud KV cache (the ``ContentManager`` maps
    ``device_id -> cloud slot``; under ``kv_layout="paged"`` the rows
    share a ``PagePool`` exactly like the edge engine's);
  * edge engines submit single-token cloud requests (the uploaded l_ee1
    packet is popped from the ContentManager at submit time, preserving
    the release/backfill semantics of the per-engine path) or k-token
    draft verification requests (``submit_draft``; the draft packets were
    popped by the engine at draft time);
  * pending requests from *any* engine are coalesced into waves — at most
    one request per cloud slot, up to ``max_batch`` rows — and each wave
    is ONE masked ``cloud_step_masked`` (or ``ring_cloud_steps`` in
    backfill mode) over the pooled cache;
  * each request's still-on-device logits and greedy tokens fan back
    out through the requester's own ``CloudChannel``; arrival times are
    priced by the channels' shared ``transport.CloudServicePoint`` (the
    timing half), so per-client latencies stay correct.

Flushes are lazy: requests queue until an engine materializes a reply
(the reply payload carries a ``flush`` hook) or ``flush()`` is called.
Under the multi-engine driver this means one lockstep round of N engines
lands N clients' requests in one wave — one masked cloud step for N edge
clients.

This module must not import ``repro.serving.engine`` (the engine imports
it); the pooled-cache scatter helpers live here and the engine reuses
them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.collm import CoLLM
from repro.core.content_manager import ContentManager
from repro.core.paging import OutOfPages, PagePool, pages_needed
from repro.models.attention import paged_reset_pages, paged_scatter_prefill
from repro.serving.mesh_exec import jit_step, mesh_context

Pytree = Any


def _pad_pages(phys: np.ndarray) -> np.ndarray:
    """Pad a physical-page id list to its power-of-two bucket by repeating
    the last id (duplicate scatter writes of identical data are no-ops),
    bounding the compile count of the swap gather/write graphs."""
    n = len(phys)
    padded = np.empty((_bucket(n, floor=1),), np.int32)
    padded[:n] = phys
    padded[n:] = phys[n - 1]
    return padded


# ---------------------------------------------------------------------------
# pooled-cache helpers (shared with the edge engine)
# ---------------------------------------------------------------------------
def _bucket(n: int, floor: int = 8) -> int:
    """Next power-of-two length bucket >= n (bounds prefill recompiles)."""
    b = floor
    while b < n:
        b *= 2
    return b


def _put_row(f: jax.Array, r: jax.Array, j) -> jax.Array:
    """Insert one cache row into a pooled leaf; the batch axis is located
    by shape mismatch (stacked segments carry batch at axis 1, shared
    segments at axis 0)."""
    if f.shape == r.shape:                          # pool of size 1
        return r.astype(f.dtype)
    axis = next(i for i, (a, b) in enumerate(zip(f.shape, r.shape))
                if a != b)
    return jax.lax.dynamic_update_slice_in_dim(f, r.astype(f.dtype), j, axis)


def _scatter_row(full: Pytree, row: Pytree, j) -> Pytree:
    """Insert a single-row cache pytree into a batched pool at row j."""
    return jax.tree.map(lambda f, r: _put_row(f, r, j), full, row)


def _scatter_row_paged(full: Pytree, row: Pytree, j,
                       pages: jax.Array) -> Pytree:
    """Paged admission scatter: self-attention K/V of the prefilled row is
    written into its allocated physical pages (``pages``: one id per
    logical prompt page, -1 entries redirect to the trash page); every
    other cache leaf (cross-attn, recurrent state) is a dense per-row
    scatter at row j exactly like the dense layout."""
    def go(f: Pytree, r: Pytree) -> Pytree:
        if isinstance(f, dict):
            if "kp" in f:
                if f["kp"].ndim == 5:       # stacked: (L, P, ps, KV, d)
                    return jax.vmap(paged_scatter_prefill,
                                    in_axes=(0, 0, None))(f, r, pages)
                return paged_scatter_prefill(f, r, pages)
            return {k: go(f[k], r[k]) for k in f}
        return _put_row(f, r, j)
    return {si: go(full[si], row[si]) for si in full}


def _reset_pages_tree(caches: Pytree, pages: jax.Array) -> Pytree:
    """Invalidate freed physical pages across every paged cache node, so a
    page returned to the free list never leaks a retired stream's K/V."""
    def go(c: Pytree) -> Pytree:
        if isinstance(c, dict):
            if "kp" in c:
                if c["kp"].ndim == 5:
                    return jax.vmap(paged_reset_pages,
                                    in_axes=(0, None))(c, pages)
                return paged_reset_pages(c, pages)
            return {k: go(v) for k, v in c.items()}
        return c
    return {si: go(c) for si, c in caches.items()}


def build_upload_ring(entries, batch: int):
    """Assemble the dense upload ring for ``ring_cloud_steps`` from
    per-row packet lists.

    ``entries``: [(row, [(pos, StatePacket), ...]), ...] — one entry per
    pool row, packets in consumption order.  Returns ``(ring, ring_pos,
    valid)`` device arrays with the ring depth bucketed to a power of two
    (bounds the scan compile count).  Shared by the engine's backfill
    dispatch, the preemption replay paths, and the CloudBatcher's wave
    compute, so the ring layout can never drift between them."""
    depth = _bucket(max((len(p) for _, p in entries), default=1), floor=1)
    first = next(p for _, pkts in entries for _, p in pkts)
    keys = first.hidden.keys()
    ring = {k: np.zeros((depth, batch) + np.shape(first.hidden[k])[1:],
                        np.asarray(first.hidden[k]).dtype) for k in keys}
    ring_pos = np.zeros((depth, batch), np.int32)
    valid = np.zeros((depth, batch), bool)
    for row, pkts in entries:
        for i, (p, pkt) in enumerate(pkts):
            for k in keys:
                ring[k][i, row] = np.asarray(pkt.hidden[k])[0]
            ring_pos[i, row] = p
            valid[i, row] = True
    return ({k: jnp.asarray(v) for k, v in ring.items()},
            jnp.asarray(ring_pos), jnp.asarray(valid))


def _page_axis(node: Pytree) -> int:
    """Batch/page axis of a paged node's leaves: stacked segments carry a
    leading layer axis (kp: (L, P, ps, KV, d)), shared ones don't."""
    return 1 if node["kp"].ndim == 5 else 0


def _gather_pages_tree(caches: Pytree, phys: jax.Array) -> Pytree:
    """Swap-out: slice the given physical pages out of every paged cache
    node (``{si: {kp, vp, pos}}`` page-axis slices, same tree shape)."""
    def go(c: Pytree) -> Pytree:
        if isinstance(c, dict):
            if "kp" in c:
                ax = _page_axis(c)
                return {k: jnp.take(v, phys, axis=ax) for k, v in c.items()}
            return {k: go(v) for k, v in c.items()}
        return None
    return {si: go(c) for si, c in caches.items()}


def _copy_pages_tree(caches: Pytree, src, dst) -> Pytree:
    """Copy-on-write device half: duplicate physical page ``src`` into
    ``dst`` across every paged cache node.  Generic over the node's
    leaves, so int8 nodes' quantized K/V *and* their scale rows ride
    along; ``src``/``dst`` trace as scalars (one compile for all ids)."""
    def go(c: Pytree) -> Pytree:
        if isinstance(c, dict):
            if "kp" in c:
                if _page_axis(c) == 1:
                    return {k: v.at[:, dst].set(v[:, src])
                            for k, v in c.items()}
                return {k: v.at[dst].set(v[src]) for k, v in c.items()}
            return {k: go(v) for k, v in c.items()}
        return c
    return {si: go(c) for si, c in caches.items()}


def _write_pages_tree(caches: Pytree, phys: jax.Array,
                      data: Pytree) -> Pytree:
    """Swap-in: write snapshotted page contents into (freshly allocated)
    physical pages.  Duplicate ids in ``phys`` carry identical data
    (``_pad_pages``), so overlapping scatters are benign."""
    def go(c: Pytree, d: Pytree) -> Pytree:
        if isinstance(c, dict):
            if "kp" in c:
                if _page_axis(c) == 1:
                    return {k: c[k].at[:, phys].set(
                        d[k].astype(c[k].dtype)) for k in c}
                return {k: c[k].at[phys].set(d[k].astype(c[k].dtype))
                        for k in c}
            return {k: go(c[k], d[k]) for k in c}
        return c
    return {si: go(c, data[si]) for si, c in caches.items()}


def gather_slot_pages(pool: PagePool, slot: int, caches: Pytree):
    """Swap-out core: slice one slot's physical pages out of a paged
    cache tree.  Returns ``(logical, host_tree)`` — the slot's logical
    page indices and the device-fetched page contents (None when the slot
    owns nothing)."""
    tbl_row = pool.block_table[slot]
    logical = np.nonzero(tbl_row >= 0)[0].astype(np.int32)
    if not len(logical):
        return logical, None
    padded = jnp.asarray(_pad_pages(tbl_row[logical].astype(np.int32)))
    return logical, jax.device_get(GATHER_PAGES(caches, padded))


def rebind_slot_pages(pool: PagePool, slot: int,
                      logical: np.ndarray) -> jax.Array:
    """Swap-in core: re-allocate a snapshot's logical pages for ``slot``
    (pages are row-agnostic — the block table re-binds them to whatever
    physical ids are free) and return the padded id vector to
    ``WRITE_PAGES`` the snapshot into."""
    for lp in logical:
        pool.alloc(slot, int(lp))
    phys = pool.block_table[slot][logical].astype(np.int32)
    return jnp.asarray(_pad_pages(phys))


def all_paged(caches: Pytree) -> bool:
    """True when every cache leaf lives under a paged ("kp") node — the
    precondition for swap preemption (a dense leaf — recurrent state,
    cross-attention — would be silently lost by a page-only snapshot)."""
    def go(c: Pytree) -> bool:
        if isinstance(c, dict):
            if "kp" in c:
                return True
            return bool(c) and all(go(v) for v in c.values())
        return False
    return all(go(c) for c in caches.values())


# one jitted wrapper per process, shared by every scheduler and batcher —
# schedulers are spawned per client in multi-engine mode and must not each
# re-trace the scatter/invalidate graphs
SCATTER = jax.jit(_scatter_row)
SCATTER_PAGED = jax.jit(_scatter_row_paged)
RESET_PAGES = jax.jit(_reset_pages_tree)
GATHER_PAGES = jax.jit(_gather_pages_tree)
WRITE_PAGES = jax.jit(_write_pages_tree)
COPY_PAGES = jax.jit(_copy_pages_tree)


# per-CoLLM memoized jit of bound step methods now lives in the
# MeshContext (serving/mesh_exec.py): same one-trace-per-CoLLM guarantee,
# but cloud steps are traced under the sharding policy when
# CollmConfig.cloud_mesh is set
_jit = jit_step


# ---------------------------------------------------------------------------
# the batcher
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Entry:
    """One queued cloud request awaiting a batched step."""
    device_id: str
    slot: int                   # cloud pool row
    pos: int
    packets: list               # [(pos, StatePacket), ...]; len > 1 means
                                # backfill ring and/or k-token draft
    group: dict                 # reply payload shared with the channel


@dataclasses.dataclass
class BatcherStats:
    requests: int = 0
    steps: int = 0              # masked batched cloud calls executed
    rows: int = 0               # summed rows served by those calls
    max_rows: int = 0           # peak rows in any single wave (occupancy)
    cancelled: int = 0
    prefills: int = 0
    prefill_chunks: int = 0     # chunked-admission cloud prefill calls
    prefix_hit_tokens: int = 0  # prompt tokens served from shared pages
    restores: int = 0           # preempted-stream cloud-KV replays
    swaps: int = 0              # cloud rows swapped out to host

    @property
    def mean_batch(self) -> float:
        return self.rows / self.steps if self.steps else 0.0

    def as_row(self) -> Dict[str, float]:
        return {"requests": self.requests, "steps": self.steps,
                "mean_batch": round(self.mean_batch, 2),
                "max_batch": self.max_rows,
                "cancelled": self.cancelled, "prefills": self.prefills,
                "prefill_chunks": self.prefill_chunks,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "restores": self.restores, "swaps": self.swaps}


class CloudBatcher:
    """One cloud partition serving N client streams out of a pooled,
    batch-major KV cache — the compute half of the shared cloud service
    point (docs/async_transport.md §cloud service point)."""

    def __init__(self, collm: CoLLM, params: Pytree, cm: ContentManager,
                 num_slots: int, max_seq: int, *,
                 max_batch: Optional[int] = None,
                 max_ctx: Optional[int] = None,
                 num_pages: Optional[int] = None):
        self.collm = collm
        # mesh-aware placement (docs/sharding.md): with cloud_mesh set the
        # params and the pooled batch-major cloud KV get committed to the
        # cloud mesh via role-based NamedShardings, and the jitted cloud
        # steps below trace under the sharding policy.  Without a mesh
        # both calls are identity.
        self._mesh = mesh_context(collm)
        self.params = self._mesh.shard_params(params)
        self.cm = cm
        self.B = num_slots
        self.max_seq = max_seq
        self.max_batch = max_batch or num_slots
        cm.init_cloud_slots(num_slots)

        self.layout = collm.ccfg.kv_layout
        self.pool: Optional[PagePool] = None
        self._tbl_device: Optional[jax.Array] = None
        if self.layout == "paged":
            ps = collm.ccfg.page_size
            self.max_ctx = max_ctx or max_seq
            n_pages = num_pages or num_slots * pages_needed(self.max_ctx, ps)
            self.pool = PagePool(n_pages, ps, num_slots,
                                 pages_needed(self.max_ctx, ps),
                                 prefix_cache=collm.ccfg.prefix_share)
            row_seq = _bucket(self.max_ctx)
            self.caches = collm.init_cloud_cache_paged(
                num_slots, self.pool.num_pages, ps)
        else:
            self.max_ctx = max_seq
            row_seq = max_seq
            self.caches = collm.init_cloud_cache(num_slots, max_seq)
        self.caches = self._mesh.shard_caches(self.caches, batch=num_slots)
        self._row_seq = row_seq
        self._row0 = collm.init_cloud_cache(1, row_seq)

        self._cloud_masked = _jit(collm, "cloud_step_masked")
        self._ring_cloud = _jit(collm, "ring_cloud_steps")
        self._ring_cloud_all = _jit(collm, "ring_cloud_steps_all")
        self._cloud_prefill = _jit(collm, "cloud_prefill_padded")
        self._cloud_chunk = _jit(collm, "cloud_prefill_chunk")
        self._invalidate_rows = _jit(collm, "invalidate_rows_after")
        self._scatter = SCATTER
        self._scatter_paged = SCATTER_PAGED
        self._reset_pages = RESET_PAGES

        self._pending: List[_Entry] = []
        self._budget: Dict[str, int] = {}   # device_id -> prompt+max_new
        self.stats = BatcherStats()

    # -- capacity / lifecycle ----------------------------------------------
    def _outstanding_pages(self) -> int:
        """Worst-case pages still owed to admitted streams.  The pool no
        longer keeps a reservation ledger; the batcher stays conservative
        (its rows are not preemptible) by re-deriving the same number from
        each active client's token budget minus what it already owns."""
        out = 0
        for dev, budget in self._budget.items():
            slot = self.cm.cloud_slot(dev)
            if slot is None:
                continue
            out += max(0, pages_needed(budget, self.pool.page_size)
                       - self.pool.owned_pages(slot))
        return out

    def can_admit(self, budget_tokens: int, hit_pages: int = 0) -> bool:
        """One more stream of ``prompt + max_new`` tokens, right now?
        ``hit_pages`` discounts prompt pages a prospective prefix-cache hit
        would map instead of allocating (see ``PagePool.can_admit``), and
        pages held only by the prefix cache count as available — they come
        back on demand through ``evict_prefix``."""
        if self.cm.cloud_slots_free() <= 0:
            return False
        if self.pool is not None:
            need = pages_needed(budget_tokens, self.pool.page_size) \
                - hit_pages
            if need > self.pool.num_pages:
                raise ValueError(
                    f"stream of {budget_tokens} tokens needs more pages "
                    f"than the cloud pool has ({self.pool.num_pages})")
            avail = (self.pool.free_pages + self.pool.reclaimable_pages
                     - self._outstanding_pages())
            return need <= avail
        return True

    def _alloc(self, slot: int, lp: int) -> None:
        """Pool alloc that reclaims prefix-cache pages under pressure: a
        failed alloc first evicts LRU trie entries (their device ``pos``
        markers are invalidated here, so the recycled page cannot leak
        stale K/V) and retries before letting ``OutOfPages`` escape."""
        try:
            self.pool.alloc(slot, lp)
        except OutOfPages:
            freed = self.pool.evict_prefix(1)
            if not freed:
                raise
            ids = np.full((self.pool.max_logical,), -1, np.int32)
            ids[:len(freed)] = freed
            self.caches = self._reset_pages(self.caches, jnp.asarray(ids))
            self.pool.alloc(slot, lp)
        self._tbl_device = None

    def admit(self, device_id: str, h1_seq: jax.Array, true_len: int,
              budget_tokens: int) -> jax.Array:
        """Prefill the cloud partition over the uploaded (padded) prompt
        hidden sequence into the client's pool row; returns the logits at
        the true last position (the cloud answer for the first token),
        still on device."""
        slot = self.cm.assign_cloud_slot(device_id)
        self._budget[device_id] = budget_tokens
        pages = None
        if self.pool is not None:
            n_prompt = pages_needed(true_len, self.pool.page_size)
            for lp in range(n_prompt):
                self._alloc(slot, lp)
            pad = h1_seq.shape[1]
            pages = np.full((pages_needed(pad, self.pool.page_size),),
                            -1, np.int32)
            pages[:n_prompt] = self.pool.block_table[slot, :n_prompt]
            self._tbl_device = None
        logits, row = self._cloud_prefill(self.params, h1_seq, true_len,
                                          self._row0)
        if pages is None:
            self.caches = self._scatter(self.caches, row, slot)
        else:
            self.caches = self._scatter_paged(self.caches, row, slot,
                                              jnp.asarray(pages))
        self.stats.prefills += 1
        return logits

    # -- chunked admission (prefix sharing) --------------------------------
    def prefix_hit(self, tokens) -> int:
        """Full-page prefix hit the batcher's OWN pool could serve for
        this prompt (0 without prefix sharing).  The engine takes the min
        of the edge-side and cloud-side hits, so upload skipping and
        cloud-page sharing stay aligned — a chunk is only skipped when
        BOTH service points already hold it."""
        if self.pool is None or not self.pool.prefix_cache:
            return 0
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        return len(self.pool.match_prefix(toks).pages)

    def admit_begin(self, device_id: str, tokens, true_len: int,
                    budget_tokens: int, hit_pages: int = 0) -> List[int]:
        """Chunked admission, bookkeeping half: assign the cloud row, map
        ``hit_pages`` shared prefix pages out of the batcher's own trie,
        allocate the remaining prompt pages upfront (chunk compute never
        allocates mid-flight), and register the prompt's full chunks for
        future sharers.  Returns the shared page ids — the engine must see
        ``pages_filled`` on them before uploading chunks that attend past
        them (their owning stream may still be mid-prefill)."""
        slot = self.cm.assign_cloud_slot(device_id)
        self._budget[device_id] = budget_tokens
        if self.pool is None:
            return []
        ps = self.pool.page_size
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)[:true_len]]
        shared: List[int] = []
        if hit_pages:
            hit = self.pool.match_prefix(toks)
            shared = list(hit.pages[:hit_pages])
            for lp, page in enumerate(shared):
                self.pool.share_page(slot, lp, page)
            self.pool.stats.prefix_hit_tokens += len(shared) * ps
            self.stats.prefix_hit_tokens += len(shared) * ps
        for lp in range(len(shared), pages_needed(true_len, ps)):
            self._alloc(slot, lp)
        if self.pool.prefix_cache:
            self.pool.insert_prefix(slot, toks)
        self._tbl_device = None
        self.stats.prefills += 1
        return shared

    def admit_chunk(self, device_id: str, h1: jax.Array, pos0: int,
                    chunk_len: int) -> jax.Array:
        """Chunked admission, compute half: cloud-prefill ONE uploaded
        hidden chunk (h1: (1, C, d), right-padded to the page size) into
        the stream's pages.  Returns the logits at the chunk's true last
        position — only the final chunk's matter; earlier chunks run for
        the KV side effect.  ``pos0``/``chunk_len`` trace as scalars, so
        every chunk of every stream shares one compile."""
        slot = self.cm.cloud_slot(device_id)
        if slot is None:
            raise KeyError(f"{device_id} has no cloud slot "
                           "(admit_begin first)")
        row_tbl = jnp.asarray(self.pool.block_table[slot:slot + 1])
        logits, self.caches = self._cloud_chunk(
            self.params, h1, jnp.int32(pos0), jnp.int32(chunk_len),
            self.caches, row_tbl)
        self.stats.prefill_chunks += 1
        ps = self.pool.page_size
        if chunk_len == ps:
            self.pool.mark_filled(
                int(self.pool.block_table[slot, pos0 // ps]))
        return logits

    def pages_filled(self, pages) -> bool:
        """True once every given shared page's owning stream has computed
        its chunk (engine-side stall check for concurrent sharers)."""
        return self.pool is None or self.pool.pages_filled(pages)

    def release(self, device_id: str) -> None:
        """Stream finished (or was preempted): cancel its queued requests,
        free its pages (invalidated on device), return its pool row."""
        self.cancel(device_id, 0)
        self._budget.pop(device_id, None)
        slot = self.cm.release_cloud_slot(device_id)
        if slot is None or self.pool is None:
            return
        freed = self.pool.free_slot(slot)
        self._tbl_device = None
        if not freed:
            return
        ids = np.full((self.pool.max_logical,), -1, np.int32)
        ids[:len(freed)] = freed
        self.caches = self._reset_pages(self.caches, jnp.asarray(ids))

    # -- request path -------------------------------------------------------
    def submit(self, device_id: str, pos: int, *, backfill: bool = False):
        """Queue one single-token cloud request; returns ``(group, row,
        packets)`` — the engine hands ``(group, row)`` to its channel as
        the reply payload and may retain ``packets`` (the consumed
        uploads) for a preemption checkpoint.  The uploaded packet(s) are
        popped from the ContentManager NOW (submit order = per-client pos
        order), so a later flush computes exactly what a per-engine call
        would have."""
        slot = self.cm.cloud_slot(device_id)
        if slot is None:
            raise KeyError(f"{device_id} has no cloud slot (admit first)")
        if backfill:
            packets = self.cm.take_uploads_upto(device_id, pos)
        else:
            packets = [(pos, self.cm.take_upload(device_id, pos))]
        if self.pool is not None:
            for p, _ in packets:
                lp = p // self.pool.page_size
                if self.pool.block_table[slot, lp] == -1:
                    self._alloc(slot, lp)
        group = {"logits": None, "tokens": None, "np": None,
                 "flush": self.flush}
        self._pending.append(_Entry(device_id=device_id, slot=slot, pos=pos,
                                    packets=packets, group=group))
        self.stats.requests += 1
        return group, slot, packets

    def submit_draft(self, device_id: str, draft, *, backfill: bool = False):
        """Queue one k-token draft verification request (the engine's
        ``_flush_drafts``).  ``draft``: [(pos, StatePacket), ...] — the
        draft positions' packets in order, popped by the engine at draft
        time (the upload window must never evict a position awaiting
        verification).  Backfill additionally drains the client's
        not-yet-consumed older uploads here, so the merged ring rebuilds
        the exact cloud KV.  Returns ``(group, row, packets)`` like
        ``submit``; ``packets`` is the merged consumption-order list the
        engine indexes the reply's per-position logits with (and may
        retain for a preemption checkpoint).  The reply group carries
        ``all`` / ``np_all``: EVERY ring entry's logits, not just the
        last-valid row."""
        slot = self.cm.cloud_slot(device_id)
        if slot is None:
            raise KeyError(f"{device_id} has no cloud slot (admit first)")
        packets = list(draft)
        if backfill:
            older = self.cm.take_uploads_upto(device_id, packets[-1][0])
            # older positions all precede the draft (the engine flushes on
            # a confident tick, so drafts stay position-contiguous)
            packets = older + packets
        if self.pool is not None:
            for p, _ in packets:
                lp = p // self.pool.page_size
                if self.pool.block_table[slot, lp] == -1:
                    self._alloc(slot, lp)
        group = {"logits": None, "tokens": None, "all": None, "np": None,
                 "np_all": None, "flush": self.flush}
        self._pending.append(_Entry(device_id=device_id, slot=slot,
                                    pos=packets[-1][0], packets=packets,
                                    group=group))
        self.stats.requests += 1
        return group, slot, packets

    def cancel(self, device_id: str, min_pos: int) -> int:
        """Drop queued (not yet computed) requests of one client at
        positions >= ``min_pos`` — a speculative rewind discarded them, or
        the stream retired.  Their replies will late-drop in the engine;
        computing them after an ``invalidate`` would resurrect stale KV."""
        keep = [e for e in self._pending
                if e.device_id != device_id or e.pos < min_pos]
        dropped = len(self._pending) - len(keep)
        self._pending = keep
        self.stats.cancelled += dropped
        return dropped

    def invalidate(self, device_id: str, cut_pos: int) -> None:
        """Speculative rewind support: invalidate the client's cloud KV at
        positions >= ``cut_pos`` (see ``CoLLM.invalidate_rows_after``)."""
        slot = self.cm.cloud_slot(device_id)
        if slot is None:
            return
        cut = np.full((self.B,), np.iinfo(np.int32).max, np.int32)
        cut[slot] = cut_pos
        self.caches = self._invalidate_rows(self.caches, jnp.asarray(cut),
                                            self._block_tbl())

    # -- preemption lifecycle ----------------------------------------------
    def restore(self, device_id: str, packets) -> None:
        """Resume (recompute mode): replay a checkpointed stream's
        consumed cloud uploads — positions strictly below the resume
        point — through the cloud partition, rebuilding its pooled-row KV
        to the exact pre-preemption state (release-semantics gaps
        included).  The caller re-``admit``s the prompt prefill first;
        positions at/after the resume point are NOT replayed — re-decode
        re-submits them through the normal request path."""
        slot = self.cm.cloud_slot(device_id)
        if slot is None:
            raise KeyError(f"{device_id} has no cloud slot (admit first)")
        if not packets:
            return
        if self.pool is not None:
            for p, _ in packets:
                lp = p // self.pool.page_size
                if self.pool.block_table[slot, lp] == -1:
                    self._alloc(slot, lp)
        ring, ring_pos, valid = build_upload_ring([(slot, packets)], self.B)
        _, self.caches = self._ring_cloud(
            self.params, ring, ring_pos, valid, self.caches,
            self._block_tbl())
        self.stats.restores += 1

    def swap_out(self, device_id: str):
        """Preempt (swap mode): snapshot the stream's cloud-KV pages to
        host memory, then release its row/pages/budget.  Returns the
        snapshot for ``swap_in`` (None when the stream owned nothing).

        Flushes the request queue first: a queued-but-uncomputed entry
        (lazy flush) has consumed its uploads without writing their KV
        yet — snapshotting before the wave runs would freeze the gap and
        ``release``'s cancel would drop the only copy of the packets
        (backfill rings cover positions re-decode never re-uploads).  The
        un-preempted run computes those entries at the next
        materialization anyway, so flushing early changes wave grouping,
        never values."""
        slot = self.cm.cloud_slot(device_id)
        if slot is None or self.pool is None:
            self.release(device_id)
            return None
        if self._pending:
            self.flush()
        logical, pages = gather_slot_pages(self.pool, slot, self.caches)
        if pages is not None:
            self.stats.swaps += 1
        snap = {"logical": logical, "pages": pages,
                "budget": self._budget.get(device_id)}
        self.release(device_id)
        return snap

    def swap_in(self, device_id: str, snap) -> None:
        """Resume (swap mode): re-acquire a cloud row (possibly a
        different one — pages are row-agnostic, the block table re-binds
        them) and write the snapshot back into freshly allocated pages."""
        self.cm.assign_cloud_slot(device_id)
        if snap is None:
            return
        if snap["budget"] is not None:
            self._budget[device_id] = snap["budget"]
        if snap["pages"] is None:
            return
        slot = self.cm.cloud_slot(device_id)
        padded = rebind_slot_pages(self.pool, slot, snap["logical"])
        self.caches = WRITE_PAGES(self.caches, padded, snap["pages"])
        self._tbl_device = None

    def flush(self) -> None:
        """Drain the queue in waves: each wave serves at most one request
        per cloud slot (and at most ``max_batch`` rows) with ONE masked
        batched cloud step; every entry's reply group gets the wave's
        still-on-device logits and greedy tokens."""
        while self._pending:
            wave, rest, seen = [], [], set()
            for e in self._pending:
                if e.slot in seen or len(wave) >= self.max_batch:
                    rest.append(e)
                else:
                    seen.add(e.slot)
                    wave.append(e)
            self._pending = rest
            self._compute(wave)

    # -- internals ----------------------------------------------------------
    def _block_tbl(self) -> Optional[jax.Array]:
        if self.pool is None:
            return None
        if self._tbl_device is None:
            self._tbl_device = jnp.asarray(self.pool.block_table)
        return self._tbl_device

    def _compute(self, wave: List[_Entry]) -> None:
        # any multi-packet entry (backfill ring OR k-token draft) needs the
        # ring pass; an all-singles wave takes the dense masked step
        ring_mode = any(len(e.packets) > 1 for e in wave)
        mask = np.zeros((self.B,), bool)
        for e in wave:
            mask[e.slot] = True
        first = wave[0].packets[0][1]
        keys = first.hidden.keys()
        if ring_mode:
            ring, ring_pos, valid = build_upload_ring(
                [(e.slot, e.packets) for e in wave], self.B)
            logits, all_logits, self.caches = self._ring_cloud_all(
                self.params, ring, ring_pos, valid, self.caches,
                self._block_tbl())
            tokens = jnp.argmax(logits, axis=-1)
            for e in wave:
                # draft replies reconcile per position; single-token
                # groups ignore the extra key
                e.group["all"] = all_logits
        else:
            dense = {k: np.zeros((self.B,) + np.shape(first.hidden[k])[1:],
                                 np.asarray(first.hidden[k]).dtype)
                     for k in keys}
            pos = np.zeros((self.B,), np.int32)
            for e in wave:
                (p, pkt), = e.packets
                for k in keys:
                    dense[k][e.slot] = np.asarray(pkt.hidden[k])[0]
                pos[e.slot] = p
            logits, tokens, self.caches = self._cloud_masked(
                self.params, {k: jnp.asarray(v) for k, v in dense.items()},
                self.caches, jnp.asarray(pos), jnp.asarray(mask),
                self._block_tbl())
        for e in wave:
            e.group["logits"] = logits
            e.group["tokens"] = tokens
        self.stats.steps += 1
        self.stats.rows += len(wave)
        self.stats.max_rows = max(self.stats.max_rows, len(wave))

    def kv_cache_bytes(self) -> int:
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree.leaves(self.caches))
