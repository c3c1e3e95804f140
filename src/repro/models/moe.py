"""Mixture-of-Experts FFN with top-k routing, in two forms.

* ``moe_served``: the served layer (prefill and decode).  Dropless: the
  (token, expert) pairs that land on the experts this device holds
  (``MoEConfig.held``; expert parallelism gives each chip a range) are
  sorted by expert and run through grouped matmuls (``_grouped``:
  megablox's Pallas kernel on the TPU, ``jax.lax.ragged_dot``
  elsewhere), so only routed pairs are computed and each held expert's
  weights are read once per row tile that holds its pairs.  A layer of
  a scanned stack is handed the whole stack of expert weights and its
  index (``params["layer"]``), so the kernel reads the layer's experts in
  place, with no copy sliced out of the stack.  The router runs over
  every expert in float32; shared experts see every token.  What experts
  held elsewhere would add is left to their devices.
* ``moe_forward``: training's scatter/capacity ("dropped") formulation,
  the standard TPU-friendly one: tokens are scattered into per-expert
  buffers of fixed capacity ``C = ceil(T * top_k / E *
  capacity_factor)``, each expert runs a dense batched FFN over its
  buffer (ECd,Edf einsums -> MXU-shaped), and results are gathered back
  with router-probability combine weights.  Auxiliary load-balance loss
  follows Switch/OLMoE.

Expert weights use gated-SiLU FFNs.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, split_rngs

Params = Dict[str, Any]


def init_moe(rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """The router over every expert, the held experts' weights (one row of
    ``w_*`` per held expert) and, with ``n_shared_experts``, one SwiGLU of
    their joint width under ``shared``."""
    assert cfg.moe is not None
    moe = cfg.moe
    e, d, f = moe.num_experts, cfg.d_model, moe.expert_d_ff
    n = moe.held_range[1]
    rngs = split_rngs(rng, 5)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(f)
    p = {
        "router": dense_init(rngs[0], d, e, jnp.float32),  # router in fp32
        "w_gate": (jax.random.normal(rngs[1], (n, d, f)) * scale_in).astype(dtype),
        "w_up": (jax.random.normal(rngs[2], (n, d, f)) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(rngs[3], (n, f, d)) * scale_out).astype(dtype),
    }
    if moe.n_shared_experts:
        fs = moe.n_shared_experts * f
        r = split_rngs(rngs[4], 3)
        p["shared"] = {"w_gate": dense_init(r[0], d, fs, dtype),
                       "w_up": dense_init(r[1], d, fs, dtype),
                       "w_down": dense_init(r[2], fs, d, dtype)}
    return p


def route(params: Params, cfg: ModelConfig, xf: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """xf (T, d) -> (weights (T, K), experts (T, K)): a float32 softmax over
    every expert and its top k, renormalised where ``norm_topk_prob``."""
    moe = cfg.moe
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), moe.top_k)
    if moe.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_e


def _swiglu(p: Params, x: jax.Array) -> jax.Array:
    g = jax.nn.silu(x @ p["w_gate"].astype(x.dtype))
    return (g * (x @ p["w_up"].astype(x.dtype))) @ p["w_down"].astype(x.dtype)


_GMM_TM = 128          # rows of a grouped-matmul tile on the TPU


def _gmm_tile(dim: int) -> int:
    return 512 if dim % 512 == 0 else dim


def _grouped(xs: jax.Array, w: jax.Array, sizes: jax.Array,
             interpret: bool = False) -> jax.Array:
    """Rows of ``xs`` (M, k), sorted by group, through their group's
    ``w`` (G, k, n): group ``g`` is the next ``sizes[g]`` rows.  Rows past
    the groups are left undefined.  On the TPU (or with ``interpret``)
    this is megablox's grouped matmul, a Pallas kernel that keeps the
    caller's named scope and fetches only the weights of groups with
    rows; elsewhere ``lax.ragged_dot``."""
    if jax.default_backend() != "tpu" and not interpret:
        return jax.lax.ragged_dot(xs, w, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = xs.shape
    pad = -m % _GMM_TM
    out = gmm(jnp.pad(xs, ((0, pad), (0, 0))), w, sizes,
              preferred_element_type=xs.dtype,
              tiling=(_GMM_TM, _gmm_tile(k), _gmm_tile(w.shape[2])),
              interpret=interpret)
    return out[:m]


def _expert_groups(params: Params, n: int, dtype
                   ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """The held experts' weights as groups of the grouped matmul, and the
    index of this layer's first group.  ``w_*`` of one layer are (n, ...);
    a stack of layers' (L, n, ...) with ``params["layer"]`` flattens to
    L * n groups, free of any copy, of which this layer's start at
    ``layer * n``."""
    w = {k: params[k].astype(dtype) for k in ("w_gate", "w_up", "w_down")}
    if "layer" not in params:
        return w, jnp.zeros((), jnp.int32)
    return ({k: v.reshape(-1, *v.shape[2:]) for k, v in w.items()},
            params["layer"].astype(jnp.int32) * n)


def moe_served(params: Params, cfg: ModelConfig, x: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """The served layer, dropless.  x: (B, S, d) -> (out (B, S, d), held
    (B,) int32: the row's (token, expert) pairs that landed on a held
    expert).  The pairs are sorted by held expert (the others go last and
    are computed by no group), each group runs through its expert's
    gated FFN (``_grouped``), and each token sums its pairs weighted by
    the router; then the shared experts on every token."""
    moe = cfg.moe
    b, s, d = x.shape
    k = moe.top_k
    first, n = moe.held_range
    xf = x.reshape(b * s, d)
    top_p, top_e = route(params, cfg, xf)
    local = top_e - first
    held = (local >= 0) & (local < n)                         # (T, K)
    group = jnp.where(held, local, n).reshape(-1)             # (T*K,)
    order = jnp.argsort(group, stable=True)
    tok = order // k
    w, start = _expert_groups(params, n, x.dtype)
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((w["w_gate"].shape[0],), jnp.int32),
        jnp.bincount(group, length=n + 1)[:n].astype(jnp.int32), (start,))
    xs = xf[tok]                                              # (T*K, d)
    hid = jax.nn.silu(_grouped(xs, w["w_gate"], sizes))
    hid = hid * _grouped(xs, w["w_up"], sizes)
    ys = _grouped(hid, w["w_down"], sizes)
    # pairs on experts held elsewhere sort last, past every group: their
    # rows are undefined, so they are selected out, not weighted by zero
    on = (group[order] < n)[:, None]
    wts = jnp.where(held, top_p, 0.0).reshape(-1)[order]
    out = jnp.zeros((b * s, d), jnp.float32).at[tok].add(
        jnp.where(on, ys.astype(jnp.float32) * wts[:, None], 0.0))
    if "shared" in params:
        out = out + _swiglu(params["shared"], xf).astype(jnp.float32)
    counts = held.reshape(b, s * k).sum(-1).astype(jnp.int32)
    return out.astype(x.dtype).reshape(b, s, d), counts


def moe_forward(params: Params, cfg: ModelConfig,
                x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).  Routing is per-token.

    When a distribution policy is active (production meshes), dispatch runs
    inside ``shard_map`` so the scatter/gather are DEVICE-LOCAL — GSPMD
    never partitions them.  Both the global flat dispatch (cumsum over
    B*S*K) and a batched-per-row scatter make the SPMD partitioner
    replicate multi-GB dispatch tensors on every device (measured 700 GB
    and 741 GB/device respectively for olmoe train_4k — EXPERIMENTS.md
    §Perf iterations 1a/1b).  The plain path below is the single-device
    reference semantics (also the oracle for the shard_map path)."""
    from repro.launch import sharding as shardlib
    if cfg.moe.held is not None or cfg.moe.n_shared_experts:
        raise ValueError("the capacity form trains whole routed layers; "
                         "held shares and shared experts are served by "
                         "moe_served")
    policy = shardlib.current_policy()
    if policy is not None and x.shape[1] > 1:
        return _moe_forward_shardmap(params, cfg, x, policy)
    return _moe_forward_local(params, cfg, x)


def _moe_forward_local(params: Params, cfg: ModelConfig,
                       x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-sequence batched dispatch (single-device reference)."""
    moe = cfg.moe
    b, s, d = x.shape
    e, k = moe.num_experts, moe.top_k
    cap = int(math.ceil(s * k / e * moe.capacity_factor))
    cap = max(cap, k)

    router_logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(router_logits, axis=-1)            # (B, S, E)
    top_p, top_e = jax.lax.top_k(probs, k)                    # (B, S, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # position of each (token, slot) within its expert's buffer, per row
    flat_e = top_e.reshape(b, s * k)                          # (B, S*K)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)       # (B, S*K, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot            # exclusive
    flat_pos = jnp.take_along_axis(pos_in_e, flat_e[..., None],
                                   axis=2)[..., 0]            # (B, S*K)
    keep = flat_pos < cap
    buf_e = jnp.where(keep, flat_e, e)                        # expert e = drop
    buf_p = jnp.where(keep, flat_pos, 0)

    tok_rep = jnp.repeat(x, k, axis=1).reshape(b, s * k, d)
    bidx = jnp.arange(b)[:, None]
    buffers = jnp.zeros((b, e + 1, cap, d), x.dtype)
    buffers = buffers.at[bidx, buf_e, buf_p].set(tok_rep, mode="drop")
    buffers = buffers[:, :e]                                  # (B, E, C, d)

    # batched expert FFN (gated SiLU)
    wg = params["w_gate"].astype(x.dtype)
    wu = params["w_up"].astype(x.dtype)
    wd = params["w_down"].astype(x.dtype)
    hidden = jax.nn.silu(jnp.einsum("becd,edf->becf", buffers, wg))
    hidden = hidden * jnp.einsum("becd,edf->becf", buffers, wu)
    expert_out = jnp.einsum("becf,efd->becd", hidden, wd)     # (B, E, C, d)

    # gather back
    gathered = expert_out[bidx, buf_e.clip(0, e - 1), buf_p]  # (B, S*K, d)
    gathered = jnp.where(keep[..., None], gathered, 0.0)
    weights = top_p.reshape(b, s * k, 1).astype(gathered.dtype)
    out = (gathered * weights).reshape(b, s, k, d).sum(axis=2)

    # Switch-style load-balance auxiliary loss (over all tokens)
    me = probs.mean(axis=(0, 1))                              # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[flat_e.reshape(-1)].add(
        jnp.full((b * s * k,), 1.0 / (b * s * k)))            # token fraction
    aux = e * jnp.sum(me * ce) * moe.router_aux_coef

    return out, aux


def _moe_forward_shardmap(params: Params, cfg: ModelConfig, x: jax.Array,
                          policy) -> Tuple[jax.Array, jax.Array]:
    """Expert FFN with device-local dispatch under shard_map.

    Tokens arrive sharded (batch over data/pod, seq over model — the
    sequence-parallel residual layout); each device dispatches ITS tokens
    into a local (E, C_loc, d) buffer, runs the expert FFN on its d_ff
    shard of every expert, and psums the down-projection over ``model``.
    The only collectives are the weight all-gathers GSPMD already inserts
    for FSDP and one psum per layer — no partitioned scatters."""
    from jax.sharding import PartitionSpec as P

    mesh = policy.mesh
    moe = cfg.moe
    b, s, d = x.shape
    e = moe.num_experts
    baxes = None
    from repro.launch.sharding import batch_axes, _fits
    baxes = batch_axes(mesh, b)
    seq_ax = "model" if (policy.seq_parallel
                         and _fits(s, mesh, "model")) else None
    x_spec = P(baxes, seq_ax, None)
    model_axes = ("model",) if "model" in mesh.axis_names else ()
    all_axes = tuple(a for a in ("pod", "data", "model")
                     if a in mesh.axis_names)

    def local_fn(xl, router, wg, wu, wd):
        bl, sl, _ = xl.shape
        t = bl * sl
        k = moe.top_k
        cap = max(int(math.ceil(t * k / e * moe.capacity_factor)), k)
        xf = xl.reshape(t, d)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        flat_e = top_e.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
        flat_pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], 1)[:, 0]
        keep = flat_pos < cap
        buf_e = jnp.where(keep, flat_e, e)
        buf_p = jnp.where(keep, flat_pos, 0)
        tok_rep = jnp.repeat(xf, k, axis=0)
        buffers = jnp.zeros((e + 1, cap, d), xl.dtype)
        buffers = buffers.at[buf_e, buf_p].set(tok_rep, mode="drop")[:e]
        hidden = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buffers, wg))
        hidden = hidden * jnp.einsum("ecd,edf->ecf", buffers, wu)
        eout = jnp.einsum("ecf,efd->ecd", hidden, wd)
        if model_axes:
            eout = jax.lax.psum(eout, model_axes)   # partial d_ff shards
        gathered = eout[buf_e.clip(0, e - 1), buf_p]
        gathered = jnp.where(keep[:, None], gathered, 0.0)
        weights = top_p.reshape(-1)[:, None].astype(gathered.dtype)
        out = (gathered * weights).reshape(t, k, d).sum(1).reshape(bl, sl, d)
        # load-balance aux across ALL shards
        me = jax.lax.pmean(probs.mean(0), all_axes)
        ce = jnp.zeros((e,), jnp.float32).at[flat_e].add(
            jnp.full((t * k,), 1.0 / (t * k)))
        ce = jax.lax.pmean(ce, all_axes)
        aux = e * jnp.sum(me * ce) * moe.router_aux_coef
        return out, aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(), P(None, None, "model"),
                  P(None, None, "model"), P(None, "model", None)),
        out_specs=(x_spec, P()),
        check_vma=False)
    out, aux = fn(x, params["router"],
                  params["w_gate"].astype(x.dtype),
                  params["w_up"].astype(x.dtype),
                  params["w_down"].astype(x.dtype))
    return out, aux
