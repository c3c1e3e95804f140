"""Mixture-of-Experts FFN with top-k routing.

Scatter/capacity ("dropped") implementation — the standard TPU-friendly
formulation: tokens are scattered into per-expert buffers of fixed capacity
``C = ceil(T * top_k / E * capacity_factor)``, each expert runs a dense
batched FFN over its buffer (ECd,Edf einsums -> MXU-shaped), and results are
gathered back with router-probability combine weights.  This keeps compute
proportional to *routed* tokens (the roofline honesty requirement) while
avoiding the (T,E,C) one-hot dispatch einsum whose memory is intractable.

Expert weights use gated-SiLU FFNs.  Auxiliary load-balance loss follows
Switch/OLMoE.
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
    assert cfg.moe is not None
    e, d, f = cfg.moe.num_experts, cfg.d_model, cfg.moe.expert_d_ff
    rngs = split_rngs(rng, 4)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(f)
    return {
        "router": dense_init(rngs[0], d, e, jnp.float32),  # router in fp32
        "w_gate": (jax.random.normal(rngs[1], (e, d, f)) * scale_in).astype(dtype),
        "w_up": (jax.random.normal(rngs[2], (e, d, f)) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(rngs[3], (e, f, d)) * scale_out).astype(dtype),
    }


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


def moe_forward_decode(params: Params, cfg: ModelConfig,
                       x: jax.Array) -> jax.Array:
    """Decode-time MoE for (B, 1, d): dense-gather formulation.

    With one token per row, the capacity machinery is overhead; gather the
    K expert weight slices per token instead (B*K is small at decode)."""
    moe = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    router_logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                               params["router"])
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, moe.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    wg = params["w_gate"].astype(x.dtype)[top_e]    # (T, K, d, f)
    wu = params["w_up"].astype(x.dtype)[top_e]
    wd = params["w_down"].astype(x.dtype)[top_e]
    h = jax.nn.silu(jnp.einsum("td,tkdf->tkf", xf, wg))
    h = h * jnp.einsum("td,tkdf->tkf", xf, wu)
    y = jnp.einsum("tkf,tkfd->tkd", h, wd)
    out = (y * top_p[..., None].astype(y.dtype)).sum(axis=1)
    return out.reshape(b, s, d)
