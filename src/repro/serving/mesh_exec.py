"""Mesh-aware serving execution (docs/sharding.md).

One ``MeshContext`` per ``CoLLM`` owns

  * the cloud ``Mesh`` built from ``CollmConfig.cloud_mesh`` (or no mesh
    at all — the single-device default), plus its ``ShardingPolicy``;
  * every jitted step wrapper the serving stack uses.  This absorbs the
    old per-CoLLM ``_jit`` memoization that lived in ``cloud_batcher``:
    the memoization is what guarantees N engines driving one CoLLM share
    a single trace per step, so it stays — but cloud-partition steps are
    now traced under the sharding policy, baking ``constrain_residual``
    / ``constrain_logits`` constraints into the compiled graph;
  * placement: params via role-based ``params_shardings`` and the pooled
    batch-major cloud KV via ``cache_shardings``.  jit then propagates
    ``NamedSharding``s from the committed inputs, and the activation
    constraints pin the interior (GSPMD fills in the rest).

With ``cloud_mesh=None`` (the default) there is no mesh, no policy and
no placement — ``jit_step`` degenerates to plain ``jax.jit`` (of the
step, with ``REPLY_TOKENS``' argmax added) and the single-device path is
byte-for-byte what it was before this layer existed.

This module must not import ``repro.serving.engine`` or
``repro.serving.cloud_batcher`` (they import us).
"""
from __future__ import annotations

import collections
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.launch import sharding as shardlib
from repro.launch.mesh import make_cloud_mesh

Pytree = Any

# Steps that run on the cloud partition: traced under the sharding
# policy so residual/logits constraints land in the jaxpr.  Edge-side
# steps stay policy-free — the mesh shards the *cloud* service; the edge
# is a different machine in the deployment this emulates.
CLOUD_STEPS = frozenset({
    "cloud_step", "cloud_step_masked",
    "ring_cloud_steps", "ring_cloud_steps_all",
    "cloud_prefill_padded", "cloud_prefill_chunk",
    "invalidate_rows_after",
    "full_step", "full_prefill_padded",      # mode="cloud" baseline
})


# Steps whose caches (argument 2) are donated: they update their KV in
# place, so the caller must take the returned caches and never read the
# ones it passed in again.
DONATES_CACHES = frozenset({"cloud_step_masked"})

# Steps that return ``(logits, caches)`` and whose jitted program returns
# ``(logits, tokens, caches)``: ``tokens`` is each row's argmax, the first
# maximum as ``np.argmax`` picks it, so a greedy reply drain fetches (B,)
# int32 and not (B, vocab) logits.  The program keeps the step's name.
REPLY_TOKENS = frozenset({"cloud_step_masked"})


def _with_reply_tokens(step: Callable) -> Callable:
    """``step`` with each row's greedy token added after its logits."""
    @functools.wraps(step)
    def stepped(*a, **kw):
        logits, caches = step(*a, **kw)
        with jax.named_scope("output_head"):
            tokens = jnp.argmax(logits, axis=-1)
        return logits, tokens, caches
    return stepped


class MeshContext:
    """Owns a cloud mesh + policy and the per-CoLLM jitted step cache."""

    def __init__(self, mesh=None, *, head_dim: int = 0):
        self.mesh = mesh
        self.head_dim = head_dim     # head-aligned attention sharding
        self.policy = (shardlib.ShardingPolicy(mesh, batch=1)
                       if mesh is not None else None)
        self._steps: Dict[str, Callable] = {}
        self._jitted: Dict[str, Callable] = {}   # underlying jax.jit objects
        # name -> number of times jax actually (re)traced the step; the
        # counter lives inside the traced python function, so cache hits
        # never bump it (bench/tests assert no re-trace per engine)
        self.trace_counts: collections.Counter = collections.Counter()

    @property
    def active(self) -> bool:
        return self.mesh is not None

    # -- jit ---------------------------------------------------------------
    def jit(self, name: str, fn: Callable) -> Callable:
        cached = self._steps.get(name)
        if cached is not None:
            return cached
        counts = self.trace_counts
        if name in REPLY_TOKENS:
            fn = _with_reply_tokens(fn)

        @functools.wraps(fn)
        def traced(*a, **kw):
            counts[name] += 1            # runs only while jax traces
            return fn(*a, **kw)

        jf = jax.jit(traced,
                     donate_argnums=(2,) if name in DONATES_CACHES else ())
        self._jitted[name] = jf
        if self.policy is not None and name in CLOUD_STEPS:
            policy = self.policy

            def stepped(*a, **kw):
                with shardlib.use_policy(policy):
                    return jf(*a, **kw)

            cached = stepped
        else:
            cached = jf
        self._steps[name] = cached
        return cached

    def jitted(self, name: str) -> Optional[Callable]:
        """Underlying ``jax.jit`` object (e.g. for ``.lower()``)."""
        return self._jitted.get(name)

    # -- placement ---------------------------------------------------------
    def params_shardings(self, specs: Pytree) -> Optional[Pytree]:
        """Role-based param shardings on the cloud mesh (None without
        one); ``specs`` may be shapes, so params can be initialized
        straight onto the mesh."""
        if not self.active:
            return None
        return shardlib.params_shardings(specs, self.mesh, fsdp=False,
                                         head_dim=self.head_dim)

    def shard_params(self, params: Pytree) -> Pytree:
        if not self.active:
            return params
        return jax.device_put(params, self.params_shardings(params))

    def shard_caches(self, caches: Pytree, *, batch: int) -> Pytree:
        if not self.active:
            return caches
        sh = shardlib.cache_shardings(caches, self.mesh, batch=batch)
        return jax.device_put(caches, sh)


def mesh_context(collm) -> MeshContext:
    """The CoLLM's MeshContext, built on first use from
    ``collm.ccfg.cloud_mesh`` and cached on the object (all engines and
    batchers of one CoLLM share it — and therefore share traces)."""
    mc = getattr(collm, "_mesh_ctx", None)
    if mc is None:
        spec = getattr(collm.ccfg, "cloud_mesh", None)
        mesh = make_cloud_mesh(spec) if spec is not None else None
        mc = collm._mesh_ctx = MeshContext(
            mesh, head_dim=collm.model.cfg.resolved_head_dim)
    return mc


def jit_step(collm, name: str) -> Callable:
    """Memoized jit of a bound CoLLM step method (the old ``_jit``)."""
    return mesh_context(collm).jit(name, getattr(collm, name))
