"""ShapeDtypeStruct input specs + lowerable step functions for every
(architecture x input-shape) combination — no device allocation anywhere.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.launch import sharding as shardlib
from repro.models.transformer import Model
from repro.training.optim import AdamWConfig, AdamWState, init_adamw
from repro.training.train_step import make_train_step

Pytree = Any
SDS = jax.ShapeDtypeStruct


# --------------------------------------------------------------------------
# input specs
# --------------------------------------------------------------------------
def batch_input_specs(cfg: ModelConfig, shape: InputShape, *,
                      with_labels: bool, dtype=jnp.bfloat16) -> Dict[str, SDS]:
    b, s = shape.global_batch, shape.seq_len
    specs: Dict[str, SDS] = {"tokens": SDS((b, s), jnp.int32)}
    if with_labels:
        specs["labels"] = SDS((b, s), jnp.int32)
        specs["mask"] = SDS((b, s), dtype)
    if cfg.is_encdec:
        specs["frames"] = SDS((b, cfg.encoder_seq, cfg.d_model), dtype)
    if cfg.vision_tokens:
        specs["patches"] = SDS((b, cfg.vision_tokens, cfg.d_model), dtype)
    return specs


def input_specs(model: Model, shape: InputShape) -> Tuple[Pytree, ...]:
    """All example arguments (as ShapeDtypeStructs) for the shape's step."""
    cfg = model.cfg
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch = batch_input_specs(cfg, shape, with_labels=True)
        params = model.param_specs()
        opt = jax.eval_shape(init_adamw, params)
        return (params, opt, batch)
    if shape.kind == "prefill":
        batch = batch_input_specs(cfg, shape, with_labels=False)
        params = model.param_specs()
        caches = model.cache_specs(b, s)
        return (params, batch, caches)
    # decode: one token against a seq_len KV cache
    params = model.param_specs()
    caches = model.cache_specs(b, s)
    token = SDS((b, 1), jnp.int32)
    pos = SDS((), jnp.int32)
    return (params, token, caches, pos)


# --------------------------------------------------------------------------
# step functions
# --------------------------------------------------------------------------
def make_step_fn(model: Model, shape: InputShape,
                 opt_cfg: AdamWConfig = AdamWConfig(),
                 microbatches: int = 1) -> Callable:
    if shape.kind == "train":
        return make_train_step(model, opt_cfg, microbatches=microbatches)
    if shape.kind == "prefill":
        def prefill_step(params, batch, caches):
            x, exit_h, new_caches, _ = model.prefill(params, batch, caches)
            logits = model.logits(params, x[:, -1:])[:, 0]
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return tok, new_caches
        return prefill_step

    def serve_step(params, token, caches, pos):
        x, exit_h, new_caches, _ = model.decode_step(params, token, caches,
                                                     pos)
        logits = model.logits(params, x)[:, 0]
        # exit heads are first-class: confidence computed every step
        confs = {}
        for l, h in exit_h.items():
            xl = model.exit_logits(params, l, h)[:, 0].astype(jnp.float32)
            confs[l] = jnp.exp(jnp.max(xl, -1) - jax.nn.logsumexp(xl, -1))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return tok, confs, new_caches
    return serve_step


# --------------------------------------------------------------------------
# sharding trees for the step arguments
# --------------------------------------------------------------------------
def choose_fsdp(param_specs: Pytree, mesh, threshold_bytes=2 << 30) -> bool:
    per_dev = shardlib.estimate_param_bytes_per_device(param_specs, mesh,
                                                       fsdp=False)
    return per_dev > threshold_bytes


def arg_shardings(model: Model, shape: InputShape, mesh, args: Tuple,
                  fsdp: bool = None) -> Tuple:
    b = shape.global_batch
    params = args[0]
    if fsdp is None:
        fsdp = choose_fsdp(params, mesh)
    psh = shardlib.params_shardings(params, mesh, fsdp=fsdp)
    if shape.kind == "train":
        _, opt, batch = args
        opt_sh = AdamWState(
            step=shardlib.replicated(opt.step, mesh),
            mu=shardlib.params_shardings(opt.mu, mesh, fsdp=fsdp),
            nu=shardlib.params_shardings(opt.nu, mesh, fsdp=fsdp))
        bsh = shardlib.batch_shardings(batch, mesh, batch=b)
        return (psh, opt_sh, bsh)
    if shape.kind == "prefill":
        _, batch, caches = args
        bsh = shardlib.batch_shardings(batch, mesh, batch=b)
        csh = shardlib.cache_shardings(caches, mesh, batch=b)
        return (psh, bsh, csh)
    _, token, caches, pos = args
    tsh = shardlib.batch_shardings(token, mesh, batch=b)
    csh = shardlib.cache_shardings(caches, mesh, batch=b)
    possh = shardlib.replicated(pos, mesh)
    return (psh, tsh, csh, possh)
