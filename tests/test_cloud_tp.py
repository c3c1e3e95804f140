"""Cloud tensor-parallel serving (docs/sharding.md).

Acceptance matrix for the mesh-aware execution layer: on a forced
8-host-device ``(data=2, model=4)`` mesh, sharded cloud steps must be
token-identical to the single-device path across {dense, paged} x
{f32, int8} x {spec_k 1, 4}, plus prefix sharing and preemption — and
N engines driving one CoLLM must never re-trace a step.

Run the multi-device tests with

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest -q tests/test_cloud_tp.py

(they skip on fewer than 8 devices; the single-device-default tests run
anywhere).
"""
import re

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.collm import CollmConfig
from repro.launch import sharding as shardlib
from repro.models.registry import build_model
from repro.serving.engine import ServingSystem
from repro.serving.mesh_exec import jit_step, mesh_context

CLOUD_MESH = (2, 4)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def tp():
    # untrained tiny GQA model: 4 heads shard over model=4, 2 KV heads
    # exercise the head-aligned replication rule
    cfg = ModelConfig(name="tiny-ee-tp", arch_type="dense", n_layers=4,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab_size=256, tie_embeddings=True,
                      exit_layers=(1, 2)).validate()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=n).astype(np.int32)
               for n in (7, 12, 9)]
    return {"cfg": cfg, "model": model, "params": params,
            "prompts": prompts, "rng": rng}


def _system(tp, **ckw):
    return ServingSystem(tp["model"], tp["params"],
                         CollmConfig(theta=0.85, **ckw))


# ---------------------------------------------------------------------------
# token identity: sharded cloud steps == single device
# ---------------------------------------------------------------------------
@needs_mesh
@pytest.mark.parametrize("ckw", [
    {},                                                       # dense f32
    {"kv_layout": "paged"},                                   # paged f32
    {"kv_layout": "paged", "kv_dtype": "int8"},               # int8 pages
    {"speculative": True, "spec_k": 4},                       # drafts
    {"kv_layout": "paged", "kv_dtype": "int8",
     "speculative": True, "spec_k": 4},                       # everything
], ids=["dense", "paged", "int8", "spec4", "int8-spec4"])
def test_tp_generate_multi_token_identity(tp, ckw):
    r0 = _system(tp, **ckw).generate_multi(tp["prompts"], 8)
    r1 = _system(tp, cloud_mesh=CLOUD_MESH, **ckw).generate_multi(
        tp["prompts"], 8)
    assert r1["tokens"] == r0["tokens"]


@needs_mesh
def test_tp_prefix_share_token_identity(tp):
    ps = 8
    rng = np.random.default_rng(7)
    sysp = rng.integers(0, 256, size=2 * ps + ps // 2).astype(np.int32)
    prompts = [np.concatenate(
        [sysp, rng.integers(0, 256, size=n).astype(np.int32)])
        for n in (5, 7)]
    ckw = dict(kv_layout="paged", page_size=ps, chunked_prefill=True,
               prefix_share=True)
    r0 = _system(tp, **ckw).generate(prompts, 8)
    r1 = _system(tp, cloud_mesh=CLOUD_MESH, **ckw).generate(prompts, 8)
    assert r1["tokens"] == r0["tokens"]
    assert r1["stats"].prefix_hit_tokens > 0
    assert r1["stats"].prefix_hit_tokens == r0["stats"].prefix_hit_tokens


@needs_mesh
def test_tp_preemption_token_identity(tp):
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, size=n).astype(np.int32) for n in (7, 9)]
    ckw = dict(kv_layout="paged", preemption="recompute")
    r0 = _system(tp, **ckw).generate(prompts, 8, num_slots=2,
                                     preempt_schedule=[(2, 0)])
    r1 = _system(tp, cloud_mesh=CLOUD_MESH, **ckw).generate(
        prompts, 8, num_slots=2, preempt_schedule=[(2, 0)])
    assert r1["tokens"] == r0["tokens"]
    assert r1["stats"].preemptions == 1


# ---------------------------------------------------------------------------
# trace discipline: one trace per step per CoLLM, stable across runs
# ---------------------------------------------------------------------------
@needs_mesh
def test_tp_no_retrace_across_runs(tp):
    sys_tp = _system(tp, cloud_mesh=CLOUD_MESH)
    r1 = sys_tp.generate_multi(tp["prompts"], 8)
    mc = mesh_context(sys_tp.collm)
    first = dict(mc.trace_counts)
    assert first.get("cloud_step_masked") == 1
    r2 = sys_tp.generate_multi(tp["prompts"], 8)
    assert dict(mc.trace_counts) == first    # second fleet: zero new traces
    assert r2["tokens"] == r1["tokens"]


# ---------------------------------------------------------------------------
# placement: per-device param bytes match the analytic estimate
# ---------------------------------------------------------------------------
@needs_mesh
def test_tp_param_bytes_shrink(tp):
    sys_tp = _system(tp, cloud_mesh=CLOUD_MESH)
    mc = mesh_context(sys_tp.collm)
    assert mc.active and dict(mc.mesh.shape) == {"data": CLOUD_MESH[0],
                                                 "model": CLOUD_MESH[1]}
    dev0 = mc.mesh.devices.flat[0]
    actual = sum(s.data.nbytes
                 for l in jax.tree.leaves(sys_tp.params)
                 for s in l.addressable_shards if s.device == dev0)
    est = shardlib.estimate_param_bytes_per_device(
        tp["model"].param_specs(), mc.mesh, fsdp=False,
        head_dim=tp["cfg"].resolved_head_dim)
    total = sum(l.size * l.dtype.itemsize
                for l in jax.tree.leaves(tp["params"]))
    assert actual == pytest.approx(est, rel=1e-6)
    # most weight is model-axis sharded (wk/wv + norms replicate)
    assert actual < 0.6 * total


@needs_mesh
def test_tp_masked_cloud_step_aliases_its_sharded_caches(tp):
    """On the mesh the donated cloud caches come back with the shardings
    they went in with, so every leaf is updated in place."""
    sys_tp = _system(tp, cloud_mesh=CLOUD_MESH)
    collm, mc = sys_tp.collm, mesh_context(sys_tp.collm)
    b, d = 4, tp["cfg"].d_model
    caches = mc.shard_caches(collm.init_cloud_cache(b, 32), batch=b)
    jit_step(collm, "cloud_step_masked")
    with shardlib.use_policy(mc.policy):
        hlo = mc.jitted("cloud_step_masked").lower(
            sys_tp.params, {"data": np.zeros((b, 1, d), np.float16)},
            caches, np.arange(b, dtype=np.int32), np.ones((b,), bool),
            None).compile().as_text()
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
    assert aliases is not None
    assert len(re.findall(r"may-alias|must-alias", aliases.group(1))) == \
        len(jax.tree.leaves(caches))


# ---------------------------------------------------------------------------
# single-device default stays zero-cost; config validation fails loudly
# ---------------------------------------------------------------------------
def test_single_device_default_is_inert(tp):
    sys_ = _system(tp)
    mc = mesh_context(sys_.collm)
    assert not mc.active
    assert mc.policy is None
    assert sys_.params is tp["params"]       # no device_put, no copy


def test_cloud_mesh_too_many_devices_raises(tp):
    with pytest.raises(ValueError, match="device_count"):
        _system(tp, cloud_mesh=(64, 64))


def test_cloud_mesh_bad_shape_raises(tp):
    with pytest.raises(ValueError, match="pair"):
        _system(tp, cloud_mesh=(0, 4))
