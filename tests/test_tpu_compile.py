"""Every Pallas kernel compiles for a TPU v5e chip at ee-llm-7b widths.

Interpret mode (tests/test_kernels.py) checks what the kernels compute;
only the TPU compiler checks what it accepts: (8, 128) block tiling,
VMEM size, Mosaic lowering.  These tests compile against a *described*
v5e topology — no chip is attached or needed — and assert the kernel
survived as a ``tpu_custom_call`` in the compiled HLO.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and pytest-xdist workers import every test file.  All compile tests live
in this one file so a single worker holds the library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.decode_attn.ops import flash_decode, flash_decode_paged
from repro.kernels.exit_head.ops import exit_confidence
from repro.kernels.exit_quant.ops import exit_quant
from repro.kernels.quantize.ops import quantize_int8

CFG = get_config("ee-llm-7b")
H, KV, D = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim
DM, V = CFG.d_model, CFG.vocab_size
B = 16                 # two 8-row blocks for the exit kernels
PAGE, N_LP = 16, 16    # CollmConfig.page_size; 256-token streams
PAGES = B * N_LP + 1   # + the trash page
S_RING = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:           # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_paged_decode_compiles(one_chip, kv_dtype):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    dt = jnp.dtype(kv_dtype)
    qdt = jnp.float32 if kv_dtype == "int8" else dt
    args = [s((B, H, D), qdt), s((PAGES, PAGE, KV, D), dt),
            s((PAGES, PAGE, KV, D), dt), s((PAGES, PAGE), jnp.int32),
            s((B, N_LP), jnp.int32), s((B,), jnp.int32)]
    if kv_dtype == "int8":
        scale = s((PAGES, PAGE, KV), jnp.float32)
        _compile(lambda q, k, v, p, t, c, ks, vs: flash_decode_paged(
            q, k, v, p, t, c, k_scale=ks, v_scale=vs), *args, scale, scale)
    else:
        _compile(flash_decode_paged, *args)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ring_decode_compiles(one_chip, dtype):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    dt = jnp.dtype(dtype)
    _compile(flash_decode, s((B, H, D), dt), s((B, S_RING, KV, D), dt),
             s((B, S_RING, KV, D), dt), s((B, S_RING), jnp.int32),
             s((), jnp.int32))


@pytest.mark.parametrize("kernel", [exit_confidence, exit_quant],
                         ids=["exit_head", "exit_quant"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_exit_kernels_compile(one_chip, kernel, dtype):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    dt = jnp.dtype(dtype)
    _compile(kernel, s((B, DM), dt), s((V, DM), dt), s((DM,), dt))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_quantize_compiles(one_chip, dtype):
    _compile(quantize_int8,
             jax.ShapeDtypeStruct((B, DM), jnp.dtype(dtype),
                                  sharding=one_chip))


@pytest.mark.parametrize("rows", [64, 1024], ids=["decode", "prefill"])
def test_served_moe_layer_compiles_to_grouped_matmuls(one_chip, rows,
                                                      monkeypatch):
    """DeepSeek-V2-Lite's expert layer (16 of 64 experts of 1408 held, top
    6, two shared) lowers its held experts to megablox's grouped-matmul
    kernel under the layer's ``moe`` scope, at a decode batch and a
    prefill bucket.  The layer picks the kernel by the default backend,
    which is the CPU here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses
    from repro.configs.base import MoEConfig
    from repro.models.moe import init_moe, moe_served
    moe = MoEConfig(num_experts=64, top_k=6, expert_d_ff=1408,
                    n_shared_experts=2, norm_topk_prob=False, held=(0, 16))
    cfg = dataclasses.replace(CFG, d_model=2048, arch_type="moe", moe=moe)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_moe(jax.random.PRNGKey(0), cfg,
                                        jnp.bfloat16)))
    x = jax.ShapeDtypeStruct((rows, 1, 2048), jnp.bfloat16, sharding=one_chip)

    def layer(p, h):
        with jax.named_scope("moe"):
            return moe_served(p, cfg, h)

    hlo = jax.jit(layer).lower(params, x).compile().as_text()
    assert 'moe/jit(gmm)/pallas_call"' in hlo


def _v2_steps_hlo(one_chip, step):
    """Compiled HLO of a CoLLM decode step for a v5e, of DeepSeek-V2-Lite's
    block at its widths (latent attention; expert layers holding 16 of 64
    experts of 1408, top 6, two shared) over seven layers, a dense first
    one, exits at 3 and 5: its edge and cloud steps each scan two stacks
    of two expert layers."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    from repro.core.collm import CoLLM, CollmConfig
    from repro.models.transformer import Model
    from repro.serving.mesh_exec import jit_step
    cfg = ModelConfig(
        name="v2-compile", arch_type="moe", n_layers=7, d_model=2048,
        n_heads=16, n_kv_heads=16, d_ff=10944, vocab_size=1024,
        norm_eps=1e-6,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=MoEConfig(num_experts=64, top_k=6, expert_d_ff=1408,
                      n_shared_experts=2, norm_topk_prob=False,
                      held=(0, 16), first_dense_layers=1),
        exit_layers=(3, 5)).validate()
    model = Model(cfg, param_dtype=jnp.bfloat16)
    collm = CoLLM(model, CollmConfig())
    rows, ctx = 8, 128

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pos = on_chip(jax.ShapeDtypeStruct((rows,), jnp.int32))
    if step == "edge_step":
        args = (on_chip(jax.ShapeDtypeStruct((rows, 1), jnp.int32)),
                on_chip(jax.eval_shape(
                    lambda: collm.init_edge_cache(rows, ctx))),
                pos, None)
    else:
        args = (on_chip({"data": jax.ShapeDtypeStruct(
                    (rows, 1, cfg.d_model), jnp.float16)}),
                on_chip(jax.eval_shape(
                    lambda: collm.init_cloud_cache(rows, ctx))),
                pos, on_chip(jax.ShapeDtypeStruct((rows,), jnp.bool_)),
                None)
    return jit_step(collm, step).lower(params, *args).compile().as_text()


@pytest.mark.parametrize("step", ["edge_step", "cloud_step_masked"])
def test_served_moe_steps_read_experts_in_place(one_chip, step, monkeypatch):
    """Both decode steps run every expert layer's grouped matmuls as the
    megablox kernel under the layer's ``moe`` scope, handed the whole
    stack of the segment's expert weights: no op but the step's own
    parameters holds one layer's held experts (16 x 2048 x 1408), so
    none is copied out of its stack.  The layer picks the kernel by the
    default backend, which is the CPU here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _v2_steps_hlo(one_chip, step)
    gmm = [ln for ln in hlo.splitlines()
           if "tpu_custom_call" in ln and "gmm" in ln]
    assert len(gmm) >= 6, "not three grouped matmuls a stack of layers"
    assert all("/moe/jit(gmm)/" in ln for ln in gmm), gmm
    one_layer = re.compile(r"= bf16\[(1,)?16,(2048,1408|1408,2048)\]"
                           r"\{[^}]*\} (?!parameter\()")
    assert not one_layer.findall(hlo), one_layer.findall(hlo)


def test_no_fallback_at_paper_widths():
    """The paper's vocabulary gets a real kernel tile, not the oracle."""
    from repro.kernels.exit_head.ops import exit_blocks
    assert exit_blocks(B, DM, V, 8, 512) == (8, 256)
