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


def test_no_fallback_at_paper_widths():
    """The paper's vocabulary gets a real kernel tile, not the oracle."""
    from repro.kernels.exit_head.ops import exit_blocks
    assert exit_blocks(B, DM, V, 8, 512) == (8, 256)
