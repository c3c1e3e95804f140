"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.decode_attn.ops import flash_decode, flash_decode_paged
from repro.kernels.decode_attn.ref import (decode_attn_paged_ref,
                                           decode_attn_ref)
from repro.kernels.exit_head.ops import exit_confidence
from repro.kernels.exit_head.ref import exit_head_ref
from repro.kernels.exit_quant.ops import exit_quant
from repro.kernels.exit_quant.ref import exit_quant_ref
from repro.kernels.quantize.ops import quantize_int8
from repro.kernels.quantize.ref import dequantize_int8_ref, quantize_int8_ref


# ---------------------------------------------------------------------------
# exit_head
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,d,v,bb,bv", [
    (8, 64, 512, 8, 128), (16, 128, 1024, 4, 256), (8, 256, 2048, 8, 512),
    (4, 128, 640, 4, 128), (32, 64, 4096, 16, 1024),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_exit_head_sweep(b, d, v, bb, bv, dtype):
    rng = jax.random.PRNGKey(b * d % 7)
    h = jax.random.normal(rng, (b, d)).astype(dtype)
    w = (jax.random.normal(jax.random.PRNGKey(1), (v, d)) * 0.05).astype(dtype)
    ns = jax.random.normal(jax.random.PRNGKey(2), (d,)) * 0.1
    c1, t1, l1 = exit_confidence(h, w, ns, block_b=bb, block_v=bv,
                                 interpret=True)
    c2, t2, l2 = exit_head_ref(h, w, ns)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=tol)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=tol, atol=tol)
    assert bool(jnp.all(t1 == t2))


def test_exit_head_confidence_bounds():
    # confidence is a probability
    rng = jax.random.PRNGKey(3)
    h = jax.random.normal(rng, (8, 64)) * 10
    w = jax.random.normal(jax.random.PRNGKey(4), (512, 64))
    c, t, l = exit_confidence(h, w, jnp.zeros(64), interpret=True)
    assert bool(jnp.all((c > 0) & (c <= 1.0 + 1e-6)))
    assert bool(jnp.all((t >= 0) & (t < 512)))


# ---------------------------------------------------------------------------
# decode_attn
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kv,d,s,bs,fill,window", [
    (2, 8, 2, 64, 1024, 256, 1000, 0),
    (1, 4, 4, 32, 512, 128, 512, 0),
    (2, 16, 2, 64, 2048, 512, 700, 256),
    (3, 6, 2, 128, 768, 256, 100, 0),
    (2, 8, 8, 64, 512, 512, 512, 64),
])
def test_decode_attn_sweep(b, h, kv, d, s, bs, fill, window):
    rng = jax.random.PRNGKey(fill % 11)
    q = jax.random.normal(rng, (b, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, d))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    pos = jnp.where(pos < fill, pos, -1)
    cur = jnp.asarray(fill - 1, jnp.int32)
    o1 = flash_decode(q, k, v, pos, cur, window=window, block_s=bs,
                      interpret=True)
    o2 = decode_attn_ref(q, k, v, pos, cur, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_decode_attn_dtypes(dtype):
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (2, 4, 64)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 2, 64)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 2, 64)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(256)[None], (2, 256))
    o1 = flash_decode(q, k, v, pos, jnp.asarray(255), block_s=128,
                      interpret=True)
    o2 = decode_attn_ref(q, k, v, pos, jnp.asarray(255))
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=3e-2)


# ---------------------------------------------------------------------------
# decode_attn, paged layout
# ---------------------------------------------------------------------------
def _paged_fixture(b, kvh, d, num_pages, ps, n_lp, seed, *, gaps=False):
    """Random page pool with per-row fills; returns jnp arrays + cur (B,)."""
    rng = np.random.RandomState(seed)
    kp = rng.randn(num_pages, ps, kvh, d).astype(np.float32)
    vp = rng.randn(num_pages, ps, kvh, d).astype(np.float32)
    pos = np.full((num_pages, ps), -1, np.int32)
    tbl = np.full((b, n_lp), -1, np.int32)
    cur = np.zeros((b,), np.int32)
    free = list(range(1, num_pages))
    for bi in range(b):
        fill = rng.randint(2, n_lp * ps)
        cur[bi] = fill - 1
        for lp in range(-(-fill // ps)):
            pg = free.pop()
            tbl[bi, lp] = pg
            n = min(ps, fill - lp * ps)
            pos[pg, :n] = np.arange(lp * ps, lp * ps + n)
            if gaps:      # release-mode: some positions were never written
                drop = rng.rand(n) < 0.3
                pos[pg, :n][drop] = -1
    return tuple(map(jnp.asarray, (kp, vp, pos, tbl, cur)))


@pytest.mark.parametrize("b,h,kv,d,pages,ps,n_lp,window", [
    (2, 8, 2, 64, 33, 16, 8, 0),
    (3, 4, 4, 32, 17, 8, 4, 0),
    (2, 16, 2, 64, 65, 32, 8, 48),
    (1, 6, 2, 128, 9, 16, 8, 0),
])
def test_decode_attn_paged_sweep(b, h, kv, d, pages, ps, n_lp, window):
    q = jnp.asarray(np.random.RandomState(7).randn(b, h, d), jnp.float32)
    kp, vp, pos, tbl, cur = _paged_fixture(b, kv, d, pages, ps, n_lp,
                                           seed=pages)
    o1 = flash_decode_paged(q, kp, vp, pos, tbl, cur, window=window,
                            interpret=True)
    o2 = decode_attn_paged_ref(q, kp, vp, pos, tbl, cur, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_decode_attn_paged_matches_dense_gather():
    """A fully-allocated identity-mapped page pool must reproduce the ring
    oracle exactly (same valid set, same logical order)."""
    b, h, kv, d, ps, n_lp = 2, 8, 2, 64, 16, 4
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
    s = n_lp * ps
    k = jnp.asarray(rng.randn(b, s, kv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kv, d), jnp.float32)
    fill = 50
    pos = jnp.where(jnp.arange(s)[None] < fill,
                    jnp.arange(s)[None], -1) + jnp.zeros((b, 1), jnp.int32)
    cur = jnp.asarray(fill - 1, jnp.int32)
    # identity paging: row b owns pages [1 + b*n_lp, ...)
    tbl = (1 + jnp.arange(b * n_lp, dtype=jnp.int32)).reshape(b, n_lp)
    kp = jnp.concatenate([jnp.zeros((1, ps, kv, d))] + [
        k[bi].reshape(n_lp, ps, kv, d) for bi in range(b)])
    vp = jnp.concatenate([jnp.zeros((1, ps, kv, d))] + [
        v[bi].reshape(n_lp, ps, kv, d) for bi in range(b)])
    posp = jnp.concatenate([jnp.full((1, ps), -1, jnp.int32)] + [
        pos[bi].reshape(n_lp, ps) for bi in range(b)])
    o_ring = decode_attn_ref(q, k, v, pos, cur)
    o_paged = flash_decode_paged(q, kp, vp, posp, tbl,
                                 jnp.broadcast_to(cur, (b,)), interpret=True)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_ring),
                               atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 16), gaps=st.booleans())
def test_decode_attn_paged_property(seed, gaps):
    """Property: kernel == oracle for random allocations, including
    release-mode gaps (pos = -1 holes inside allocated pages)."""
    b, h, kv, d, pages, ps, n_lp = 2, 4, 2, 32, 17, 8, 6
    q = jnp.asarray(np.random.RandomState(seed).randn(b, h, d), jnp.float32)
    kp, vp, pos, tbl, cur = _paged_fixture(b, kv, d, pages, ps, n_lp,
                                           seed=seed, gaps=gaps)
    o1 = flash_decode_paged(q, kp, vp, pos, tbl, cur, interpret=True)
    o2 = decode_attn_paged_ref(q, kp, vp, pos, tbl, cur)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


# ---------------------------------------------------------------------------
# decode_attn, paged layout, int8 pages (in-kernel dequant)
# ---------------------------------------------------------------------------
def _quantize_pool(kp, vp):
    """Per-(slot, kv_head)-row int8 quantization of a page pool — the same
    scaling the engine applies on page write."""
    from repro.models.attention import quantize_kv_rows
    qk, sk = quantize_kv_rows(kp)
    qv, sv = quantize_kv_rows(vp)
    return qk, qv, sk, sv


@pytest.mark.parametrize("b,h,kv,d,pages,ps,n_lp,window", [
    (2, 8, 2, 64, 33, 16, 8, 0),
    (3, 4, 4, 32, 17, 8, 4, 0),
    (2, 16, 2, 64, 65, 32, 8, 48),
])
def test_decode_attn_paged_int8_sweep(b, h, kv, d, pages, ps, n_lp, window):
    """int8 pages + in-kernel dequant == the gather-dequant oracle."""
    q = jnp.asarray(np.random.RandomState(11).randn(b, h, d), jnp.float32)
    kp, vp, pos, tbl, cur = _paged_fixture(b, kv, d, pages, ps, n_lp,
                                           seed=pages + 1)
    qk, qv, sk, sv = _quantize_pool(kp, vp)
    o1 = flash_decode_paged(q, qk, qv, pos, tbl, cur, k_scale=sk, v_scale=sv,
                            window=window, interpret=True)
    o2 = decode_attn_paged_ref(q, qk, qv, pos, tbl, cur, k_scale=sk,
                               v_scale=sv, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_decode_attn_paged_int8_close_to_f32():
    """Dequantized int8 attention stays near the float32 result — the
    per-row absmax quantizer bounds the K/V perturbation, so the softmax
    output moves by O(1/127), not O(1)."""
    b, h, kv, d, pages, ps, n_lp = 2, 8, 2, 64, 33, 16, 8
    q = jnp.asarray(np.random.RandomState(13).randn(b, h, d), jnp.float32)
    kp, vp, pos, tbl, cur = _paged_fixture(b, kv, d, pages, ps, n_lp, seed=5)
    qk, qv, sk, sv = _quantize_pool(kp, vp)
    o_f32 = flash_decode_paged(q, kp, vp, pos, tbl, cur, interpret=True)
    o_i8 = flash_decode_paged(q, qk, qv, pos, tbl, cur, k_scale=sk,
                              v_scale=sv, interpret=True)
    np.testing.assert_allclose(np.asarray(o_i8), np.asarray(o_f32),
                               atol=0.15)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), gaps=st.booleans())
def test_decode_attn_paged_int8_property(seed, gaps):
    """Property: int8 kernel == oracle for random allocations + gaps."""
    b, h, kv, d, pages, ps, n_lp = 2, 4, 2, 32, 17, 8, 6
    q = jnp.asarray(np.random.RandomState(seed).randn(b, h, d), jnp.float32)
    kp, vp, pos, tbl, cur = _paged_fixture(b, kv, d, pages, ps, n_lp,
                                           seed=seed, gaps=gaps)
    qk, qv, sk, sv = _quantize_pool(kp, vp)
    o1 = flash_decode_paged(q, qk, qv, pos, tbl, cur, k_scale=sk, v_scale=sv,
                            interpret=True)
    o2 = decode_attn_paged_ref(q, qk, qv, pos, tbl, cur, k_scale=sk,
                               v_scale=sv)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,d,bn", [(256, 128, 64), (128, 512, 128),
                                    (512, 64, 256)])
def test_quantize_sweep(n, d, bn):
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d)) * 5
    qa, sa = quantize_int8(x, block_n=bn, interpret=True)
    qb, sb = quantize_int8_ref(x)
    assert bool(jnp.all(qa == qb))
    np.testing.assert_allclose(np.asarray(sa), np.asarray(sb), rtol=1e-6)


@settings(max_examples=25, deadline=None)
@given(n=st.sampled_from([8, 32, 64]), d=st.sampled_from([16, 64, 128]),
       scale=st.floats(0.01, 100.0), seed=st.integers(0, 2 ** 16))
def test_quantize_roundtrip_property(n, d, scale, seed):
    """Property: int8 roundtrip error bounded by scale/127 per element."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d)) * scale
    q, s = quantize_int8(x, interpret=True)
    back = dequantize_int8_ref(q, s)
    bound = np.asarray(s) * 0.5 + 1e-9
    assert np.all(np.abs(np.asarray(back - x)) <= bound + 1e-6)


@settings(max_examples=20, deadline=None)
@given(b=st.sampled_from([4, 8]), v=st.sampled_from([256, 512]),
       seed=st.integers(0, 2 ** 16))
def test_exit_head_property(b, v, seed):
    """Property: kernel and oracle agree on confidence/argmax for random
    inputs; confidence equals softmax max prob."""
    d = 64
    h = jax.random.normal(jax.random.PRNGKey(seed), (b, d))
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (v, d)) * 0.1
    c1, t1, _ = exit_confidence(h, w, jnp.zeros(d), block_b=b, block_v=v // 2,
                                interpret=True)
    c2, t2, _ = exit_head_ref(h, w, jnp.zeros(d))
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-5)
    assert bool(jnp.all(t1 == t2))


# ---------------------------------------------------------------------------
# exit_quant (fused exit head + wire quantize)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,d,v,bb,bv", [
    (8, 64, 512, 8, 256),
    (16, 128, 1024, 8, 512),
    (4, 32, 256, 4, 128),
])
def test_exit_quant_sweep(b, d, v, bb, bv):
    h = jax.random.normal(jax.random.PRNGKey(b + v), (b, d)) * 3
    w = jax.random.normal(jax.random.PRNGKey(1), (v, d)) * 0.05
    ns = jax.random.normal(jax.random.PRNGKey(2), (d,)) * 0.1
    ker = exit_quant(h, w, ns, block_b=bb, block_v=bv, interpret=True)
    ref = exit_quant_ref(h, w, ns)
    np.testing.assert_allclose(np.asarray(ker[0]), np.asarray(ref[0]),
                               atol=1e-5)                       # confidence
    assert bool(jnp.all(ker[1] == ref[1]))                      # token
    np.testing.assert_allclose(np.asarray(ker[2]), np.asarray(ref[2]),
                               atol=1e-4)                       # logsumexp
    assert bool(jnp.all(ker[3] == ref[3]))                      # int8 data
    np.testing.assert_allclose(np.asarray(ker[4]), np.asarray(ref[4]),
                               rtol=1e-6)                       # scale


def test_exit_quant_ref_is_two_launch_composition():
    """The fused oracle == exit_head_ref + quantize_int8_ref verbatim (it
    must quantize the RAW pre-norm hidden, not the exit head's normalized
    view)."""
    b, d, v = 8, 64, 512
    h = jax.random.normal(jax.random.PRNGKey(9), (b, d)) * 2
    w = jax.random.normal(jax.random.PRNGKey(10), (v, d)) * 0.05
    ns = jnp.zeros((d,))
    conf, tok, lse, q, s = exit_quant_ref(h, w, ns)
    c2, t2, l2 = exit_head_ref(h, w, ns)
    q2, s2 = quantize_int8_ref(h)
    assert bool(jnp.all(tok == t2)) and bool(jnp.all(q == q2))
    np.testing.assert_allclose(np.asarray(conf), np.asarray(c2), rtol=1e-7)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), rtol=1e-7)


def test_exit_quant_fallback_on_indivisible_shapes():
    """A vocabulary no 128-multiple tile divides is refused with a clear
    error, never quietly served by the oracle; ``use_kernel=False`` is the
    explicit reference.  A B that fits one block is taken whole."""
    b, d, v = 5, 48, 300                    # 300 % 128 != 0
    h = jax.random.normal(jax.random.PRNGKey(3), (b, d))
    w = jax.random.normal(jax.random.PRNGKey(4), (v, d)) * 0.05
    ns = jnp.zeros((d,))
    with pytest.raises(ValueError, match="V=300"):
        exit_quant(h, w, ns, block_b=4, block_v=128, interpret=True)
    out = exit_quant(h, w, ns, use_kernel=False)
    ref = exit_quant_ref(h, w, ns)
    for a, r in zip(out, ref):
        assert a.shape == r.shape and a.dtype == r.dtype
        assert bool(jnp.all(a == r))
    ker = exit_quant(h, w[:256], ns, block_b=4, block_v=128, interpret=True)
    ref = exit_quant_ref(h, w[:256], ns)
    assert bool(jnp.all(ker[1] == ref[1])) and bool(jnp.all(ker[3] == ref[3]))


@settings(max_examples=15, deadline=None)
@given(b=st.sampled_from([4, 8]), v=st.sampled_from([256, 512]),
       seed=st.integers(0, 2 ** 16))
def test_exit_quant_property(b, v, seed):
    """Property: fused kernel agrees with BOTH unfused kernels on random
    inputs — exit decision with exit_head, packet with quantize."""
    d = 64
    h = jax.random.normal(jax.random.PRNGKey(seed), (b, d)) * 4
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (v, d)) * 0.1
    conf, tok, _, q, s = exit_quant(h, w, jnp.zeros(d), block_b=b,
                                    block_v=v // 2, interpret=True)
    c2, t2, _ = exit_head_ref(h, w, jnp.zeros(d))
    q2, s2 = quantize_int8_ref(h)
    np.testing.assert_allclose(np.asarray(conf), np.asarray(c2), atol=1e-5)
    assert bool(jnp.all(tok == t2)) and bool(jnp.all(q == q2))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), rtol=1e-6)


def test_fused_exit_upload_matches_edge_step_decision():
    """CoLLM.fused_exit_upload == evaluate_exit(exit_logits) + the
    transport int8 quantizer, packet layout included."""
    from repro.configs.base import ModelConfig
    from repro.core.collm import CoLLM, CollmConfig
    from repro.core.exits import evaluate_exit
    from repro.core.transport import dequantize, quantize
    from repro.models.registry import build_model

    cfg = ModelConfig(name="tiny-ee", arch_type="dense", n_layers=4,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=128, tie_embeddings=True,
                      exit_layers=(1, 2)).validate()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    collm = CoLLM(model, CollmConfig(theta=0.8))
    hid = jax.random.normal(jax.random.PRNGKey(5), (3, 1, cfg.d_model))
    for use_kernel in (False, True):
        conf, tok, pkt = collm.fused_exit_upload(params, hid,
                                                 use_kernel=use_kernel,
                                                 interpret=True)
        dec = evaluate_exit(model.exit_logits(params, collm.l_ee1, hid))
        ref_pkt = quantize(hid, "int8")
        np.testing.assert_allclose(np.asarray(conf),
                                   np.asarray(dec.confidence.reshape(-1)),
                                   atol=1e-5)
        assert bool(jnp.all(tok == dec.token.reshape(-1)))
        assert pkt["data"].shape == ref_pkt["data"].shape
        assert pkt["scale"].shape == ref_pkt["scale"].shape
        assert bool(jnp.all(pkt["data"] == ref_pkt["data"]))
        np.testing.assert_allclose(np.asarray(pkt["scale"]),
                                   np.asarray(ref_pkt["scale"]), rtol=1e-6)
        # the packet opens through the standard transport dequantizer
        back = dequantize(pkt)
        assert back.shape == hid.shape
