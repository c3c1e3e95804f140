"""The DeepSeek-V2 adapter (``bench/arch/deepseek_v2.py``): the program's
prefill and latent-cache decode against the adapter's reference forward,
the chip configuration's counts, the refusal of a block count for its
non-uniform stack, and a tiny served run that every new reader reads."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _tiny
import _v2
from bench import arch, flops, harness, reference, spec, trace, weights
from bench.driver import build_params, model_config

CELL = "deepseek-v2-lite.collm.rag"
NEW_READERS = ("edge_step_ms.moe", "cloud_step_ms.moe", "prefill_ms.moe",
               "cloud_share.moe", "device_idle.moe", "edge_step_roofline.moe",
               "cloud_step_roofline.moe", "step_mfu.moe", "moe_roofline",
               "mla_decode_roofline")
# float32 logits of the width-64 model agree to ~1e-6 of their scale;
# computing in bfloat16 (same weights) moves them by ~1e-2 of it
TOL = 1e-4


@pytest.fixture(scope="module")
def float32_params():
    """The tiny configuration's program weights at float32; the bfloat16
    ones are the same values rounded, as ``bench.weights`` draws them."""
    from repro.models.transformer import Model
    key = weights.base_key(2 ** 33 + 3)
    model = Model(model_config(_v2.CONFIG), param_dtype=jnp.float32)
    return key, build_params(model, _v2.CONFIG, key)


def _program_logits(cfg, params, tokens, prompt):
    """The program's logits at positions prompt-1 .. len-2: a prefill of
    the prompt, then one decode step per fed-back token through the
    latent cache."""
    from repro.models.transformer import Model
    model = Model(model_config(cfg), param_dtype=weights.served_dtype(cfg))
    params = jax.tree.map(
        lambda x, spec: x.astype(model.param_dtype).astype(spec.dtype),
        params, model.param_specs())
    caches = model.init_cache(1, 32)
    x, caches = jax.jit(lambda p, t, c: model.prefill(
        p, {"tokens": t}, c)[::2])(params, tokens[:, :prompt], caches)
    out = [model.logits(params, x[:, -1:])[0, 0]]
    step = jax.jit(lambda p, t, c, pos: model.decode_step(p, t, c, pos))
    for i in range(prompt, tokens.shape[1] - 1):
        x, _, caches, _ = step(params, tokens[:, i:i + 1], caches,
                            jnp.asarray([i]))
        out.append(model.logits(params, x)[0, 0])
    return np.asarray(jnp.stack(out), np.float64)


def _reference_logits(cfg, key, tokens, prompt):
    """The adapter's float32 reference over the whole sequence, read by
    the final norm and the output head."""
    cfg = arch.frozen(cfg)
    a = arch.of(cfg)
    with jax.default_matmul_precision("highest"):
        x = reference._embed(cfg, key, tokens)
        keys = jnp.ones(tokens.shape, bool)
        for layer in range(cfg["num_hidden_layers"]):
            x = a.block(cfg, key, layer, x, keys, False)
        h = reference.rms(x, reference.w(cfg, key, "final_norm", 0),
                          float(cfg["rms_norm_eps"]))
        logits = h[0] @ reference.w(cfg, key, "lm_head", 0).T
    return np.asarray(logits[prompt - 1:tokens.shape[1] - 1], np.float64)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_latent_decode_matches_the_reference(dtype,
                                                          float32_params):
    """At float32 the program agrees with the reference on every logit;
    the same weights computed in bfloat16 do not, so the tolerance tells
    the stated precision from the next one below."""
    cfg = {**_v2.CONFIG, "torch_dtype": dtype}
    key, params = float32_params
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (1, 15)), jnp.int32)
    got = _program_logits(cfg, params, tokens, 9)
    want = _reference_logits(cfg, key, tokens, 9)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    if dtype == "float32":
        assert err <= TOL
    else:
        assert err > 10 * TOL


def test_the_chip_configuration_counts():
    """PERF.md's hand count: the embedding and head, 27 attentions, layer
    0's MLP, 26 expert layers of the router with 16 held and 2 shared
    experts, the norms; a token's latent over the 34 layer caches of the
    edge (layers 1-14) and the cloud (8-27)."""
    cfg = spec.cell(CELL)["config"]
    a = arch.of(cfg)
    m = a.dims(cfg)
    assert a.attn_params(m) == 13_763_072
    assert a.layer_params(m, 0) == 13_763_072 + 67_239_936 + 2 * 2048
    assert a.layer_params(m, 1) == 13_763_072 + 155_844_608 + 2 * 2048
    assert flops.count_params(cfg, (7, 14)) == 4_910_349_824
    assert (flops.kv_bytes_per_token(cfg, range(14))
            + flops.kv_bytes_per_token(cfg, range(7, 27))) == 39_168
    # a token's expected held share: 6 of 64 routed experts, 16 held
    assert a.moe_token_flops(m) == pytest.approx(
        2 * 2048 * 64 + 2 * 6 * 2048 * 1408 + 1.5 * 6 * 2048 * 1408)


@pytest.mark.parametrize("count", [
    lambda cfg: flops.step_work(cfg, 14, 2, [100]),
    lambda cfg: flops.prefill_flops(cfg, 7, 1, 50),
    lambda cfg: flops.kv_bytes_per_token(cfg, 14),
    lambda cfg: flops.count_params(cfg, (), layers=27)],
    ids=["step_work", "prefill_flops", "kv_bytes_per_token", "count_params"])
def test_a_count_of_blocks_is_refused(count):
    cfg = spec.cell(CELL)["config"]
    with pytest.raises(ValueError, match="blocks differ"):
        count(cfg)


# what a traced window's reduction and its scopes hand the readers (a CPU
# run has no device trace)
SUMMARY = {"window_s": 2.0, "busy_s": 1.5, "devices": 1,
           "programs": {"edge_step": {"count": 40, "seconds": 0.4},
                        "cloud_step_masked": {"count": 30, "seconds": 0.3},
                        "edge_prefill_padded": {"count": 9, "seconds": 0.09},
                        "cloud_prefill_padded": {"count": 9,
                                                 "seconds": 0.045}},
           "device_ops": [], "idle_gaps": []}
SCOPES = {"edge_step:mla": 0.1, "edge_step:moe": 0.2,
          "cloud_step_masked:mla": 0.08, "cloud_step_masked:moe": 0.15}


def test_a_tiny_run_is_correct_and_every_new_reader_reads(monkeypatch,
                                                          tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(trace, "reduce_dir",
                        lambda d: json.loads(json.dumps(SUMMARY)))
    monkeypatch.setattr(trace, "scopes", lambda path: dict(SCOPES))
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: None)
    c = _v2.cell()
    c["per_layer"] = list(NEW_READERS)
    out = harness.run(c, seed=2 ** 33 + 11, seconds=2.0, trace_on=True,
                      t_start=0.0, peaks=harness.peaks_for("TPU v5 lite"),
                      log=lambda m: None, clock=_tiny.Ticker())
    res, info = out["result"], out["info"]
    assert res["correct"], info
    assert {"l1", "l2", "cloud"} <= set(info["served_by"])
    assert set(res["metrics"]) == set(NEW_READERS)
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("reader", ["moe_roofline", "mla_decode_roofline"])
def test_kernel_rooflines_count_decode_calls_not_ticks(reader):
    """The two kernel rooflines charge the weights once per recorded call
    of a decode step: ticks that stamp tokens beyond the trace's calls
    spread the same rows over the calls and add no weight bytes.  The
    expert layers' reading is the calls' layers x the weights' bytes over
    HBM bandwidth, over the scope's seconds."""
    from types import SimpleNamespace
    cfg = spec.cell(CELL)["config"]
    peaks = harness.peaks_for("TPU v5 lite")
    calls = 10
    trace = {"programs": {"edge_step": {"count": calls, "seconds": 0.2},
                          "cloud_step_masked": {"count": calls,
                                                "seconds": 0.2}},
             "scopes": {"edge_step:moe": 0.06, "cloud_step_masked:moe": 0.09,
                        "edge_step:mla": 0.02,
                        "cloud_step_masked:mla": 0.03}}

    def reading(ticks, rows):
        run = harness.Run(
            cfg=cfg, cell={}, mix={}, seconds=1.0, setup_s=0.0, t0=0.0,
            t_end=1.0, records=[], trace=trace, peaks=peaks,
            ticks=[SimpleNamespace(edge_ctx=[500] * rows,
                                   cloud_ctx=[300] * (rows // 2))
                   for _ in range(ticks)])
        return spec.reader(reader).read(run)

    assert reading(13, 60) == pytest.approx(reading(10, 78))
    if reader == "moe_roofline":
        a = arch.of(cfg)
        m = a.dims(cfg)
        layers = a.moe_layers(m, range(14)) + a.moe_layers(m, range(7, 27))
        secs = a.moe_work(m, 1)[1] / peaks["hbm_bytes_per_s"]
        assert reading(13, 60) == pytest.approx(
            100.0 * calls * layers * secs / 0.15)
