"""Smoke test of the served path on a TPU: ee-llm-7b at its published
widths, through ``ServingSystem.generate``.

    python chip_smoke.py               # one chip: collm, standalone, cloud
    python chip_smoke.py --four-chips  # cloud partition on a (1, 4) mesh
                                       # against the same prompts on one chip

Weights are random, made from ``--seed``, in bfloat16 as the launcher
serves a published config; all 32 layers and every width are kept.  The
script runs in one process and never falls back to the CPU: without a TPU
it exits non-zero before touching the model.  Every number it prints is
a count, a byte size or a set-up time — never a speed.  The last line of
stdout is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every check passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "ee-llm-7b"
N_PROMPTS = 4
PROMPT_LEN = 16
MAX_NEW = 16
# An exit fires when its softmax confidence reaches θ; a probability
# never exceeds 1, so at θ = 1 nothing exits and every collm token is the
# cloud partition's — which must then agree with the undivided model.
THETA_NO_EXIT = 1.0
# Mean LCS-F1 (serve.py's token_agreement) a stream pair must reach.  The
# partitioned and the undivided programs round bf16 differently, and with
# random weights the top two logits are often closer than bf16 resolves,
# so one argmax flip can derail the rest of a greedy stream.  A broken
# partition or sharding agrees only by chance (~1/V per token), far below.
MIN_AGREEMENT = 0.5
# Largest |logit difference| over the largest |logit|, sharded against one
# device.  Four shards reduce bf16 partial sums in another order (a few
# bf16 ulps through 32 layers); a wrong head split or a missing all-reduce
# is off by the logits' own scale.
MAX_LOGIT_REL_DIFF = 0.1


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_prompts(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, PROMPT_LEN).astype(np.int32)
            for _ in range(N_PROMPTS)]


def prompt_logits(system, prompts) -> np.ndarray:
    """Undivided-model logits after each prompt, (N, V) float32, from the
    served jitted prefill step (on the cloud mesh when there is one)."""
    from repro.serving.mesh_exec import jit_step
    step = jit_step(system.collm, "full_prefill_padded")
    out = []
    for p in prompts:
        caches = system.model.init_cache(1, len(p))
        logits, _ = step(system.params, jnp.asarray(p[None]),
                         len(p), caches)
        out.append(np.asarray(logits, np.float32)[0, 0])
    return np.stack(out)


def _check_streams(tokens, vocab: int, mode: str) -> None:
    _check(len(tokens) == N_PROMPTS, f"{mode}: {len(tokens)} streams")
    for i, t in enumerate(tokens):
        _check(len(t) == MAX_NEW, f"{mode}: stream {i} has {len(t)} tokens, "
               f"want {MAX_NEW}")
        _check(all(0 <= x < vocab for x in t),
               f"{mode}: stream {i} has a token outside [0, {vocab})")


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def _agreement(a, b):
    from repro.serving.engine import token_agreement
    return [float(token_agreement(x, y)) for x, y in zip(a, b)]


def run_modes(*, smoke: bool = False, seed: int = 0, log=print) -> dict:
    """One device: build the served model, run ``generate`` in every mode
    on seeded prompts, and check streams, logits and collm/cloud
    agreement.  Returns the streams, logits and agreement."""
    from repro.core.collm import CollmConfig
    from repro.launch.serve import init_params, served_model
    from repro.serving.engine import ServingSystem

    t0 = time.perf_counter()
    model = served_model(ARCH, smoke)
    ccfg = CollmConfig(theta=THETA_NO_EXIT)
    params = jax.block_until_ready(init_params(model, ccfg, seed))
    vocab = model.cfg.vocab_size
    log(f"model: {model.cfg.name} layers={model.cfg.n_layers} "
        f"d={model.cfg.d_model} vocab={vocab} exits={model.cfg.exit_layers}"
        f" param_dtype={jnp.dtype(model.param_dtype).name}")
    log(f"param bytes: {sum(x.nbytes for x in jax.tree.leaves(params))}")
    log(f"set-up s (param init incl. compile): {time.perf_counter() - t0:.1f}")
    prompts = make_prompts(vocab, seed)
    system = ServingSystem(model, params, ccfg)
    out = {"tokens": {}}
    for mode in ("collm", "standalone", "cloud"):
        t = time.perf_counter()
        r = system.generate(prompts, MAX_NEW, mode=mode)
        st = r["stats"]
        _check_streams(r["tokens"], vocab, mode)
        log(f"{mode}: counts streams={len(r['tokens'])} tokens={st.tokens} "
            f"cloud_requests={st.cloud_requests} exits_l1={st.exits_l1} "
            f"exits_l2={st.exits_l2}; first-call s incl. compile "
            f"{time.perf_counter() - t:.1f}")
        out["tokens"][mode] = r["tokens"]
        if mode == "collm":
            _check(st.exits_l1 + st.exits_l2 == 0,
                   f"collm at theta={THETA_NO_EXIT} exited "
                   f"{st.exits_l1 + st.exits_l2} tokens")
    logits = prompt_logits(system, prompts)
    _check(logits.shape == (N_PROMPTS, vocab), f"logits {logits.shape}")
    _check(bool(np.isfinite(logits).all()), "non-finite logits")
    agree = _agreement(out["tokens"]["collm"], out["tokens"]["cloud"])
    log(f"collm vs cloud agreement (LCS-F1): {[round(a, 3) for a in agree]} "
        f"(min mean {MIN_AGREEMENT})")
    _check(float(np.mean(agree)) >= MIN_AGREEMENT,
           f"collm agrees with cloud at {np.mean(agree):.3f}")
    log(f"peak_bytes_in_use: {_peak_bytes(jax.devices()[0])}")
    out.update(logits=logits, agreement=agree)
    return out


def run_four_chips(*, smoke: bool = False, seed: int = 0, log=print) -> dict:
    """The cloud partition on a (1, 4) mesh (``CollmConfig.cloud_mesh``)
    against the same prompts on one device, in this process.  Checks that
    the params spread over all four devices and that streams and logits
    agree."""
    from repro.core.collm import CollmConfig
    from repro.launch.serve import init_params, served_model
    from repro.serving.engine import ServingSystem

    devs = jax.devices()
    _check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    model = served_model(ARCH, smoke)
    vocab = model.cfg.vocab_size
    prompts = make_prompts(vocab, seed)
    runs = {}
    # the mesh first, so devices 1-3 report the sharded run's peak alone;
    # each system is dropped before the next is built (two copies of the
    # params would not fit one chip)
    for name, mesh in (("mesh(1,4)", (1, 4)), ("one device", None)):
        t = time.perf_counter()
        ccfg = CollmConfig(theta=THETA_NO_EXIT, cloud_mesh=mesh)
        params = init_params(model, ccfg, seed)
        per_dev = {d: 0 for d in devs[:4]}
        for leaf in jax.tree.leaves(params):
            for sh in leaf.addressable_shards:
                per_dev[sh.device] = per_dev.get(sh.device, 0) + sh.data.nbytes
        total = sum(x.nbytes for x in jax.tree.leaves(params))
        log(f"{name}: param bytes total={total} per device="
            f"{[per_dev[d] for d in devs[:4]]}")
        if mesh is not None:
            _check(min(per_dev[d] for d in devs[:4]) > 0,
                   "a mesh device holds no params")
            _check(max(per_dev.values()) < total / 2,
                   "params did not spread over the mesh")
        system = ServingSystem(model, params, ccfg)
        r = system.generate(prompts, MAX_NEW, mode="collm")
        _check_streams(r["tokens"], vocab, name)
        logits = prompt_logits(system, prompts)
        _check(bool(np.isfinite(logits).all()), f"{name}: non-finite logits")
        log(f"{name}: counts tokens={r['stats'].tokens} cloud_requests="
            f"{r['stats'].cloud_requests}; s incl. compile "
            f"{time.perf_counter() - t:.1f}")
        log(f"{name}: peak_bytes_in_use per device="
            f"{[_peak_bytes(d) for d in devs[:4]]}")
        runs[name] = {"tokens": r["tokens"], "logits": logits}
        del system, params, r
        gc.collect()
    a, b = runs["mesh(1,4)"], runs["one device"]
    rel = float(np.max(np.abs(a["logits"] - b["logits"]))
                / np.max(np.abs(b["logits"])))
    agree = _agreement(a["tokens"], b["tokens"])
    log(f"mesh vs one device: logits max rel diff={rel:.6f} "
        f"(max {MAX_LOGIT_REL_DIFF}); streams LCS-F1="
        f"{[round(x, 3) for x in agree]} (min mean {MIN_AGREEMENT})")
    _check(rel <= MAX_LOGIT_REL_DIFF, f"logits differ by {rel:.4f}")
    _check(float(np.mean(agree)) >= MIN_AGREEMENT,
           f"mesh streams agree at {np.mean(agree):.3f}")
    return {"rel_diff": rel, "agreement": agree, **runs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1, 4) cloud-mesh path and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{info['platform']!r}); nothing was run", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {info['kind']} x{info['count']}; compile cache: "
          f"{enable_compile_cache()}", flush=True)
    log = lambda *a: print(*a, flush=True)  # noqa: E731
    try:
        if args.four_chips:
            run_four_chips(seed=args.seed, log=log)
        else:
            run_modes(seed=args.seed, log=log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
