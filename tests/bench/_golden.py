"""Golden values of the benchmark's yardstick on the CPU: the seeded
weights (tensor by tensor, and as laid out into the program's parameter
tree), the reference's readings for a fixed batch, and one whole tiny
run's result with every metric reader's value.  They were written by
this module's ``main`` at the commit before the architecture-specific
parts of ``bench/`` moved behind adapters; ``test_bench_golden.py``
recomputes them and asks for exact equality.

    python3 tests/bench/_golden.py [path]       # writes golden.json

Only names that the harness had before the move are used here.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import _tiny
from bench import harness, reference, spec, trace, weights

PATH = pathlib.Path(__file__).resolve().parent / "golden.json"
SEED = 0
LAYER_NAMES = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/w_gate",
               "mlp/w_up", "mlp/w_down", "ln1", "ln2")
MODEL_NAMES = ("embed", "lm_head", "final_norm")
CHIP_CELLS = ("deepseek-llm-7b.collm.chat", "yi-6b.collm.summarize")
RUN_SEED = 2 ** 33 + 5
# what a traced window's reduction hands the readers (CPU runs have no
# device trace): every program a device reader looks for, called a few
# times, so that every reader finds something to read
SUMMARY = {"window_s": 2.0, "busy_s": 1.25, "devices": 1,
           "programs": {"edge_step": {"count": 40, "seconds": 0.4},
                        "cloud_step_masked": {"count": 30, "seconds": 0.3},
                        "edge_prefill_padded": {"count": 9, "seconds": 0.09},
                        "cloud_prefill_padded": {"count": 9,
                                                 "seconds": 0.045}},
           "device_ops": [["edge_step:fusion.1", 0.25]],
           "idle_gaps": [["tick", 0.5], ["wait", 0.25]]}


def digest(x) -> str:
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()


def tensors() -> dict:
    """sha256 of every tensor of the tiny configuration, and of the layer
    tensors of the first and last layers of the chip cells' models."""
    key = weights.base_key(SEED)
    out = {}
    cfg = _tiny.CONFIG
    for name in MODEL_NAMES:
        out[f"tiny:{name}:0"] = digest(weights.tensor(cfg, key, name, 0))
    for layer in cfg["assumed"]["exit_layers"]:
        out[f"tiny:exit_norm:{layer}"] = digest(
            weights.tensor(cfg, key, "exit_norm", layer))
    layers = [("tiny", cfg, range(cfg["num_hidden_layers"]))]
    for name in CHIP_CELLS:
        c = spec.cell(name)["config"]
        layers.append((c["name"], c, (0, c["num_hidden_layers"] - 1)))
    for model, c, ls in layers:
        for layer in ls:
            for name in LAYER_NAMES:
                out[f"{model}:{name}:{layer}"] = digest(
                    weights.tensor(c, key, name, layer))
    return out


def program_params() -> dict:
    """sha256 of every leaf of the tiny program's parameter tree."""
    from bench.driver import build_params, model_config
    from repro.models.transformer import Model
    cfg = _tiny.CONFIG
    model = Model(model_config(cfg), param_dtype=jnp.bfloat16)
    params = build_params(model, cfg, weights.base_key(SEED))
    return {jax.tree_util.keystr(p): digest(x) for p, x in
            jax.tree_util.tree_flatten_with_path(params)[0]}


def batch() -> tuple:
    """A fixed padded batch of three requests and the tokens to read."""
    rng = np.random.default_rng(5)
    b, s, n = 3, 40, 12
    out = {"tokens": rng.integers(0, 256, (b, s)).astype(np.int32),
           "cloud_keys": rng.random((b, s)) < 0.5,
           "wire": np.zeros((b, s), bool),
           "read": np.zeros((b, n), np.int32)}
    for i, p in enumerate((10, 17, 23)):
        out["cloud_keys"][i, :p] = True
        out["wire"][i, p:] = True
        out["read"][i] = p - 1 + np.arange(n)
    served = rng.integers(0, 256, (2, b, n)).astype(np.int32)
    return out, {h: served for h in reference.HEADS}


def readings() -> dict:
    b, tokens = batch()
    out = {}
    for lowp in (False, True):
        r = reference.readings(_tiny.CONFIG, RUN_SEED, (1, 2), b, lowp=lowp,
                               tokens=tokens)
        out[f"lowp={lowp}"] = {h: {k: v.tolist() for k, v in r[h].items()}
                               for h in sorted(r)}
    return out


def run(trace_dir, names=None) -> dict:
    """One tiny traced run with the control, on the ticking clock, that
    reads the metrics ``names`` (every metric of the benchmark where None);
    what the host clock times is left out."""
    c = _tiny.cell()
    c["per_layer"] = c["per_layer"] + c["end_to_end"]
    if names is not None:
        c["per_layer"] = list(names)
    saved = (harness.TRACE_DIR, trace.reduce_dir, jax.profiler.start_trace,
             jax.profiler.stop_trace)
    harness.TRACE_DIR = pathlib.Path(trace_dir)
    trace.reduce_dir = lambda d: json.loads(json.dumps(SUMMARY))
    jax.profiler.start_trace = jax.profiler.stop_trace = \
        lambda *a, **k: None
    try:
        out = harness.run(c, seed=RUN_SEED, seconds=2.0, trace_on=True,
                          t_start=time.perf_counter(), control=True,
                          peaks=harness.peaks_for("TPU v5 lite"),
                          clock=_tiny.Ticker())
    finally:
        (harness.TRACE_DIR, trace.reduce_dir, jax.profiler.start_trace,
         jax.profiler.stop_trace) = saved
    res, info = out["result"], out["info"]
    res["metrics"].pop("setup_s", None)
    res["device"].pop("memory_peak_bytes")
    for k in ("reference_s", "setup_phases_s"):
        info.pop(k)
    return json.loads(json.dumps({"result": res, "info": info}))


def main(argv) -> int:
    import tempfile
    path = pathlib.Path(argv[0]) if argv else PATH
    with tempfile.TemporaryDirectory() as d:
        out = {"tensors": tensors(), "program_params": program_params(),
               "readings": readings(), "run": run(d)}
    path.write_text(json.dumps(out, indent=1, sort_keys=True) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
