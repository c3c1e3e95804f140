"""A CPU-sized cell for the benchmark's tests: the same harness, loop,
reference and check as the chip cells, on a 4-layer model of width 64."""
from __future__ import annotations

import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, spec  # noqa: E402

CONFIG = {
    "name": "tiny", "source": "test", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "initializer_range": 0.2,
    "torch_dtype": "bfloat16",
    "assumed": {"exit_layers": [1, 2], "wire_format": "float16"},
}
# theta puts about half of the tokens on the cloud at this width; the
# limits sit between the program's readings and the fp8 control's on the
# CPU (see test_bench_check.py)
CELL = {
    "config": "tiny", "traffic": "tiny", "mode": "collm", "theta": 0.119,
    "slots": 4, "max_seq": 64, "kv_layout": "dense",
    "calibrate": {"cloud_share": 0.5},
    "check": {"requests": 4,
              "limits": {"gap_exit": 0.03, "gap_cloud": 0.1,
                         "gate_miss": 0.08}},
}
MIX = {
    "loop": "open", "arrival": {"kind": "poisson", "rate": 20.0},
    "prompt_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                   "min": 4, "max": 30},
    "output_len": {"dist": "uniform", "min": 6, "max": 12},
    "sizes_seed": 1, "pool": 256,
}


def cell(**mix) -> dict:
    bm = spec.benchmark()
    return {"name": "tiny", "entry": {"chips": 1}, "cell": dict(CELL),
            "config": dict(CONFIG), "traffic": {**MIX, **mix},
            "end_to_end": [m["name"] for m in bm["end_to_end"]],
            "per_layer": [m["name"] for m in bm["per_layer"]],
            "units": {m["name"]: m["unit"]
                      for m in bm["end_to_end"] + bm["per_layer"]}}


class Ticker:
    """A clock that moves on by ``step`` seconds each time it is read.  On
    the host clock the batches a tiny run serves, and with them the bf16
    rounding the check reads, follow how busy the machine is; on this one
    a seed always serves the same batches."""

    def __init__(self, step: float = 2.5e-3):
        self.t, self.step = 0.0, step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


def run(seed: int = 2 ** 33 + 5, seconds: float = 2.0, trace: bool = False,
        control: bool = False, **mix) -> dict:
    return harness.run(cell(**mix), seed=seed, seconds=seconds,
                       trace_on=trace, t_start=time.perf_counter(),
                       control=control, log=lambda m: None, clock=Ticker())
