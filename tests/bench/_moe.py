"""A CPU-sized cell of a sparse-expert architecture that the benchmark
does not carry: its adapter (``arch/tiny_moe.py``) and configuration live
here, and reach the harness only through the registry's search path."""
from __future__ import annotations

import contextlib
import pathlib
import time

import _tiny
from bench import arch, harness

ARCH = pathlib.Path(__file__).resolve().parent / "arch"

# served in float32: at bfloat16 the program's rounding moves some
# tokens' top-2 across a near tie, and the widest gap of a run then swings
# as far as the fp8 control's on some seeds (PERF.md, section 7)
CONFIG = {**_tiny.CONFIG, "name": "tiny-moe", "model_type": "tiny_moe",
          "num_local_experts": 4, "num_experts_per_tok": 2,
          "torch_dtype": "float32"}
CELL = {**_tiny.CELL, "config": "tiny-moe"}


@contextlib.contextmanager
def registered():
    """The registry searches this directory's ``arch/`` too."""
    saved = arch.SEARCH
    arch.SEARCH = saved + (ARCH,)
    try:
        yield
    finally:
        arch.SEARCH = saved


def cell() -> dict:
    return {**_tiny.cell(), "name": "tiny-moe", "config": dict(CONFIG),
            "cell": dict(CELL)}


def run(seed: int, seconds: float = 2.0, control: bool = False) -> dict:
    with registered():
        return harness.run(cell(), seed=seed, seconds=seconds,
                           trace_on=False, t_start=time.perf_counter(),
                           control=control, log=lambda m: None,
                           clock=_tiny.Ticker())
