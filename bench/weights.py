"""Seeded weights of a served model, named tensor by tensor.

Each tensor is a function of (seed, name, layer) alone, so the plain
reference regenerates any layer by itself and the served model receives
the same values, stacked however the program lays them out.  Values are
uniform with the given standard deviation (integer bits to float: the
same on every backend and in every fusion), rounded to the served dtype.
The whole-model tensors (embedding, output head, final and exit norms)
are named here; a block's names, shapes and deviations come from the
architecture's adapter (``bench.arch``).  Norm weights are stored as
deltas: the norm scales by ``1 + delta``.

Imports nothing of the program.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import arch

NORM_STD = 0.05      # spread of the norm deltas around a scale of 1
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def served_dtype(cfg: Dict):
    """The dtype the configuration's weights are served in."""
    return DTYPES[cfg["torch_dtype"]]


def dims(cfg: Dict) -> Dict[str, int]:
    """The sizes the reference and the generator need, from a
    configuration file's published keys (the architecture's adapter)."""
    return arch.of(cfg).dims(cfg)


def spec_of(cfg: Dict, name: str) -> Tuple[Tuple[int, ...], float]:
    """(shape, standard deviation) of tensor ``name``."""
    m = dims(cfg)
    if name in ("embed", "lm_head"):
        return (m["vocab"], m["d"]), float(cfg["initializer_range"])
    if name in ("final_norm", "exit_norm"):
        return (m["d"],), NORM_STD
    block = arch.of(cfg).tensors(cfg)
    if name not in block:
        raise ValueError(f"{cfg['name']} has no tensor {name!r}")
    return block[name]


def base_key(seed: int) -> jax.Array:
    """The key all of a run's weights derive from; any seed up to 64 bits."""
    return jax.random.PRNGKey(seed)


def tensor(cfg: Dict, key: jax.Array, name: str, layer, dtype=jnp.bfloat16
           ) -> jax.Array:
    """Tensor ``name`` of ``layer`` (0-based; the exit layer for an exit
    norm, 0 for the other whole-model tensors), in ``dtype``, from the
    run's ``base_key``.  ``key`` and ``layer`` may be traced."""
    shape, std = spec_of(cfg, name)
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, layer)
    a = std * 3.0 ** 0.5
    x = jax.random.uniform(key, shape, jnp.float32, -a, a)
    return x.astype(dtype)
