"""Seeded weights of a dense pre-RMSNorm decoder, named tensor by tensor.

Each tensor is a function of (seed, name, layer) alone, so the plain
reference regenerates any layer by itself and the served model receives
the same values, stacked however the program lays them out.  Values are
uniform with the configuration's ``initializer_range`` as standard
deviation (integer bits to float: the same on every backend and in every
fusion), rounded to the served dtype.  Norm weights are stored as
deltas: the norm scales by ``1 + delta``.

Imports nothing of the program.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

NORM_STD = 0.05      # spread of the norm deltas around a scale of 1


def dims(cfg: Dict) -> Dict[str, int]:
    """The sizes the reference and the generator need, from a
    configuration file's published keys."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"d": d, "h": h, "kv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "ff": int(cfg["intermediate_size"]),
            "vocab": int(cfg["vocab_size"]),
            "layers": int(cfg["num_hidden_layers"])}


def shape_of(cfg: Dict, name: str) -> Tuple[int, ...]:
    m = dims(cfg)
    d, hq, hk = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"]
    return {"embed": (m["vocab"], d), "lm_head": (m["vocab"], d),
            "final_norm": (d,), "exit_norm": (d,),
            "attn/wq": (d, hq), "attn/wk": (d, hk), "attn/wv": (d, hk),
            "attn/wo": (hq, d), "mlp/w_gate": (d, m["ff"]),
            "mlp/w_up": (d, m["ff"]), "mlp/w_down": (m["ff"], d),
            "ln1": (d,), "ln2": (d,)}[name]


def _std(cfg: Dict, name: str) -> float:
    if name in ("final_norm", "exit_norm", "ln1", "ln2"):
        return NORM_STD
    return float(cfg["initializer_range"])


def base_key(seed: int) -> jax.Array:
    """The key all of a run's weights derive from; any seed up to 64 bits."""
    return jax.random.PRNGKey(seed)


def tensor(cfg: Dict, key: jax.Array, name: str, layer, dtype=jnp.bfloat16
           ) -> jax.Array:
    """Tensor ``name`` of ``layer`` (0-based; the exit layer for an exit
    norm, 0 for the other whole-model tensors), in ``dtype``, from the
    run's ``base_key``.  ``key`` and ``layer`` may be traced."""
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, layer)
    a = _std(cfg, name) * 3.0 ** 0.5
    x = jax.random.uniform(key, shape_of(cfg, name), jnp.float32, -a, a)
    return x.astype(dtype)
