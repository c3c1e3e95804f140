"""Operations and bytes that the CE-CoLLM served path requires, from shapes.

Counts follow the configuration files (``weights.dims``) and the work a
request needs, not what an implementation happens to compute: padding,
rows of the pool that hold no request and masked rows count nothing.
Parameter arithmetic follows the program's ``roofline/analyze.count_params``
(a dense block is attention plus a gated MLP) and adds the norms, the exit
heads and the attention over the context.

A matmul of an (m, k) by a (k, n) operand is 2mkn operations.  Bytes are
those a step must read or write at least once from HBM: every weight it
uses (the exit heads share the output head, which is read once), and the
keys and values of each row's context at the served dtype.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from bench.weights import dims

BF16 = 2


def layer_matmul_params(m: Dict[str, int]) -> int:
    """Weights of one block that multiply an activation."""
    d, hd = m["d"], m["hd"]
    attn = d * hd * (m["h"] + 2 * m["kv"]) + m["h"] * hd * d
    return attn + 3 * d * m["ff"]


def count_params(cfg: Dict, exits: Tuple[int, ...] = ()) -> int:
    """Every parameter of the served model: embedding, blocks with their
    two norms, final norm, output head and one norm per exit."""
    m = dims(cfg)
    per_layer = layer_matmul_params(m) + 2 * m["d"]
    return (2 * m["vocab"] * m["d"] + m["layers"] * per_layer
            + m["d"] * (1 + len(exits)))


def kv_bytes_per_token(cfg: Dict, layers: int, itemsize: int = BF16) -> int:
    m = dims(cfg)
    return layers * 2 * m["kv"] * m["hd"] * itemsize


def _decode_layers(m: Dict[str, int], layers: int, ctx: int) -> float:
    """Operations of ``layers`` blocks for one token attending over ``ctx``
    keys (QK^T and PV: 4 * heads * head_dim per key)."""
    return layers * (2.0 * layer_matmul_params(m) + 4.0 * m["h"] * m["hd"]
                     * ctx)


def head_flops(cfg: Dict) -> float:
    m = dims(cfg)
    return 2.0 * m["vocab"] * m["d"]


def step_work(cfg: Dict, layers: int, heads: int, ctxs: Iterable[int]
              ) -> Tuple[float, float]:
    """(operations, bytes) of one decode step over ``layers`` blocks and
    ``heads`` output heads (sharing one weight), for rows whose contexts
    are ``ctxs`` (keys each row attends over, its new one included)."""
    m = dims(cfg)
    ctxs = list(ctxs)
    if not ctxs:
        return 0.0, 0.0
    flops = sum(_decode_layers(m, layers, c) + heads * head_flops(cfg)
                for c in ctxs)
    weights = (layers * (layer_matmul_params(m) + 2 * m["d"])
               + m["vocab"] * m["d"] + heads * m["d"]) * BF16
    kv = kv_bytes_per_token(cfg, layers) * sum(ctxs)
    return flops, float(weights + kv)


def prefill_flops(cfg: Dict, layers: int, heads: int, prompt: int) -> float:
    """Operations of a causal prefill of ``prompt`` tokens through
    ``layers`` blocks, with ``heads`` heads read at the last position."""
    m = dims(cfg)
    attn = 2.0 * m["h"] * m["hd"] * prompt * (prompt + 1)
    return (layers * (2.0 * layer_matmul_params(m) * prompt + attn)
            + heads * head_flops(cfg))
