"""Operations and bytes that the CE-CoLLM served path requires, from shapes.

Counts follow the configuration files and the work a request needs, not
what an implementation happens to compute: padding, rows of the pool that
hold no request and masked rows count nothing.  A block's parameters,
operations and cache come from the architecture's adapter
(``bench.arch``); this module adds the embedding, the output head, the
final and exit norms, and sums the blocks.

``layers`` is an explicit sequence of 0-based layers, or a count.  A
count says only how many blocks, which is enough where every block is
alike (the adapter's ``uniform``); for a stack whose blocks differ it
raises, since the edge's first blocks and the cloud's last ones are not
the same work there.

A matmul of an (m, k) by a (k, n) operand is 2mkn operations.  Bytes are
those a step must read or write at least once from HBM: every weight it
uses (the exit heads share the output head, which is read once), and
what each row's context holds in the cache at the served dtype.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Sequence, Tuple, Union

from bench import arch

BF16 = 2

Layers = Union[int, Sequence[int]]


def _layers(cfg: Dict, layers: Layers) -> Sequence[int]:
    if not isinstance(layers, int):
        return list(layers)
    if not arch.of(cfg).uniform(cfg):
        raise ValueError(f"{cfg['name']}: its blocks differ, so a count of "
                         f"{layers} names no layers; pass the layers")
    return range(layers)


def count_params(cfg: Dict, exits: Tuple[int, ...] = (),
                 layers: Optional[Layers] = None) -> int:
    """Every parameter of the served model: embedding, blocks (all, or
    ``layers``), final norm, output head and one norm per exit."""
    a = arch.of(cfg)
    m = a.dims(cfg)
    ls = range(m["layers"]) if layers is None else _layers(cfg, layers)
    return (2 * m["vocab"] * m["d"] + sum(a.layer_params(m, l) for l in ls)
            + m["d"] * (1 + len(exits)))


def kv_bytes_per_token(cfg: Dict, layers: Layers, itemsize: int = BF16
                       ) -> int:
    a = arch.of(cfg)
    m = a.dims(cfg)
    return sum(a.cache_bytes(m, l, itemsize) for l in _layers(cfg, layers))


def head_flops(cfg: Dict) -> float:
    m = arch.of(cfg).dims(cfg)
    return 2.0 * m["vocab"] * m["d"]


def step_work(cfg: Dict, layers: Layers, heads: int, ctxs: Iterable[int]
              ) -> Tuple[float, float]:
    """(operations, bytes) of one decode step over ``layers`` blocks and
    ``heads`` output heads (sharing one weight), for rows whose contexts
    are ``ctxs`` (keys each row attends over, its new one included)."""
    a = arch.of(cfg)
    m = a.dims(cfg)
    ls = _layers(cfg, layers)
    ctxs = list(ctxs)
    if not ctxs:
        return 0.0, 0.0
    head = heads * head_flops(cfg)
    flops = sum(sum(a.token_flops(m, l, c) for l in ls) + head
                for c in ctxs)
    weights = (sum(a.layer_params(m, l) for l in ls)
               + m["vocab"] * m["d"] + heads * m["d"]) * BF16
    kv = kv_bytes_per_token(cfg, ls) * sum(ctxs)
    return flops, float(weights + kv)


def prefill_flops(cfg: Dict, layers: Layers, heads: int, prompt: int
                  ) -> float:
    """Operations of a causal prefill of ``prompt`` tokens through
    ``layers`` blocks, with ``heads`` heads read at the last position."""
    a = arch.of(cfg)
    m = a.dims(cfg)
    return (sum(a.prompt_flops(m, l, prompt) for l in _layers(cfg, layers))
            + heads * head_flops(cfg))
