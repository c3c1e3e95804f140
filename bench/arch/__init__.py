"""Architecture adapters: what in ``bench/`` depends on a model's block.

One module per architecture, ``<model_type>.py`` in a directory of
``SEARCH`` (this one first), found by the configuration's ``model_type``
(``llama`` where a configuration gives none) and loaded from its path,
as ``spec.reader`` loads a metric's reader.  Adding an architecture adds
a file; nothing here or in the generic harness changes.

An adapter provides, for a configuration ``cfg`` (the JSON object of its
file) and its sizes ``m = dims(cfg)``:

    dims(cfg)                  sizes; at least "d", "vocab" and "layers"
    model_config(cfg)          the program's ``ModelConfig``; the only
                               part that imports the program, lazily
    tensors(cfg)               {name: (shape, std)} of the blocks' seeded
                               tensors (``bench.weights`` draws them); a
                               kind of block that differs in shape has
                               names of its own
    LEAVES                     {program leaf path under a segment's layer
                               stack: tensor name}
    block(cfg, key, layer, x, keymask, lowp)
                               the float32 reference block over (B, S, d)
                               at ``highest`` precision; ``cfg`` is
                               hashable (``frozen``), ``lowp`` puts every
                               linear layer through ``reference.mm``'s
                               fp8 control; key ``k`` is visible to query
                               ``q`` when ``k <= q`` and (``keymask[b, k]``
                               or ``k == q``)
    uniform(cfg)               whether every block is alike, so that a
                               count of blocks says which they are
    layer_params(m, layer)     parameters of a block, all of which a
                               decode step is counted as reading
    token_flops(m, layer, ctx) operations of a block for one token over
                               ``ctx`` keys
    prompt_flops(m, layer, prompt)
                               operations of a block's causal prefill
    cache_bytes(m, layer, itemsize)
                               bytes the block caches per token

The reference side of an adapter (everything but ``model_config``)
imports nothing of the program.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Dict

from bench.spec import SpecError

HERE = pathlib.Path(__file__).resolve().parent
SEARCH = (HERE,)
DEFAULT = "llama"
_modules: Dict[pathlib.Path, object] = {}
_found: Dict[tuple, object] = {}


def load(model_type: str):
    """The adapter module of ``model_type``: the first
    ``<dir>/<model_type>.py`` over the directories of ``SEARCH``."""
    found = _found.get((SEARCH, model_type))
    if found is not None:
        return found
    tried = [d / f"{model_type}.py" for d in SEARCH]
    path = next((p for p in tried if p.is_file()), None)
    if path is None:
        raise SpecError(f"no architecture adapter for model_type "
                        f"{model_type!r}: looked for "
                        f"{', '.join(str(p) for p in tried)}")
    if path not in _modules:
        spec = importlib.util.spec_from_file_location(
            f"bench_arch_{model_type}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    _found[(SEARCH, model_type)] = _modules[path]
    return _modules[path]


def of(cfg: Dict):
    """The adapter of a configuration."""
    return load(cfg.get("model_type") or DEFAULT)


class frozen(dict):
    """A configuration as a static argument of ``jax.jit``: a dict that
    hashes by its JSON text."""

    def __hash__(self) -> int:
        return hash(json.dumps(self, sort_keys=True))
