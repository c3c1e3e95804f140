"""Plain float32 reference of the CE-CoLLM served path, and its fp8 control.

The model is the one the configuration file states: token embedding,
``num_hidden_layers`` blocks of its architecture (the adapter's
``block``, ``bench.arch``), a final RMSNorm and an untied output head.
The two early exits read the residual after layers ``l_ee1`` and
``l_ee2`` through an RMSNorm of their own and the shared output head.

CE-CoLLM semantics that the reference follows:

* the edge partition (layers 1..l_ee2) sees every position;
* the cloud partition (layers l_ee1+1..L) continues from the l_ee1
  residual, and keeps keys and values only at the prompt's positions and
  at the decode positions whose token the cloud served: the content
  manager releases the other uploads (paper Algorithm 1), so those
  positions are gaps in the cloud's cache;
* a decode position's l_ee1 residual crosses the wire in the stated
  ``wire_format`` (float16); the prompt's is handed over as it is.

Everything is computed layer by layer with float32 matmuls at
``highest`` precision.  The control (``lowp=True``) rounds both operands
of every linear layer to float8 e4m3 with one scale per row or output
column (``mm``): the nearest precision below the served bfloat16.

Imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch
from bench import weights as W

HEADS = ("l1", "l2", "cloud")
_F8_MAX = 448.0


def q8(x: jax.Array, axis: int) -> jax.Array:
    """``x`` rounded to float8 e4m3 with one scale per slice along
    ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x: jax.Array, w: jax.Array, lowp: bool) -> jax.Array:
    """x (..., k) @ w (k, n): a linear layer, at fp8 in the control."""
    if lowp:
        x, w = q8(x, -1), q8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rms(x: jax.Array, delta: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + delta)


def w(cfg, key, name, layer):
    """Tensor ``name`` of ``layer`` as the reference computes with it: the
    served values, in float32."""
    return W.tensor(cfg, key, name, layer, W.served_dtype(cfg)).astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(cfg, key, tokens):
    return w(cfg, key, "embed", 0)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 2, 6))
def _head(cfg, key, norm, layer, h, tokens, lowp):
    """Per-position readings of one head over h (B, N, d): the best logit,
    the confidence (largest softmax probability), the argmax, and the
    logit of each token in ``tokens`` (T, B, N)."""
    eps = float(cfg["rms_norm_eps"])
    hn = rms(h, w(cfg, key, norm, layer), eps)
    lm = w(cfg, key, "lm_head", 0)
    if lowp:
        hn, lm = q8(hn, -1), q8(lm, -1)
    logits = jnp.einsum("bnd,vd->bnv", hn, lm,
                        precision=jax.lax.Precision.HIGHEST)
    best = jnp.max(logits, -1)
    conf = jnp.exp(best - jax.nn.logsumexp(logits, -1))
    at = jax.vmap(lambda t: jnp.take_along_axis(logits, t[..., None],
                                                -1)[..., 0])(tokens)
    return {"best": best, "conf": conf,
            "argmax": jnp.argmax(logits, -1).astype(jnp.int32), "at": at}


@jax.jit
def _take(read, h):
    return jnp.take_along_axis(h, read[..., None], 1)


def _rows(cfg, key, exits, n_layers, row, lowp):
    """Hidden states of one request (a batch of one) at the read positions:
    after l_ee1, after l_ee2, and after the last layer."""
    block = arch.of(cfg).block
    l1, l2 = exits
    allkeys = jnp.ones(row["tokens"].shape, bool)
    take = functools.partial(_take, row["read"])
    x = _embed(cfg, key, row["tokens"])
    at = {}
    for layer in range(l2):
        x = block(cfg, key, layer, x, allkeys, lowp)
        if layer + 1 == l1:
            h1 = x
        if layer + 1 in (l1, l2):
            at[layer + 1] = take(x)
    x = jnp.where(row["wire"][..., None],
                  h1.astype(jnp.float16).astype(jnp.float32), h1)
    del h1
    for layer in range(l1, n_layers):
        x = block(cfg, key, layer, x, row["cloud_keys"], lowp)
    at["final"] = take(x)
    return at


def readings(cfg: Dict, seed: int, exits, batch: Dict[str, np.ndarray], *,
             lowp: bool = False,
             tokens: Optional[Dict[str, np.ndarray]] = None,
             log=lambda m: None) -> Dict[str, Dict[str, np.ndarray]]:
    """Run the reference over a padded batch and read each head at the
    served positions.

    ``batch``: ``tokens`` (B, S) the prompt and served tokens fed back;
    ``cloud_keys`` (B, S) the positions the cloud holds keys for;
    ``wire`` (B, S) the positions whose l_ee1 residual crossed the wire;
    ``read`` (B, N) the positions whose next token was served.
    ``tokens`` maps a head to (T, B, N) token ids whose logits to read.
    Requests run one at a time (the TPU compiler has faulted on some
    batched shapes of these blocks).
    Returns {head: {"best", "conf", "argmax", "at"}} as numpy arrays."""
    cfg = arch.frozen(cfg)
    l1, l2 = exits
    n_layers = W.dims(cfg)["layers"]
    key = W.base_key(seed)
    names = ("tokens", "cloud_keys", "wire", "read")
    hidden = {l1: [], l2: [], "final": []}
    with jax.default_matmul_precision("highest"):
        for i in range(batch["tokens"].shape[0]):
            row = {k: jnp.asarray(batch[k][i:i + 1]) for k in names}
            at = _rows(cfg, key, exits, n_layers, row, lowp)
            for k in hidden:
                hidden[k].append(at[k])
        hidden = {k: jnp.concatenate(v) for k, v in hidden.items()}
        log(f"reference layers done for {batch['tokens'].shape[0]} requests")
        none = jnp.zeros((1,) + batch["read"].shape, jnp.int32)
        out = {}
        for head, (norm, layer, h) in {
                "l1": ("exit_norm", l1, hidden[l1]),
                "l2": ("exit_norm", l2, hidden[l2]),
                "cloud": ("final_norm", 0, hidden["final"])}.items():
            t = none if tokens is None else jnp.asarray(tokens[head])
            r = _head(cfg, key, norm, layer, h, t, lowp)
            out[head] = {k: np.asarray(v) for k, v in r.items()}
    return out
