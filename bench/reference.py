"""Plain float32 reference of the CE-CoLLM served path, and its fp8 control.

The decoder is the one the configuration files state: token embedding,
``num_hidden_layers`` pre-RMSNorm blocks (rotary attention with grouped
KV heads and no bias, SwiGLU MLP), a final RMSNorm and an untied output
head.  The two early exits read the residual after layers ``l_ee1`` and
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
column: the nearest precision below the served bfloat16.

Imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HEADS = ("l1", "l2", "cloud")
_F8_MAX = 448.0


def _q8(x: jax.Array, axis: int) -> jax.Array:
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jax.Array, w: jax.Array, lowp: bool) -> jax.Array:
    """x (..., k) @ w (k, n)."""
    if lowp:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x: jax.Array, delta: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + delta)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x (B, S, H, D) at positions 0..S-1; rotates the two halves."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs     # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _w(cfg, key, name, layer):
    return W.tensor(cfg, key, name, layer).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _layer(cfg_items, key, layer, x, keymask, lowp):
    """One decoder block over (B, S, d).  Key ``k`` is visible to query
    ``q`` when ``k <= q`` and (``keymask[b, k]`` or ``k == q``)."""
    cfg = dict(cfg_items)
    m = W.dims(cfg)
    b, s, _ = x.shape
    g = m["h"] // m["kv"]
    eps = float(cfg["rms_norm_eps"])
    h = _rms(x, _w(cfg, key, "ln1", layer), eps)
    q = _mm(h, _w(cfg, key, "attn/wq", layer), lowp).reshape(
        b, s, m["h"], m["hd"])
    k = _mm(h, _w(cfg, key, "attn/wk", layer), lowp).reshape(
        b, s, m["kv"], m["hd"])
    v = _mm(h, _w(cfg, key, "attn/wv", layer), lowp).reshape(
        b, s, m["kv"], m["hd"])
    theta = float(cfg["rope_theta"])
    q, k = _rope(q, theta), _rope(k, theta)
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST)
    logits = logits / np.sqrt(m["hd"])
    idx = jnp.arange(s)
    causal = idx[None, :] <= idx[:, None]                      # (q, k)
    ok = causal[None] & (keymask[:, None, :] | jnp.eye(s, dtype=bool)[None])
    logits = jnp.where(ok[:, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(att.reshape(b, s, -1), _w(cfg, key, "attn/wo", layer), lowp)
    h2 = _rms(x, _w(cfg, key, "ln2", layer), eps)
    gate = jax.nn.silu(_mm(h2, _w(cfg, key, "mlp/w_gate", layer), lowp))
    up = _mm(h2, _w(cfg, key, "mlp/w_up", layer), lowp)
    return x + _mm(gate * up, _w(cfg, key, "mlp/w_down", layer), lowp)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, key, tokens):
    return _w(dict(cfg_items), key, "embed", 0)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 2, 6))
def _head(cfg_items, key, norm, layer, h, tokens, lowp):
    """Per-position readings of one head over h (B, N, d): the best logit,
    the confidence (largest softmax probability), the argmax, and the
    logit of each token in ``tokens`` (T, B, N)."""
    cfg = dict(cfg_items)
    eps = float(cfg["rms_norm_eps"])
    hn = _rms(h, _w(cfg, key, norm, layer), eps)
    lm = _w(cfg, key, "lm_head", 0)
    if lowp:
        hn, lm = _q8(hn, -1), _q8(lm, -1)
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


def _rows(items, key, exits, n_layers, row, lowp):
    """Hidden states of one request (a batch of one) at the read positions:
    after l_ee1, after l_ee2, and after the last layer."""
    l1, l2 = exits
    allkeys = jnp.ones(row["tokens"].shape, bool)
    take = functools.partial(_take, row["read"])
    x = _embed(items, key, row["tokens"])
    at = {}
    for layer in range(l2):
        x = _layer(items, key, layer, x, allkeys, lowp)
        if layer + 1 == l1:
            h1 = x
        if layer + 1 in (l1, l2):
            at[layer + 1] = take(x)
    x = jnp.where(row["wire"][..., None],
                  h1.astype(jnp.float16).astype(jnp.float32), h1)
    del h1
    for layer in range(l1, n_layers):
        x = _layer(items, key, layer, x, row["cloud_keys"], lowp)
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
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    l1, l2 = exits
    n_layers = W.dims(cfg)["layers"]
    key = W.base_key(seed)
    names = ("tokens", "cloud_keys", "wire", "read")
    hidden = {l1: [], l2: [], "final": []}
    with jax.default_matmul_precision("highest"):
        for i in range(batch["tokens"].shape[0]):
            row = {k: jnp.asarray(batch[k][i:i + 1]) for k in names}
            at = _rows(items, key, exits, n_layers, row, lowp)
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
            r = _head(items, key, norm, layer, h, t, lowp)
            out[head] = {k: np.asarray(v) for k, v in r.items()}
    return out
