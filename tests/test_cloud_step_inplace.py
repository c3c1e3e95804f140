"""The masked cloud step updates its dense KV in place.

Rows the mask leaves out drop their KV write at the scatter, so their
caches come back bit-for-bit; served rows match the old semantics (every
row written, then a per-row select back to the input).  The compiled step
aliases each dense cache leaf from input to output and holds no
whole-cache select or copy, and the jitted step donates its caches."""
import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, reduced
from repro.configs.registry import get_config
from repro.core.collm import CoLLM, CollmConfig
from repro.models.registry import build_model
from repro.serving.engine import ServingSystem
from repro.serving.mesh_exec import jit_step

B, MAX_SEQ = 5, 24


def _dense(**kw):
    return ModelConfig(name="ip-dense", arch_type="dense", n_layers=6,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab_size=128, tie_embeddings=True,
                       exit_layers=(1, 2), **kw).validate()


def _gla_mix():
    # zamba2 family: mamba2 (GLA state) layers with a shared attention
    # block every other layer; its cloud partition mixes both
    return dataclasses.replace(
        reduced(get_config("zamba2-1.2b"), n_layers=6, d_model=64,
                vocab=128), exit_layers=(1, 2)).validate()


CASES = {"dense": _dense, "ring": lambda: _dense(sliding_window=8),
         "gla_mix": _gla_mix}


def _random_caches(caches, key):
    """Random contents in every leaf (K/V, ring positions, state) so a
    row left as it was is told apart from one rewritten."""
    leaves, tree = jax.tree.flatten(caches)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, a in zip(keys, leaves):
        if jnp.issubdtype(a.dtype, jnp.integer):
            out.append(jax.random.randint(k, a.shape, -1, 40, a.dtype))
        else:
            out.append(jax.random.normal(k, a.shape, a.dtype))
    return jax.tree.unflatten(tree, out)


def _batch_axis(collm, si):
    return 0 if collm.model.segments[si].shared else 1


@pytest.mark.parametrize("case", sorted(CASES))
def test_masked_cloud_step_matches_old_semantics(case):
    cfg = CASES[case]()
    model = build_model(cfg)
    collm = CoLLM(model, CollmConfig())
    params = model.init(jax.random.PRNGKey(1))
    kc, kh, kp, km = jax.random.split(jax.random.PRNGKey(2), 4)
    caches = _random_caches(collm.init_cloud_cache(B, MAX_SEQ), kc)
    upload = {"data": jax.random.normal(kh, (B, 1, cfg.d_model),
                                        jnp.float16)}
    pos = jax.random.randint(kp, (B,), 0, 40, jnp.int32)
    mask = jax.random.bernoulli(km, 0.5, (B,)).at[0].set(True).at[1].set(
        False)

    logits, got = jax.jit(collm.cloud_step_masked)(params, upload, caches,
                                                   pos, mask)
    # old semantics: write every row, then select the masked rows back
    ref_logits, written = jax.jit(collm.cloud_step)(params, upload, caches,
                                                    pos)
    m = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(logits)[m],
                                  np.asarray(ref_logits)[m])
    for si in caches:
        ax = _batch_axis(collm, si)
        for path, old in jax.tree_util.tree_flatten_with_path(caches[si])[0]:
            new = np.asarray(_at(got[si], path))
            ref = np.asarray(_at(written[si], path))
            old = np.asarray(old)
            np.testing.assert_array_equal(
                np.compress(~m, new, ax), np.compress(~m, old, ax),
                err_msg=f"masked-off rows changed: seg {si} {path}")
            np.testing.assert_array_equal(
                np.compress(m, new, ax), np.compress(m, ref, ax),
                err_msg=f"served rows differ: seg {si} {path}")


def _at(tree, path):
    for p in path:
        tree = tree[p.key]
    return tree


def _ops_of_shapes(hlo: str, shapes) -> collections.Counter:
    """Ops of the optimized HLO whose result has one of ``shapes``."""
    cnt = collections.Counter()
    for m in re.finditer(r"= \w+\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(", hlo):
        if m.group(1) in shapes:
            cnt[m.group(2)] += 1
    return cnt


# op counts of edge_step's lowered text on _dense(), before the in-place
# masked path existed: the unmasked steps must trace exactly as they did
EDGE_STEP_OPS = {
    "abs": 2, "add": 51, "and": 6, "broadcast_in_dim": 187, "compare": 34,
    "concatenate": 10, "constant": 199, "convert": 8, "cosine": 4,
    "divide": 19, "dot_general": 20, "dynamic_slice": 26,
    "dynamic_update_slice": 6, "exponential": 7, "gather": 2, "iota": 7,
    "is_finite": 2, "log": 2, "maximum": 10, "multiply": 35, "negate": 1,
    "not": 2, "or": 4, "power": 4, "reduce": 17, "remainder": 1,
    "reshape": 40, "return": 11, "rsqrt": 6, "scatter": 12, "select": 24,
    "sine": 4, "slice": 12, "subtract": 10, "while": 2}


def test_compiled_masked_step_updates_caches_in_place():
    cfg = _dense()
    model = build_model(cfg)
    collm = CoLLM(model, CollmConfig())
    params = model.init(jax.random.PRNGKey(0))
    caches = collm.init_cloud_cache(B, MAX_SEQ)
    stacked = {",".join(map(str, a.shape)) for si, c in caches.items()
               if not model.segments[si].shared
               for a in jax.tree.leaves(c)}
    upload = {"data": jnp.zeros((B, 1, cfg.d_model), jnp.float16)}
    pos = jnp.arange(B, dtype=jnp.int32)
    mask = jnp.arange(B) % 2 == 0
    hlo = jit_step(collm, "cloud_step_masked").lower(
        params, upload, caches, pos, mask, None).compile().as_text()

    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
    assert aliases, "no cache leaf aliased from input to output"
    n_alias = len(re.findall(r"may-alias|must-alias", aliases.group(1)))
    assert n_alias == len(jax.tree.leaves(caches))
    ops = _ops_of_shapes(hlo, stacked)
    assert ops["select"] == 0 and ops["copy"] == 0, ops
    assert ops["dynamic-update-slice"] == 0, ops

    edge = jit_step(collm, "edge_step").lower(
        params, jnp.zeros((B, 1), jnp.int32),
        collm.init_edge_cache(B, MAX_SEQ), pos, None).as_text()
    counts = collections.Counter(re.findall(r"stablehlo\.(\w+)", edge))
    assert dict(counts) == EDGE_STEP_OPS


def test_jitted_masked_step_donates_its_caches():
    cfg = _dense()
    model = build_model(cfg)
    collm = CoLLM(model, CollmConfig())
    params = model.init(jax.random.PRNGKey(0))
    caches = collm.init_cloud_cache(B, MAX_SEQ)
    _, out = jit_step(collm, "cloud_step_masked")(
        params, {"data": jnp.zeros((B, 1, cfg.d_model), jnp.float16)},
        caches, jnp.arange(B, dtype=jnp.int32), jnp.ones((B,), bool), None)
    assert all(a.is_deleted() for a in jax.tree.leaves(caches))
    assert not any(a.is_deleted() for a in jax.tree.leaves(out))


@pytest.mark.parametrize("speculative", [False, True])
def test_dense_generate_keeps_its_tokens(tiny_trained, speculative):
    """The sync dense path (preemption off; and speculation at spec_k=1)
    gives the per-client loop's tokens; a read of a donated cache would
    raise instead."""
    model, params, data = (tiny_trained["model"], tiny_trained["params"],
                           tiny_trained["data"])
    prompts = [data.sample_tokens(n) for n in (8, 11, 9, 12)]
    ref = ServingSystem(model, params, CollmConfig(theta=0.8)
                        ).generate_sequential(prompts, 10, mode="collm")
    ccfg = CollmConfig(theta=0.8, speculative=speculative, spec_k=1)
    got = ServingSystem(model, params, ccfg).generate(
        prompts, 10, mode="collm", num_slots=3)
    assert got["tokens"] == ref["tokens"]
    assert got["stats"].cloud_requests > 0
