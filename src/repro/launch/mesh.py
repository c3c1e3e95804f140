"""Production mesh construction (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state — ``jax.make_mesh`` is only called by launchers (dryrun.py sets
XLA_FLAGS for 512 host devices *before* any jax import)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the serving and dry-run
    code places arrays with ``NamedSharding`` and pins activations with
    ``with_sharding_constraint``, which refuses ``Explicit`` axes (the
    installed JAX's default)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_cloud_mesh(shape):
    """Cloud-service mesh from a ``CollmConfig.cloud_mesh`` pair.

    ``shape`` is a ``(data, model)`` device grid, e.g. ``(2, 4)``.  Fails
    loudly when the host exposes fewer devices than the grid needs — on a
    CPU dev box run with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    exported *before* python starts (jax reads it at import)."""
    dims = tuple(int(s) for s in shape)
    if len(dims) != 2 or any(d < 1 for d in dims):
        raise ValueError(f"cloud_mesh must be a (data, model) pair of "
                         f"positive ints, got {shape!r}")
    need, have = dims[0] * dims[1], len(jax.devices())
    if need > have:
        raise ValueError(
            f"cloud_mesh {dims} needs {need} devices but only {have} "
            f"visible; set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={need} before importing jax to emulate them")
    return _auto_mesh(dims, ("data", "model"))


def make_debug_mesh(n_devices: int = 1):
    """Tiny mesh over whatever devices exist (tests)."""
    n = min(n_devices, len(jax.devices()))
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return _auto_mesh((n // model, model), ("data", "model"))


def pod_submeshes(mesh):
    """Split a multi-pod mesh into per-pod ("data","model") meshes — the
    two-tier (edge pod / cloud pod) CE-CoLLM deployment."""
    import numpy as np
    from jax.sharding import Mesh
    devs = np.asarray(mesh.devices)
    assert "pod" in mesh.axis_names and devs.shape[0] >= 2
    edge = Mesh(devs[0], ("data", "model"))
    cloud = Mesh(devs[1], ("data", "model"))
    return edge, cloud
