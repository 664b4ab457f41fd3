"""Mesh construction (function, not module constant — importing this module
never touches jax device state)."""
from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Production mesh: 16×16 = 256 chips per pod; 2 pods when multi_pod.

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    DP runs over ("pod", "data"), TP/EP over "model"."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data * model} devices, have {n}")
    devs = jax.devices()[: data * model]
    import numpy as np
    return Mesh(np.array(devs).reshape(data, model), ("data", "model"))


@functools.lru_cache(maxsize=None)
def make_solver_mesh(n_shards: int, *, axis: str = "shards") -> Mesh:
    """1-D mesh for row-block sharded solver plans (``partition_plan``),
    over the first ``n_shards`` devices; built once per ``(n_shards,
    axis)`` and shared by every program of the process, so the shardings
    a plan hands out (``CompiledPlan.feed_shardings``) name the mesh its
    executable runs on.

    On CPU hosts the device count is 1 unless forced:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (how CI runs
    the distributed suite on one runner)."""
    devs = jax.devices()
    if n_shards > len(devs):
        raise ValueError(
            f"need {n_shards} devices for {n_shards} shards, have "
            f"{len(devs)} (on CPU, force more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards})")
    import numpy as np
    return Mesh(np.array(devs[:n_shards]), (axis,))
