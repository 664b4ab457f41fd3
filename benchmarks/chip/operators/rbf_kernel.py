"""Operator kind ``rbf_kernel``: an exact Gaussian-process kernel matrix.

``A = s2 exp(-|u_i - u_j|^2 / (2 l^2)) + noise I`` over ``n`` inputs
``u_i`` of ``inputs_dim`` coordinates, each drawn from the seed uniformly
on ``[-sqrt 3, sqrt 3]`` (standardized: zero mean, unit variance), with
``l`` = ``lengthscale``, ``s2`` = ``outputscale`` and ``noise`` =
``noise_variance`` from the configuration's ``operator``.  Made on the
device in one jitted call, symmetrized, in the type it is served in.

The solver service cannot be given an operator (it makes its own), so no
served cell can use this kind.  The reference reads back the matrix this
file makes, as float64.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Mapping

import numpy as np

import inputs

SERVED = False


def _shape(config: Mapping[str, Any]):
    op = config["operator"]
    return (int(config["params"]["n"]), int(op["inputs_dim"]),
            float(op["lengthscale"]), float(op["outputscale"]),
            float(op["noise_variance"]), config["dtype"])


def nnz(config: Mapping[str, Any]) -> int:
    n = int(config["params"]["n"])
    return n * n


def operator_bytes(config: Mapping[str, Any], itemsize: int) -> int:
    """A dense ``n x n`` matrix: every application reads all of it."""
    return nnz(config) * itemsize


@functools.lru_cache(maxsize=None)
def _builder(n: int, dim: int, lengthscale: float, outputscale: float,
             noise: float, dtype: str):
    import jax
    import jax.numpy as jnp
    half_width = math.sqrt(3.0)

    @jax.jit
    def build(key):
        u = jax.random.uniform(key, (n, dim), jnp.float32, -half_width,
                               half_width)
        sq = jnp.sum(u * u, axis=1)
        cross = jnp.matmul(u, u.T, precision=jax.lax.Precision.HIGHEST)
        d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * cross, 0.0)
        k = outputscale * jnp.exp(-d2 / (2.0 * lengthscale ** 2))
        a = 0.5 * (k + k.T) + noise * jnp.eye(n, dtype=jnp.float32)
        return {"A": a.astype(dtype)}
    return build


def build(config: Mapping[str, Any], seed: int) -> Dict[str, Any]:
    return _builder(*_shape(config))(inputs.device_key(seed, "operator"))


def reference_matvec(config: Mapping[str, Any], seed: int
                     ) -> Callable[[np.ndarray], np.ndarray]:
    a = np.asarray(build(config, seed)["A"], np.float64)
    return a.__matmul__


def bf16_operator(config: Mapping[str, Any], seed: int):
    import jax.numpy as jnp
    return build(config, seed)["A"].astype(jnp.bfloat16)


def bf16_rows_matvec(a, p):
    return p @ a.T
