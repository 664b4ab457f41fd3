"""Operator kind ``service_spd``: the solver service's own dense operator.

The service makes the shared operator of a dense bucket itself, from seed
0, by its published ``init="spd"`` rule: ``m m^T / n + I`` with ``m``
standard normal from the generator keyed by ``sha256("0\\0A")``, computed
in float64 and rounded to the served type.  It cannot be given another, so
a served dense cell runs this one, whatever its seed; the seed only draws
the right-hand sides.  No cell of solves uses it, so it has no device
builder.  This file rebuilds the matrix from that recipe and
imports nothing of the program.  Eigenvalues lie in about ``[1, 5]``.
"""
from __future__ import annotations

import functools
import hashlib
from typing import Any, Callable, Mapping

import numpy as np

SERVED = True


def nnz(config: Mapping[str, Any]) -> int:
    n = int(config["params"]["n"])
    return n * n


def operator_bytes(config: Mapping[str, Any], itemsize: int) -> int:
    return nnz(config) * itemsize


@functools.lru_cache(maxsize=1)
def _matrix(n: int, dtype: str) -> np.ndarray:
    """Kept for the next call: it takes seconds to build, and callers only
    read it."""
    h = hashlib.sha256(b"0\0A").digest()
    m = np.random.default_rng(int.from_bytes(h[:8], "little")) \
        .standard_normal((n, n))
    return ((m @ m.T) / n + np.eye(n)).astype(dtype)


def _host(config: Mapping[str, Any]) -> np.ndarray:
    return _matrix(int(config["params"]["n"]), config["dtype"])


def reference_matvec(config: Mapping[str, Any], seed: int
                     ) -> Callable[[np.ndarray], np.ndarray]:
    return _host(config).astype(np.float64).__matmul__


def bf16_operator(config: Mapping[str, Any], seed: int):
    import jax.numpy as jnp
    return jnp.asarray(_host(config), jnp.bfloat16)


def bf16_rows_matvec(a, p):
    return p @ a.T
