"""Operator kind ``laplacian5``: the 5-point Laplacian of a ``g x g`` grid.

Dirichlet boundaries, 4 on the diagonal and -1 per grid neighbour, rows in
grid order and each row's columns ascending, stored as CSR (``A.indptr``,
``A.indices``, ``A.data``).  It does not depend on the seed.  The solver
service makes the same matrix for a ``laplacian5`` workload, so a served
cell can use it.

The reference applies it as the stencil on the grid, which needs no CSR
at all (``tests/test_correctness.py`` ties the CSR the harness feeds to
that stencil).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Mapping

import numpy as np

#: the solver service makes this operator itself for the workload
SERVED = True

_INDEX_BYTES = 4


def _grid(n: int) -> int:
    g = math.isqrt(n)
    if g * g != n:
        raise ValueError(f"laplacian5 needs a square grid, n={n}")
    return g


def nnz(config: Mapping[str, Any]) -> int:
    """Every row has its diagonal and one entry per grid neighbour."""
    n = int(config["params"]["n"])
    return 5 * n - 4 * _grid(n)


def operator_bytes(config: Mapping[str, Any], itemsize: int) -> int:
    """Bytes one application reads of the operator: values, column
    indices and row pointers."""
    n = int(config["params"]["n"])
    return nnz(config) * (itemsize + _INDEX_BYTES) + (n + 1) * _INDEX_BYTES


@functools.lru_cache(maxsize=None)
def _builder(n: int, dtype: str):
    import jax
    import jax.numpy as jnp
    g = _grid(n)
    count = 5 * n - 4 * g

    @jax.jit
    def build():
        r = jnp.arange(n, dtype=jnp.int32)
        i, j = r // g, r % g
        offsets = jnp.array([-g, -1, 0, 1, g], jnp.int32)
        present = jnp.stack([i > 0, j > 0, jnp.ones(n, bool), j < g - 1,
                             i < g - 1], axis=1)
        (slot,) = jnp.nonzero(present.ravel(), size=count)
        cols = (r[:, None] + offsets[None, :]).ravel()[slot]
        vals = jnp.where(offsets == 0, 4.0, -1.0).astype(dtype)
        data = jnp.broadcast_to(vals, (n, 5)).ravel()[slot]
        indptr = jnp.concatenate([
            jnp.zeros(1, jnp.int32),
            jnp.cumsum(present.sum(axis=1, dtype=jnp.int32))])
        return {"A.indptr": indptr, "A.indices": cols.astype(jnp.int32),
                "A.data": data}
    return build


def build(config: Mapping[str, Any], seed: int) -> Dict[str, Any]:
    """The CSR leaves on the default device, in one jitted call."""
    return _builder(int(config["params"]["n"]), config["dtype"])()


def reference_matvec(config: Mapping[str, Any], seed: int
                     ) -> Callable[[np.ndarray], np.ndarray]:
    """``v -> A v`` in float64 on columns of ``(n,)`` or ``(n, k)``."""
    g = _grid(int(config["params"]["n"]))

    def mv(v):
        u = v.reshape((g, g) + v.shape[1:])
        out = 4.0 * u
        out[1:] -= u[:-1]
        out[:-1] -= u[1:]
        out[:, 1:] -= u[:, :-1]
        out[:, :-1] -= u[:, 1:]
        return out.reshape(v.shape)
    return mv


def bf16_operator(config: Mapping[str, Any], seed: int):
    """The stencil needs no stored operator."""
    return None


def bf16_rows_matvec(op, p):
    """The stencil applied to each row of ``p``, in ``p``'s precision."""
    g = int(round(p.shape[1] ** 0.5))
    u = p.reshape(p.shape[0], g, g)
    out = 4 * u
    out = out.at[:, 1:].add(-u[:, :-1]).at[:, :-1].add(-u[:, 1:])
    out = out.at[:, :, 1:].add(-u[:, :, :-1]).at[:, :, :-1].add(-u[:, :, 1:])
    return out.reshape(p.shape)
