"""Operator kind ``rbf_kernel_sharded``: the exact-GP kernel matrix of
``rbf_kernel``, made in place in row blocks over a mesh of chips.

``A = s2 exp(-|u_i - u_j|^2 / (2 l^2)) + noise I`` over ``n`` inputs
``u_i`` of ``inputs_dim`` coordinates, drawn from the seed as
``rbf_kernel`` draws them (uniform on ``[-sqrt 3, sqrt 3]``), replicated
on every chip.  Each of the configuration's ``mesh`` chips computes its
own ``(n / mesh, n)`` row block, ``CHUNK_ROWS`` rows at a time into the
block, so the build needs little more than the block itself.  The squared
distance is the sum of the squared coordinate differences, in coordinate
order, so entries ``(i, j)`` and ``(j, i)`` are the same floats: ``A`` is
exactly symmetric with no ``0.5 (K + K^T)``, which would move every block
to the chip of its transpose.

The row sharding (:func:`row_sharding`) is the first ``mesh`` devices
along one axis ``"shards"``: the sharding a plan lowered with ``mesh=K``
gives its operator (``CompiledPlan.feed_shardings``), which the driver
checks.  The reference rebuilds the matrix with the same jitted function
on that sharding, which gives the same floats
(``tests/test_mesh_cell.py``), reads it back shard by shard into float64
row blocks, and applies it in threads over the blocks.
"""
from __future__ import annotations

import functools
import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Mapping, Tuple

import numpy as np

import inputs

SERVED = False
#: rows of the row block computed at once
CHUNK_ROWS = 512
#: rows of each float64 block of the host reference
HOST_BLOCK_ROWS = 1024
AXIS = "shards"


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


def row_sharding(config: Mapping[str, Any]):
    """Row blocks of ``A`` over the first ``config["mesh"]`` devices."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    k = int(config["mesh"])
    devices = jax.devices()
    if len(devices) < k:
        raise RuntimeError(f"the operator is split over {k} devices; JAX "
                           f"found {len(devices)}")
    return NamedSharding(Mesh(np.array(devices[:k]), (AXIS,)),
                         PartitionSpec(AXIS))


@functools.lru_cache(maxsize=None)
def _builder(n: int, dim: int, lengthscale: float, outputscale: float,
             noise: float, dtype: str, sharding):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec
    mesh = sharding.mesh
    axis = sharding.spec[0]
    rows = n // mesh.shape[axis]
    chunk = math.gcd(rows, CHUNK_ROWS)
    half_width = math.sqrt(3.0)

    def block(u):
        r0 = lax.axis_index(axis) * rows
        cols = jnp.arange(n)

        def chunk_rows(c, a):
            i0 = r0 + c * chunk
            ui = lax.dynamic_slice_in_dim(u, i0, chunk)
            d2 = jnp.zeros((chunk, n), jnp.float32)
            for d in range(dim):
                t = ui[:, d:d + 1] - u[None, :, d]
                d2 = d2 + t * t
            k = outputscale * jnp.exp(-d2 / (2.0 * lengthscale ** 2))
            on_diag = (i0 + jnp.arange(chunk))[:, None] == cols[None, :]
            k = jnp.where(on_diag, k + noise, k)
            return lax.dynamic_update_slice_in_dim(a, k.astype(dtype),
                                                   c * chunk, 0)
        return lax.fori_loop(0, rows // chunk, chunk_rows,
                             jnp.zeros((rows, n), dtype))

    @functools.partial(jax.jit, out_shardings=sharding)
    def build(key):
        u = jax.random.uniform(key, (n, dim), jnp.float32, -half_width,
                               half_width)
        return jax.shard_map(block, mesh=mesh, in_specs=PartitionSpec(),
                             out_specs=PartitionSpec(axis),
                             check_vma=False)(u)
    return build


def build(config: Mapping[str, Any], seed: int,
          sharding=None) -> Dict[str, Any]:
    """``{"A": ...}`` made on ``sharding`` (default :func:`row_sharding`)."""
    sharding = row_sharding(config) if sharding is None else sharding
    return {"A": _builder(*_shape(config), sharding)(
        inputs.device_key(seed, "operator"))}


def host_blocks(a) -> List[Tuple[int, np.ndarray]]:
    """``(first row, float64 rows)`` blocks of a row-sharded device
    matrix, read back one shard at a time."""
    out: List[Tuple[int, np.ndarray]] = []
    shards = sorted(a.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        for s in shards:
            rows = np.asarray(s.data)
            r0 = s.index[0].start or 0
            starts = range(0, rows.shape[0], HOST_BLOCK_ROWS)
            out += zip((r0 + i for i in starts), pool.map(
                lambda i: rows[i:i + HOST_BLOCK_ROWS].astype(np.float64),
                starts))
            del rows
    return out


def blocks_matvec(blocks: List[Tuple[int, np.ndarray]]
                  ) -> Callable[[np.ndarray], np.ndarray]:
    """``v -> A v`` in float64 over row blocks, the blocks in threads
    (NumPy releases the interpreter lock)."""
    n = sum(b.shape[0] for _, b in blocks)
    pool = ThreadPoolExecutor(os.cpu_count() or 1)

    def mv(v):
        v = np.asarray(v, np.float64)
        out = np.empty((n,) + v.shape[1:])

        def one(block):
            r0, rows = block
            out[r0:r0 + rows.shape[0]] = rows @ v
        list(pool.map(one, blocks))
        return out
    return mv


def reference_matvec(config: Mapping[str, Any], seed: int
                     ) -> Callable[[np.ndarray], np.ndarray]:
    return blocks_matvec(host_blocks(build(config, seed)["A"]))


def bf16_operator(config: Mapping[str, Any], seed: int):
    import jax.numpy as jnp
    return build(config, seed)["A"].astype(jnp.bfloat16)


def bf16_rows_matvec(a, p):
    return p @ a.T
