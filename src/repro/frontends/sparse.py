"""Deterministic CSR pattern / value generators for sparse operands.

A sparse operator leaf (``Program.sparse_operator``) is three typed
sub-leaves — ``A.indptr`` (int32, ``(n+1,)``), ``A.indices`` (int32,
``(nnz,)``), ``A.data`` (float, ``(nnz,)``) — whose *shapes* must be known
at DAG-build time.  This module is therefore the single source of truth for
both sides of that contract:

* :func:`pattern_nnz` / :func:`row_counts` — the exact nonzero count of a
  pattern, computed at build time to size the sub-leaves,
* :func:`pattern_offsets` — the static column offsets of a pattern whose
  rows all draw from one small set (its diagonals), for the kernels'
  diagonal layout,
* :func:`csr_component` — the deterministic values ``make_feeds`` generates
  at feed time (same per-(seed, operand) stream as every other leaf; the
  three sub-leaves of one operand share one stream so they describe one
  matrix).

Patterns (all square, diagonal always present):

``laplacian5``
    The 5-point Laplacian of a ``g×g`` grid with Dirichlet boundaries
    (``n = g²``): 4 on the diagonal, −1 per grid neighbour.  Exactly
    symmetric positive definite — the canonical Krylov test operator.
    ``nnz = 5n − 4g`` (boundary rows lose neighbours).

``banded``
    All ``|i − j| ≤ bandwidth``; off-diagonal values are symmetric random
    draws and the diagonal is ``1 + Σ|row off-diagonals|``, so the matrix
    is symmetric strictly diagonally dominant ⇒ SPD.
    ``nnz = n(2b+1) − b(b+1)``.

``random``
    Uniform density: every row gets ``max(1, round(density·n))`` entries
    (diagonal + random distinct columns).  Values are random with a
    dominant diagonal; *not* symmetric — use it for BiCGStab/Jacobi-style
    solvers or reuse analysis, not CG convergence claims.

``skewed``
    Power-law row populations (row ``r`` weight ``1/√(r+1)``) at a target
    overall density — the skewed-density regime Tailors-style buffer
    policies care about.  Same value model as ``random``.

Everything here is plain NumPy (no scipy); :func:`csr_to_dense` is the
explicit densifier tests and docs use as the scipy-free reference.
"""
from __future__ import annotations

import functools
import hashlib
import math
from typing import Dict, Optional, Tuple

import numpy as np

PATTERNS = ("laplacian5", "banded", "random", "skewed")


def rng_for(seed: int, name: str) -> np.random.Generator:
    """Deterministic per-(seed, name) generator (same scheme as
    ``frontends.reference``)."""
    h = hashlib.sha256(f"{seed}\0{name}".encode()).digest()
    return np.random.default_rng(int.from_bytes(h[:8], "little"))


def _grid_side(n: int) -> int:
    g = math.isqrt(n)
    if g * g != n:
        raise ValueError(f"laplacian5 needs a square grid: n={n} is not a "
                         "perfect square")
    return g


def row_counts(pattern: str, n: int, *, density: Optional[float] = None,
               bandwidth: Optional[int] = None) -> np.ndarray:
    """Per-row nonzero counts of a pattern — exact, deterministic, and
    computable at DAG-build time (no value generation involved)."""
    if n < 1:
        raise ValueError(f"sparse operator needs n >= 1, got {n}")
    if pattern == "laplacian5":
        g = _grid_side(n)
        i, j = np.divmod(np.arange(n), g)
        return (1 + (i > 0) + (i < g - 1) + (j > 0)
                + (j < g - 1)).astype(np.int64)
    if pattern == "banded":
        if bandwidth is None or bandwidth < 1 or bandwidth >= n:
            raise ValueError(f"banded pattern needs 1 <= bandwidth < n, "
                             f"got bandwidth={bandwidth!r} (n={n})")
        r = np.arange(n)
        return np.minimum(r, bandwidth) + np.minimum(n - 1 - r,
                                                     bandwidth) + 1
    if pattern == "random":
        if density is None or not 0.0 < density <= 1.0:
            raise ValueError(f"random pattern needs 0 < density <= 1, "
                             f"got {density!r}")
        k = min(n, max(1, int(round(density * n))))
        return np.full(n, k, np.int64)
    if pattern == "skewed":
        if density is None or not 0.0 < density <= 1.0:
            raise ValueError(f"skewed pattern needs 0 < density <= 1, "
                             f"got {density!r}")
        w = 1.0 / np.sqrt(np.arange(n) + 1.0)
        target = density * n * n
        return np.clip(np.floor(target * w / w.sum()).astype(np.int64),
                       1, n)
    raise ValueError(f"unknown sparse pattern {pattern!r}; "
                     f"have {PATTERNS}")


def pattern_offsets(pattern: str, n: int, bandwidth: Optional[int] = None
                    ) -> Optional[Tuple[int, ...]]:
    """The static set of column offsets ``j − i`` every row of a pattern
    draws its entries from, ascending, or ``None`` for patterns whose
    columns follow no such set (``random``, ``skewed``).  With it, ``A x``
    is ``Σ_k diag_k ⊙ x[i + d_k]``: the diagonal layout the kernels use
    instead of gathering ``x`` by column index."""
    if pattern == "laplacian5":
        g = _grid_side(n)
        offsets = {-g, -1, 0, 1, g}
    elif pattern == "banded":
        row_counts(pattern, n, bandwidth=bandwidth)      # validates
        offsets = set(range(-bandwidth, bandwidth + 1))
    elif pattern in PATTERNS:
        return None
    else:
        raise ValueError(f"unknown sparse pattern {pattern!r}; "
                         f"have {PATTERNS}")
    return tuple(sorted(d for d in offsets if abs(d) < n))


def pattern_nnz(pattern: str, n: int, *, density: Optional[float] = None,
                bandwidth: Optional[int] = None) -> int:
    """Exact nonzero count of a pattern (sizes the CSR sub-leaves)."""
    return int(row_counts(pattern, n, density=density,
                          bandwidth=bandwidth).sum())


@functools.lru_cache(maxsize=16)
def _components(pattern: str, n: int, density: Optional[float],
                bandwidth: Optional[int], seed: int,
                operand: str) -> Dict[str, np.ndarray]:
    """Build the full CSR of one operand: indptr/indices/data/dinv.

    Values are generated in float64 (cast to the requested dtype by the
    caller) from one rng stream keyed by (seed, operand name), so the three
    sub-leaves — drawn through separate ``make_feeds`` calls — always
    describe the same matrix.  Cached: one operand is typically read as
    3–4 leaves per feed build.
    """
    rng = rng_for(seed, operand)
    counts = row_counts(pattern, n, density=density, bandwidth=bandwidth)
    nnz = int(counts.sum())
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    indices = np.empty(nnz, np.int64)
    data = np.empty(nnz, np.float64)

    if pattern == "laplacian5":
        # row r = (i, j) holds columns r-g, r-1, r, r+1, r+g in that
        # order, each present when the grid neighbour exists
        g = _grid_side(n)
        r = np.arange(n)
        i, j = np.divmod(r, g)
        offsets = np.array([-g, -1, 0, 1, g])
        present = np.stack([i > 0, j > 0, np.ones(n, bool), j < g - 1,
                            i < g - 1], axis=1)
        indices[:] = (r[:, None] + offsets[None, :])[present]
        data[:] = np.broadcast_to(np.where(offsets == 0, 4.0, -1.0),
                                  (n, 5))[present]
    elif pattern == "banded":
        # symmetric off-diagonal values: v(i, j) = V[min(i, j), |i - j|]
        V = rng.standard_normal((n, bandwidth + 1))
        pos = 0
        for r in range(n):
            lo, hi = max(0, r - bandwidth), min(n - 1, r + bandwidth)
            cols = np.arange(lo, hi + 1)
            k = cols.size
            indices[pos:pos + k] = cols
            data[pos:pos + k] = V[np.minimum(cols, r), np.abs(cols - r)]
            pos += k
    else:                                  # random / skewed
        pos = 0
        for r in range(n):
            k = int(counts[r])
            if k >= n:
                cols = np.arange(n)
            else:
                off = rng.choice(n - 1, size=k - 1, replace=False)
                off = np.where(off >= r, off + 1, off)   # skip the diagonal
                cols = np.sort(np.append(off, r))
            indices[pos:pos + k] = cols
            data[pos:pos + k] = rng.standard_normal(k)
            pos += k

    # dominant positive diagonal: 1 + Σ|row off-diagonals| keeps every
    # pattern's iteration stable (and makes the symmetric ones SPD)
    diag_mask = indices == np.repeat(np.arange(n), counts)
    if pattern != "laplacian5":
        rowsum = np.add.reduceat(np.abs(np.where(diag_mask, 0.0, data)),
                                 indptr[:-1])
        data[diag_mask] = 1.0 + rowsum
    dinv = 1.0 / data[diag_mask]
    return {"indptr": indptr.astype(np.int32),
            "indices": indices.astype(np.int32),
            "data": data, "dinv": dinv}


def csr_component(node, seed: int, dtype) -> np.ndarray:
    """The feed value of one CSR sub-leaf (``make_feeds``'s ``init="csr"``
    rule).  ``node`` is the sub-leaf's ExprNode; its params carry the
    pattern and the ``role`` (indptr | indices | data | dinv)."""
    operand = node.name.rsplit(".", 1)[0]
    comp = _components(node.param("pattern"), int(node.param("rows")),
                       node.param("density"), node.param("bandwidth"),
                       int(seed), operand)
    role = node.param("role")
    if role not in comp:
        raise ValueError(f"{node.name}: unknown CSR role {role!r}")
    arr = comp[role]
    if role in ("indptr", "indices"):
        return arr.copy()                 # index leaves stay int32
    return arr.astype(dtype)              # float64 -> requested width


def csr_to_dense(indptr: np.ndarray, indices: np.ndarray,
                 data: np.ndarray, shape) -> np.ndarray:
    """Explicit scipy-free densifier — the reference tests compare sparse
    results against ``csr_to_dense(...) @ x``."""
    rows, cols = shape
    out = np.zeros((rows, cols), np.asarray(data).dtype)
    indptr = np.asarray(indptr)
    counts = np.diff(indptr)
    out[np.repeat(np.arange(rows), counts), np.asarray(indices)] = data
    return out
