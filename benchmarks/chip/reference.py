"""The plain reference a cell's answers are compared with.

A float64 NumPy CG of the same recurrence the ``cg`` / ``cg_sparse``
workloads run, from ``x0 = 0``, on the operator kind's own float64
product (``operators/<kind>.py``'s ``reference_matvec``): it imports
nothing of the program and takes nothing the program made.
:func:`csr_matvec` (the CSR product written out) lets a test tie the CSR
a kind feeds to the product its reference applies.

The comparison (:func:`compare_all`) reads three numbers per answer
``(x, r)`` to the right-hand side ``b``:

* ``x_err``: the 2-norm of ``x - x_ref`` over that of ``x_ref``, the
  float64 solution after the same iterations;
* ``x_maxerr``: the largest entry of ``x - x_ref`` over the largest of
  ``x_ref`` (one altered entry moves it, which ``x_err`` averages away);
* ``r_gap``: the 2-norm of ``r - (b - A x)`` over that of ``b``, with
  ``b - A x`` worked out in float64: whether the residual the program
  returns is the residual of the answer it returns.  The reference's own
  recurrence residual is no yardstick for ``r``: near convergence a
  float32 recurrence's residual departs from a float64 one's by as much
  as a bfloat16 one's does (``PERF.md``).
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Mapping, Sequence, Tuple

import numpy as np


def host_cg(matvec: Callable[[np.ndarray], np.ndarray], b: np.ndarray,
            iters: int) -> Tuple[np.ndarray, np.ndarray]:
    """Plain CG from ``x0 = 0`` in float64 on each column of ``b``
    (``(n,)`` or ``(n, k)``): every column runs its own recurrence.
    Returns the solution and the recurrence's residual."""
    x = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rs = np.sum(r * r, axis=0)
    for _ in range(iters):
        ap = matvec(p)
        alpha = rs / np.sum(p * ap, axis=0)
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = np.sum(r * r, axis=0)
        p = r + (rs_new / rs) * p
        rs = rs_new
    return x, r


def csr_matvec(indptr, indices, data):
    """``v -> A v`` for a CSR operator, in the precision of ``data``."""
    rows = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))

    def mv(v):
        return np.bincount(rows, weights=data * v[indices],
                           minlength=len(indptr) - 1)
    return mv


def compare_all(b: np.ndarray, x: np.ndarray, r: np.ndarray,
                x_ref: np.ndarray, matvec: Callable[[np.ndarray], np.ndarray]
                ) -> List[Dict[str, float]]:
    """The numbers of each answer (see the module docstring); columns of
    the ``(n, k)`` arrays are the answers.  A non-finite answer reads
    ``inf``."""
    b = np.asarray(b, np.float64)
    x = np.asarray(x, np.float64)
    r = np.asarray(r, np.float64)
    finite = np.all(np.isfinite(x), axis=0) & np.all(np.isfinite(r), axis=0)
    true_r = b - matvec(np.where(finite, x, 0.0))
    out = []
    for i in range(b.shape[1]):
        if not finite[i]:
            out.append({"x_err": math.inf, "x_maxerr": math.inf,
                        "r_gap": math.inf})
            continue
        d = x[:, i] - x_ref[:, i]
        out.append({
            "x_err": float(np.linalg.norm(d) / np.linalg.norm(x_ref[:, i])),
            "x_maxerr": float(np.max(np.abs(d))
                              / np.max(np.abs(x_ref[:, i]))),
            "r_gap": float(np.linalg.norm(r[:, i] - true_r[:, i])
                           / np.linalg.norm(b[:, i]))})
    return out


def worst(readings: Sequence[Mapping[str, float]]) -> Dict[str, float]:
    """The largest reading of each number over a sample."""
    return {k: max(r[k] for r in readings) for k in readings[0]}


def judge(readings: Sequence[Mapping[str, float]],
          limits: Mapping[str, float]) -> Dict[str, Dict[str, Any]]:
    """``{name: {"value": worst reading, "limit": limit}}``; a non-finite
    reading is given as ``None`` (JSON has no infinity)."""
    w = worst(readings)
    return {k: {"value": w[k] if math.isfinite(w[k]) else None,
                "limit": float(limits[k])} for k in limits}
