"""The algorithmic work of one solve, from a configuration alone.

What any implementation of the configuration's CG recurrence has to do,
independent of how the program under test does it, so that a roofline
share computed from it reads the same work whichever kernels run:

* ``iters + 1`` operator applications (``r0 = b - A x0`` and one per
  iteration), each reading the whole operator once, as many bytes as its
  kind's ``operator_bytes`` gives (``operators/<kind>.py``): ``n*n``
  values for a dense operator (it does not fit on chip, so every
  application streams it), and for CSR the values, the column indices
  and the row pointers.  Each application also reads its input vector and
  writes its output.
* per iteration, one pass over the state that reads ``x``, ``r``, ``p``
  and ``A p`` and writes ``x``, ``r`` and ``p``: seven vectors.
* the set-up reads ``b`` and ``x0`` and writes ``r0``: three vectors.

Flops: ``2 nnz`` per operator application (``nnz = n^2`` for a dense
operator, the kind's ``nnz``); per iteration two dot products (``2n`` each) and three axpys
(``2n`` each), ``10 n``; the set-up's subtraction and dot, ``3 n``.

The least time of a solve on a chip is the larger of bytes over the
chip's HBM bandwidth and flops over its peak rate (``peaks.json``); the
peak is the bf16 matrix rate, so the flop bound is a lower bound for
float32 work.  Every configuration here is bandwidth-bound by orders of
magnitude.
"""
from __future__ import annotations

import json
import pathlib
from types import ModuleType
from typing import Any, Dict, Mapping

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float64": 8}


def solve_work(config: Mapping[str, Any], kind: ModuleType) -> Dict[str, int]:
    """``{"bytes": ..., "flops": ...}`` of one solve of ``config``, whose
    operator is of the kind ``kind`` (its file's module)."""
    n = int(config["params"]["n"])
    iters = int(config["params"]["iters"])
    item = _ITEMSIZE[config["dtype"]]
    apply_bytes = kind.operator_bytes(config, item) + 2 * n * item
    apply_flops = 2 * kind.nnz(config)
    applies = iters + 1
    return {
        "bytes": applies * apply_bytes + iters * 7 * n * item + 3 * n * item,
        "flops": applies * apply_flops + iters * 10 * n + 3 * n,
    }


def load_peaks(path: pathlib.Path = PEAKS_FILE) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def peaks_for(device_kind: str, table: Mapping[str, Any] = None
              ) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``.  A kind that
    is not in the table is an error: there is no default peak."""
    table = load_peaks() if table is None else table
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; have {sorted(kinds)}")
    return dict(kinds[device_kind])


def least_time_s(work: Mapping[str, int], peaks: Mapping[str, float]):
    """``(seconds, bound)``: the least time of ``work`` on one chip, and
    which of ``"hbm"`` or ``"flops"`` sets it."""
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    t_flops = work["flops"] / peaks["flops_bf16_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
