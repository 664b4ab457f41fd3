"""The work one chip of a mesh cell does, from a configuration alone.

A configuration with ``mesh`` = K splits its dense ``n x n`` operator
into K row blocks, one per chip.  One application of the operator then
reads, on each chip, its ``n / K`` rows of ``n`` values, the whole input
vector (gathered from the other chips) and writes its ``n / K`` rows of
the output; a solve applies it ``iters + 1`` times (``work.py``).  A
kernel's share of its roofline on one chip is these bytes over the chip's
HBM bandwidth, over the kernel's device time.
"""
from __future__ import annotations

from typing import Any, Mapping

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float64": 8}


def chips(config: Mapping[str, Any]) -> int:
    return int(config.get("mesh", 1))


def shard_apply_bytes(config: Mapping[str, Any]) -> int:
    """HBM bytes one chip's part of one dense application moves."""
    n = int(config["params"]["n"])
    k = chips(config)
    item = _ITEMSIZE[config["dtype"]]
    return (n * n // k + n + n // k) * item


def shard_solve_matvec_bytes(config: Mapping[str, Any]) -> int:
    """HBM bytes one chip's operator applications of one solve move."""
    return (int(config["params"]["iters"]) + 1) * shard_apply_bytes(config)
