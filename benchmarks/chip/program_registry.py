"""Totals of the program's own counters and histograms (``repro.obs``),
read in the process that ran the cell, after its window.

They count from the start of the process, so set-up and warm-up are in
them too: a reader uses them where every run or batch of the cell does
the same (bytes copied per solve), or excludes the calls that traced
and compiled the program (their ``traced`` label).  Each function returns
``None`` where the program does not define the instrument.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional


def _cells(name: str, labels: Dict[str, str]) -> Optional[List[Any]]:
    from repro import obs
    inst = obs.registry().snapshot().get(name)
    if inst is None:
        return None
    return [c["value"] for c in inst["cells"]
            if all(c["labels"].get(k) == v for k, v in labels.items())]


def counter_total(name: str, **labels: str) -> Optional[float]:
    """The sum of a counter's cells whose labels match ``labels``."""
    cells = _cells(name, labels)
    return None if cells is None else float(sum(cells))


def hist_mean(name: str, **labels: str) -> Optional[float]:
    """The mean observation over a histogram's cells whose labels match
    ``labels``; ``None`` when they hold none."""
    cells = _cells(name, labels)
    if not cells:
        return None
    count = sum(c["count"] for c in cells)
    return sum(c["sum"] for c in cells) / count if count else None
