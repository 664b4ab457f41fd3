"""Device time under one of the program's named scopes, per solve.

A TPU trace names each device op by its HLO instruction alone
(``fusion.6``): the ``jax.named_scope`` the program put around the work
(``spmv_gather``, ``csr_layout``) is not in it.  The program says which
instructions of its executable carry which scope
(``CompiledPlan.device_scopes``); this builds the plan of the cell's
configuration through the entry points its traffic kind runs, asks it,
and sums the trace's per-op device time over those instructions.  The
compile is the executable the window ran, found again in the
compilation cache.

The trace's breakdown keeps its ten largest ops (``device_ops``), so
only ops among them are counted.  A program without the accessor gives
``None``.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Dict, Mapping, Optional


@functools.lru_cache(maxsize=None)
def _scopes(workload: str, params: str, dtype: str) -> Dict[str, str]:
    from repro.api import CompiledPlan, Session
    if not hasattr(CompiledPlan, "device_scopes"):
        return {}
    plan = Session(use_cache=False).trace(
        workload=workload, **json.loads(params)).analyze().codesign() \
        .lower(backend="pallas")
    return plan.device_scopes(dtype)


def scopes_of(config: Mapping[str, Any]) -> Dict[str, str]:
    """``{instruction: scope}`` of the plan ``config`` describes."""
    return _scopes(config["workload"],
                   json.dumps(config["params"], sort_keys=True),
                   config["dtype"])


def ms_per_solve(ctx: Mapping[str, Any], scope: str) -> Optional[float]:
    """Device ms per solve of the window in the ops under ``scope``;
    ``None`` without a trace, solves, or such an op among the ten."""
    trace, solves = ctx.get("trace"), ctx.get("solves")
    if trace is None or not solves:
        return None
    names = {n for n, s in scopes_of(ctx["config"]).items() if s == scope}
    found = [t for name, t in trace["device_ops"] if name in names]
    if not found:
        return None
    return sum(found) / solves * 1e3
