"""Traffic kind ``closed_solve_mesh``: one client solves back to back on
a plan split over a mesh of chips.

As ``closed_solve``, with the plan lowered with ``mesh=config["mesh"]``:
``Session().trace().analyze().codesign().lower(backend="pallas",
mesh=K)`` then ``CompiledPlan.run``.  The operator (the operator kind's
``build``) and a pool of ``rhs_pool`` right-hand sides are made on the
device from the seed, in place on the shardings the plan gives its leaves
(``CompiledPlan.feed_shardings``): each chip makes and holds its own row
block, and nothing passes through one chip.  Solve ``i`` takes
right-hand side ``i mod rhs_pool`` and ends in ``block_until_ready``.
Reports ``solve_ms``: the window over the solves completed in it.
"""
from __future__ import annotations

import importlib.util
import pathlib
import time
from typing import Any, Dict, Tuple

import numpy as np

import inputs
from driver_base import stage_seconds

_spec = importlib.util.spec_from_file_location(
    "closed_solve_base", pathlib.Path(__file__).with_name("closed_solve.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)


class ClosedSolveMesh(_base.ClosedSolve):

    def _lower(self):
        from repro.api import CompiledPlan, Session
        if not hasattr(CompiledPlan, "feed_shardings"):
            raise RuntimeError(
                "this program cannot feed a plan in place: CompiledPlan has "
                "no feed_shardings(), so an operator larger than one chip "
                "cannot be built on the plan's mesh")
        cfg = self.config
        traced = Session(use_cache=False).trace(workload=cfg["workload"],
                                                **cfg["params"])
        plan = traced.analyze().codesign().lower(backend="pallas",
                                                 mesh=int(cfg["mesh"]))
        return traced, plan

    def _operator_on(self, shardings) -> Tuple[Dict[str, Any], Any]:
        """The operator and ``x0``, made in place on the plan's
        shardings."""
        import jax
        if shardings["A"] != self.kind.row_sharding(self.config):
            raise RuntimeError(
                f"the plan lays its operator out as {shardings['A']}, not "
                f"as the operator kind's reference rebuilds it")
        op = self.kind.build(self.config, self.seed, shardings["A"])
        x0 = jax.numpy.zeros((self.n,), self.config["dtype"],
                             device=shardings["x0"])
        return op, x0

    def setup(self) -> None:
        import jax
        t0 = time.perf_counter()
        stage0 = stage_seconds()
        traced, self.plan = self._lower()
        self.setup_split["codesign_s"] = stage_seconds() - stage0
        t1 = time.perf_counter()
        shardings = self.plan.feed_shardings()
        self.operator, self.x0 = self._operator_on(shardings)
        self.rhs = [jax.device_put(b, shardings["b"]) for b in
                    inputs.device_rhs(self.config, self.seed,
                                      int(self.traffic["rhs_pool"]))]
        jax.block_until_ready((self.operator, self.rhs, self.x0))
        t2 = time.perf_counter()
        leaves = {nd.name for nd in traced.program.leaves()}
        given = set(self.operator) | {"b", "x0"}
        if leaves != given:
            raise ValueError(f"the traced program's leaves {sorted(leaves)} "
                             f"are not the feeds {sorted(given)}")
        for i in range(2):
            jax.block_until_ready(self.plan.run(self._feeds(i)))
        t3 = time.perf_counter()
        self.setup_split.update(plan_s=t1 - t0, feeds_s=t2 - t1,
                                warmup_s=t3 - t2)

    def solve_rows(self, b: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        import jax
        _, plan = self._lower()
        shardings = plan.feed_shardings()
        op, x0 = self._operator_on(shardings)
        return self._rows([plan.run(dict(op, b=jax.device_put(
            np.asarray(row, self.config["dtype"]), shardings["b"]), x0=x0))
            for row in b])


DRIVER = ClosedSolveMesh
