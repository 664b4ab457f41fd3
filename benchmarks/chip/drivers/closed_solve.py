"""Traffic kind ``closed_solve``: one client solves back to back.

Each solve ends in ``block_until_ready``.  The operator (the operator
kind's ``build``) and a pool of ``rhs_pool`` right-hand sides are made on
the device from the seed; solve ``i`` takes right-hand side ``i mod
rhs_pool``.  Path: ``Session().trace().analyze().codesign()
.lower(backend="pallas")`` then ``CompiledPlan.run``.  Reports
``solve_ms``: the window over the solves completed in it.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import numpy as np

import inputs
from driver_base import WINDOW_SPAN, Driver, annotation, stage_seconds


class ClosedSolve(Driver):

    def setup(self) -> None:
        import jax
        from repro.api import Session
        t0 = time.perf_counter()
        self.operator = self.kind.build(self.config, self.seed)
        pool = int(self.traffic["rhs_pool"])
        self.rhs = inputs.device_rhs(self.config, self.seed, pool)
        self.x0 = jax.numpy.zeros((self.n,), self.config["dtype"])
        jax.block_until_ready((self.operator, self.rhs, self.x0))
        t1 = time.perf_counter()
        stage0 = stage_seconds()
        traced = Session(use_cache=False).trace(
            workload=self.config["workload"], **self.config["params"])
        self.plan = traced.analyze().codesign().lower(backend="pallas")
        self.setup_split["codesign_s"] = stage_seconds() - stage0
        t2 = time.perf_counter()
        leaves = {nd.name for nd in traced.program.leaves()}
        given = set(self.operator) | {"b", "x0"}
        if leaves != given:
            raise ValueError(f"the traced program's leaves {sorted(leaves)} "
                             f"are not the feeds {sorted(given)}")
        for i in range(2):
            jax.block_until_ready(self.plan.run(self._feeds(i)))
        t3 = time.perf_counter()
        self.setup_split.update(feeds_s=t1 - t0, plan_s=t2 - t1,
                                warmup_s=t3 - t2)

    def _feeds(self, i: int) -> Dict[str, Any]:
        return dict(self.operator, b=self.rhs[i % len(self.rhs)],
                    x0=self.x0)

    def prime(self) -> None:
        import jax
        jax.block_until_ready(self.plan.run(self._feeds(0)))

    def sample_inputs(self, count: int) -> np.ndarray:
        pool = inputs.device_rhs(self.config, self.seed,
                                 int(self.traffic["rhs_pool"]))
        return np.stack([np.asarray(b, np.float64) for b in pool[:count]])

    def solve_rows(self, b: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        import jax
        from repro.api import Session
        cfg = self.config
        plan = Session(use_cache=False).trace(
            workload=cfg["workload"], **cfg["params"]).analyze().codesign() \
            .lower(backend="pallas")
        op = self.kind.build(cfg, self.seed)
        x0 = jax.numpy.zeros((self.n,), cfg["dtype"])
        return self._rows([plan.run(dict(op, b=jax.numpy.asarray(
            row, cfg["dtype"]), x0=x0)) for row in b])

    def window(self, seconds: float) -> Dict[str, Any]:
        import jax
        # a sample of the window's answers, drawn from the seed as they
        # come (reservoir sampling: every solve equally likely to be kept)
        keep = int(self.config["check_sample"])
        kept: List[Tuple[int, Any]] = []
        solves = 0
        self.compiles.on = True
        with annotation(WINDOW_SPAN):
            t0 = time.perf_counter()
            end = t0 + seconds
            while time.perf_counter() < end:
                feeds = self._feeds(solves)
                with annotation("bench.dispatch"):
                    out = self.plan.run(feeds)
                with annotation("bench.wait"):
                    jax.block_until_ready(out)
                answer = (out[self.out_x], out[self.out_r])
                if solves < keep:
                    kept.append((solves, answer))
                else:
                    slot = self.sample_rng.integers(solves + 1)
                    if slot < keep:
                        kept[slot] = (solves, answer)
                solves += 1
            elapsed = time.perf_counter() - t0
        self.compiles.on = False
        self.kept = kept
        self.solves_in_window = solves
        self.window_s = elapsed
        self.log(f"window: {solves} solves in {elapsed:.3f} s")
        return {"attempted": solves, "failed": 0,
                "metrics": {"solve_ms": elapsed / solves * 1e3}}

    def finish(self) -> None:
        pass

    def answers(self):
        """The sampled solves of the window, as ``(b, x, r)`` on the
        host."""
        return [(np.asarray(self.rhs[i % len(self.rhs)]), np.asarray(x),
                 np.asarray(r))
                for i, (x, r) in sorted(self.kept, key=lambda ix: ix[0])]

    def free(self) -> None:
        self.plan = self.operator = self.kept = self.rhs = self.x0 = None


DRIVER = ClosedSolve
