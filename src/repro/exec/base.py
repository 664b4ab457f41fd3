"""Executor interface + registry for plan execution backends.

A backend turns a lowered :class:`~repro.api.artifacts.CompiledPlan` for a
frontend (expression-DAG) trace into an actual computation::

    fn = get_backend("pallas").compile(plan)   # plan -> callable(feeds)
    outputs = fn(feeds)                        # {tensor name: array}

Backends register by name exactly like ``core.search.SearchStrategy``
instances, so ``CompiledPlan.run(backend=...)`` resolves through one
registry and a new backend (sharded, multi-device, TPU-real) is a registry
entry, not a rewrite.  The contract every backend must meet:

* it executes the plan's **co-designed group order** (the flattened fusion
  groups), not the program's build order,
* its outputs match the ``reference`` backend on the same feeds — bitwise
  for backends that replay the same per-op jax.numpy rules, within the
  documented reduction-reassociation tolerances for tiled backends
  (``docs/execution_backends.md``).
"""
from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence

from .. import obs
from ..testing import faults

Feeds = Dict[str, Any]
CompiledFn = Callable[[Feeds], Dict[str, Any]]

_COMPILE_S = obs.registry().histogram(
    "exec.compile_s", "plan -> callable compile wall-clock (memoized: one "
    "observation per distinct plan per executor)", unit="s")
_DISPATCH_S = obs.registry().histogram(
    "exec.dispatch_s", "host time to enqueue one run (feed checks and "
    "copies, the executable's launch), not device time: JAX dispatch is "
    "async; traced=True marks runs that traced (and compiled) the "
    "program", unit="s")


class Executor:
    """Protocol: compile a frontend plan into a callable and run it."""

    name: str = "base"

    def __init__(self) -> None:
        # compiled-plan cache keyed by plan *identity* (plan equality
        # ignores the carried trace/program, so two distinct programs can
        # compare equal); weakrefs keep dead plans from pinning entries.
        # The lock serializes lookup+compile+insert, so two threads racing
        # the same plan compile it once (a serving process hits this) —
        # the weakref finalizer's dict.pop is atomic under the GIL and
        # never takes the lock, so it cannot deadlock against a compile.
        self._compiled: Dict[int, tuple] = {}
        self._compile_lock = threading.Lock()

    # -- backend contract ----------------------------------------------
    def compile(self, plan) -> CompiledFn:
        """Lower ``plan`` to a callable ``feeds -> {name: value}``."""
        raise NotImplementedError

    def compile_pure(self, plan) -> CompiledFn:
        """Like :meth:`compile`, but the returned callable must be **pure**
        and jax-traceable (``feeds -> {name: tracer}`` with no Python side
        effects per call), so it composes under ``jax.jit`` / ``jax.vmap``.
        Backends whose compiled callable is already pure (reference) just
        inherit this; backends with per-call driver state (dispatch
        counters, donation) override it to expose the traced core
        (``repro.serve.BatchedPlan`` batches through this hook)."""
        return self.compile(plan)

    # -- shared driver --------------------------------------------------
    def compiled(self, plan) -> CompiledFn:
        """``plan``'s callable, compiled once per plan (memoized,
        thread-safe)."""
        with self._compile_lock:
            entry = self._compiled.get(id(plan))
            fn = (entry[1] if entry is not None and entry[0]() is plan
                  else None)
            if fn is None:
                t0 = time.perf_counter()
                with obs.span("exec.compile", backend=self.name):
                    # fault-injection site (docs/robustness.md):
                    # exec.compile@<backend>
                    faults.check("exec.compile", backend=self.name)
                    fn = self.compile(plan)
                _COMPILE_S.observe(time.perf_counter() - t0,
                                   backend=self.name)
                try:
                    ref = weakref.ref(
                        plan,
                        lambda _, k=id(plan): self._compiled.pop(k, None))
                except TypeError:                    # not weakref-able
                    pass
                else:
                    self._compiled[id(plan)] = (ref, fn)
        return fn

    def run(self, plan, feeds: Optional[Feeds] = None, *,
            seed: int = 0) -> Dict[str, Any]:
        """Compile (memoized, thread-safe) and execute ``plan``."""
        program = plan_program(plan)
        fn = self.compiled(plan)
        if feeds is None:
            from ..frontends.reference import make_feeds
            feeds = make_feeds(program, seed)
        traces = _traces(fn)
        t0 = time.perf_counter()
        with obs.span("exec.dispatch", backend=self.name):
            # fault-injection site: exec.dispatch@<backend>
            faults.check("exec.dispatch", backend=self.name)
            out = fn(feeds)
        _DISPATCH_S.observe(time.perf_counter() - t0, backend=self.name,
                            traced=_traces(fn) != traces)
        return out

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.name!r})"


def _traces(fn) -> Optional[int]:
    """How often ``fn`` has traced its program, where it counts that."""
    stats = getattr(fn, "stats", None)
    return None if stats is None else stats["traces"]


# --------------------------------------------------------------------------
# plan plumbing shared by every backend
# --------------------------------------------------------------------------

def plan_program(plan):
    """The expression :class:`~repro.frontends.expr.Program` behind ``plan``
    (execution backends only run frontend-traced plans)."""
    if plan.trace is None or plan.trace.program is None:
        raise ValueError("execution backends need a frontend-traced plan "
                         "(Session.trace(workload=...) or "
                         "Session.from_graph(program))")
    return plan.trace.program


def plan_groups(plan) -> List[List[str]]:
    """The co-designed fusion groups in scheduled order (each op its own
    group, in build order, when no search was run)."""
    program = plan_program(plan)
    if plan.codesigned is not None:
        return [list(g) for g in plan.codesigned.best.schedule.groups]
    return [[n] for n in program.schedulable_order()]


def plan_order(plan) -> List[str]:
    """The flattened scheduled op order."""
    return [o for g in plan_groups(plan) for o in g]


# --------------------------------------------------------------------------
# registry (mirrors core.search.SearchStrategy)
# --------------------------------------------------------------------------

EXECUTOR_REGISTRY: Dict[str, Executor] = {}


def register_backend(backend):
    """Register a backend instance (or class, instantiated with no args)."""
    inst = backend() if isinstance(backend, type) else backend
    EXECUTOR_REGISTRY[inst.name] = inst
    return backend


def get_backend(name_or_obj) -> Executor:
    if isinstance(name_or_obj, str):
        if name_or_obj not in EXECUTOR_REGISTRY:
            raise KeyError(f"unknown execution backend {name_or_obj!r}; "
                           f"have {sorted(EXECUTOR_REGISTRY)}")
        return EXECUTOR_REGISTRY[name_or_obj]
    if isinstance(name_or_obj, type):    # mirror register_backend: a bare
        return name_or_obj()             # class is instantiated with no args
    return name_or_obj


def list_backends() -> Sequence[str]:
    return sorted(EXECUTOR_REGISTRY)
