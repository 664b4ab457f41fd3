"""`BatchedPlan`: one device dispatch answers a whole batch of requests.

A compiled plan solves one problem instance per ``run()``.  Serving wants
the opposite shape: many user requests against the *same* operator (the
expensive, co-designed part) with different right-hand sides / starting
points (the cheap, per-request part).  ``BatchedPlan`` vmaps the backend's
pure single-program callable (:meth:`repro.exec.base.Executor.compile_pure`)
over a leading batch axis:

* **operator leaves are shared** — ``in_axes=None``: the dense ``A`` (or a
  CSR operand's indptr/indices/data sub-leaves) is passed once, unbatched,
  and every lane of the vmap reads the same buffers;
* **input leaves are batched** — ``in_axes=0``: each request contributes
  one row of ``b``, ``x0``, ... stacked on a new leading axis.

Under the ``pallas`` backend each stream pass of the pure core reads a
shared operand once for all lanes: vmapped with its matrix (or diagonal
layout) unbatched it runs as one lane kernel, ``cello_lanes_<first op>``,
contracting each tile of ``A`` with every lane's vector; a pass the lane
kernel does not cover (a per-tile CSR spmv, whose gathered values differ
per lane) runs ``jax.vmap`` of the single-RHS kernel, one read per lane.
The counter ``exec.batched_pass`` (``lanes: shared | per_lane``) counts
each dispatch's passes by which one ran.

The vmapped callable is wrapped in one ``jax.jit``, so a ``run_batch()`` is
exactly one device dispatch regardless of batch size — the serving-layer
image of the PR-4 single-program guarantee, and ``stats`` mirrors its
counters: ``dispatches`` counts ``run_batch`` calls, ``traces`` counts jit
retraces (one per distinct (batch size, dtype); batch sizes are not padded
to a bucket — the server's coalescing loop keeps the set of sizes small).

Numerics: under the ``reference`` backend the vmapped solve matches the
*jitted* single-request path (:meth:`run_one`) bitwise for gather/segment
workloads (``cg_sparse``); dense matvecs lower to a batched contraction
whose summation order may differ in the last ulps — see
``docs/serving.md`` for the measured tolerance policy.  Pallas plans match
within the tolerances already documented in ``docs/execution_backends.md``:
a lane kernel computes each lane's rows from the same tiles in the same
order as the single-RHS kernel, and no value crosses lanes.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Mapping, Optional, Sequence

from .. import obs
from ..core.lowering import check_csr_feeds
from ..exec import get_backend
from ..exec.base import plan_program
from ..testing import faults

__all__ = ["BatchedPlan"]

_BP_TRACES = obs.registry().counter(
    "serve.batch_traces", "BatchedPlan jit retraces (one per distinct "
    "(batch size, dtype)), per plan (scope label)")
_BP_DISPATCHES = obs.registry().counter(
    "serve.batch_dispatches", "BatchedPlan coalesced-batch device "
    "dispatches, per plan (scope label)")
_BATCHED_PASS = obs.registry().counter(
    "exec.batched_pass", "stream passes each batched dispatch runs, by "
    "whether the pass read its streamed operand once for all lanes or once "
    "per lane (lanes: shared | per_lane), per plan (scope label)")
_H2D_B = obs.registry().counter(
    "serve.h2d_bytes", "bytes of host (non-device) arrays passed into "
    "batched dispatches, shared and batched leaves, per plan (scope "
    "label)", unit="B")
_LAUNCH_S = obs.registry().histogram(
    "serve.launch_s", "the batched executable's launch, per batch: its "
    "host arguments' transfers are enqueued here and run on; traced=True "
    "marks launches that traced (and compiled) a new batch width",
    unit="s")
_READBACK_S = obs.registry().histogram(
    "serve.readback_s", "the batch's outputs read back to the host, per "
    "batch: waits for the argument transfers and the solve to finish",
    unit="s")


class BatchedPlan:
    """vmap a plan's single-program executable over a request batch.

    ``feeds`` for :meth:`run_batch` carry every leaf of the program:
    operator leaves at their traced shape (shared across the batch), input
    leaves with one extra leading batch axis.  :meth:`run_many` stacks
    per-request feed dicts for you.
    """

    def __init__(self, plan, *, backend: Optional[str] = None,
                 donate: Optional[bool] = None):
        program = plan_program(plan)
        self.plan = plan
        self.program = program
        executor = get_backend(backend or plan.backend)
        self.backend = executor.name
        leaves = program.leaves()
        self.shared_leaves = [nd.name for nd in leaves
                              if nd.op == "operator"]
        self.batched_leaves = [nd.name for nd in leaves
                               if nd.op != "operator"]
        if not self.batched_leaves:
            raise ValueError(f"{program.name!r} has no per-request (input) "
                             "leaves to batch over")
        self._single = executor.compile_pure(plan)
        if donate is None:
            from ..exec.pallas import use_donation
            donate = use_donation()
        self.donate = bool(donate)
        # counters live on the obs registry under this plan's unique scope
        # label; ``stats`` reads them back as the familiar dict
        self._scope = obs.next_scope("batched")
        self._jit = None        # built lazily: importing jax is deferred
        self._jit_one = None
        # {shared | per_lane: passes} a dispatch runs, decided as the
        # batched program traces (empty for a backend without passes)
        self._passes: Dict[str, int] = {}

    @property
    def stats(self) -> Dict[str, int]:
        """This plan's counters off the obs registry (dict-comparable)."""
        return {
            "traces": int(_BP_TRACES.value(backend=self.backend,
                                           scope=self._scope)),
            "dispatches": int(_BP_DISPATCHES.value(backend=self.backend,
                                                   scope=self._scope)),
        }

    # -- construction of the jitted executables -------------------------
    def _one(self, shared_vals, batched_vals):
        _BP_TRACES.inc(backend=self.backend, scope=self._scope)
        feeds = dict(zip(self.shared_leaves, shared_vals))
        feeds.update(zip(self.batched_leaves, batched_vals))
        return dict(self._single(feeds))

    def _build(self):
        import jax
        vmapped = jax.vmap(self._one, in_axes=(None, 0))
        passes = getattr(self._single, "batched_passes", dict)

        def traced(shared_vals, batched_vals):
            out = vmapped(shared_vals, batched_vals)
            self._passes = passes()
            return out

        kwargs = {"donate_argnums": (1,)} if self.donate else {}
        return jax.jit(traced, **kwargs)

    # -- execution -------------------------------------------------------
    def run_batch(self, feeds: Mapping[str, Any]) -> Dict[str, Any]:
        """One dispatch over a stacked batch: ``{output: (B, ...) array}``.

        Shared (operator) leaves must come at their traced shape; batched
        (input) leaves with a consistent leading batch axis prepended.
        When donation is on, batched feeds that are caller-owned
        ``jax.Array``\\ s are copied first (donation must never consume a
        caller's buffer); numpy feeds transfer fresh buffers anyway.
        """
        if self._jit is None:
            self._jit = self._build()
        shared_vals = []
        for n in self.shared_leaves:
            v = _require(feeds, n)
            want = self.program.nodes[n].shape
            if tuple(getattr(v, "shape", ())) != tuple(want):
                raise ValueError(
                    f"operator leaf {n!r} is shared across the batch: "
                    f"expected shape {tuple(want)}, got "
                    f"{tuple(getattr(v, 'shape', ()))} (pass it unbatched)")
            shared_vals.append(v)
        batch = None
        batched_vals = []
        for n in self.batched_leaves:
            v = _require(feeds, n)
            want = self.program.nodes[n].shape
            shape = tuple(getattr(v, "shape", ()))
            if len(shape) != len(want) + 1 or shape[1:] != tuple(want):
                raise ValueError(
                    f"input leaf {n!r} must be batched: expected "
                    f"(B,) + {tuple(want)}, got {shape}")
            if batch is None:
                batch = shape[0]
            elif shape[0] != batch:
                raise ValueError(f"inconsistent batch sizes: leaf {n!r} "
                                 f"has {shape[0]}, expected {batch}")
            if self.donate:
                v = _own(v)
            batched_vals.append(v)
        if self.backend == "pallas":
            # shared CSR operands must fit the kernels' per-tile layout
            check_csr_feeds(self.plan.exec_plan.units, self.program, feeds)
        _BP_DISPATCHES.inc(backend=self.backend, scope=self._scope)
        h2d = _host_bytes(shared_vals) + _host_bytes(batched_vals)
        if h2d:
            _H2D_B.inc(h2d, backend=self.backend, scope=self._scope)
        traces = self.stats["traces"]
        t0 = time.perf_counter()
        with obs.span("serve.launch", backend=self.backend, batch=batch):
            # fault-injection site (docs/robustness.md):
            # serve.dispatch@<backend> — fail or slow the coalesced
            # dispatch itself
            faults.check("serve.dispatch", backend=self.backend)
            out = dict(self._jit(shared_vals, batched_vals))
        for lanes, n in self._passes.items():
            _BATCHED_PASS.inc(n, backend=self.backend, lanes=lanes,
                              scope=self._scope)
        _LAUNCH_S.observe(time.perf_counter() - t0, backend=self.backend,
                          scope=self._scope,
                          traced=self.stats["traces"] != traces)
        return out

    def run_many(self, requests: Sequence[Mapping[str, Any]],
                 shared: Mapping[str, Any], *,
                 pad: bool = True) -> List[Dict[str, Any]]:
        """Stack per-request feed dicts, dispatch once, unstack results.

        ``requests`` each map every batched (input) leaf to its unbatched
        value; ``shared`` maps the operator leaves.  Returns one output
        dict per request (numpy arrays — the stacked device outputs
        transfer to host in one sync per output, never one per request).

        ``pad=True`` (default) rounds the batch up to the next power of
        two by repeating the last request, then drops the filler lanes.
        jit retraces per distinct batch size, so an open-loop server
        coalescing variable-size batches would otherwise pay a fresh
        trace (hundreds of ms) for every new size; padding bounds the
        trace set to {1, 2, 4, ...} at ≤ 2× wasted lanes.  vmap lanes are
        independent, so filler lanes cannot perturb real ones.
        """
        import numpy as np
        if not requests:
            return []
        n_real = len(requests)
        n_lanes = _next_pow2(n_real) if pad else n_real
        feeds: Dict[str, Any] = dict(shared)
        with obs.span("serve.stack", lanes=n_lanes):
            for n in self.batched_leaves:
                vals = [np.asarray(_require(r, n)) for r in requests]
                vals += [vals[-1]] * (n_lanes - n_real)
                feeds[n] = np.stack(vals)
        dev = self.run_batch(feeds)
        t0 = time.perf_counter()
        with obs.span("serve.readback"):
            out = {k: np.asarray(v) for k, v in dev.items()}
        _READBACK_S.observe(time.perf_counter() - t0, backend=self.backend,
                            scope=self._scope)
        return [{k: v[i] for k, v in out.items()} for i in range(n_real)]

    def run_one(self, feeds: Mapping[str, Any]) -> Dict[str, Any]:
        """The *jitted* unbatched solve — the sequential twin of one vmap
        lane.  This is the parity anchor: for gather/segment programs the
        vmapped batch matches a loop of ``run_one`` bitwise under the
        reference backend (same jit, same lowering), which a loop of eager
        ``plan.run()`` calls does not guarantee (jit fusion reorders)."""
        import jax
        if self._jit_one is None:
            self._jit_one = jax.jit(self._one)
        shared_vals = [_require(feeds, n) for n in self.shared_leaves]
        batched_vals = [_require(feeds, n) for n in self.batched_leaves]
        return dict(self._jit_one(shared_vals, batched_vals))


def _require(feeds: Mapping[str, Any], name: str):
    if name not in feeds:
        raise KeyError(f"feeds missing leaf {name!r}")
    return feeds[name]


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def _host_bytes(vals) -> int:
    """Bytes of the values in ``vals`` that are not on a device yet: the
    dispatch transfers them."""
    import jax
    return sum(int(getattr(v, "nbytes", 0)) for v in vals
               if not isinstance(v, jax.Array))


def _own(v):
    """A buffer safe to donate: copy caller-owned jax.Arrays."""
    import jax
    import jax.numpy as jnp
    if isinstance(v, jax.Array):
        return jnp.array(v, copy=True)
    return v
