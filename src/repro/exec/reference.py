"""The ``reference`` execution backend: a ``jax.numpy`` interpreter.

This is the bit-exact oracle every other backend is validated against.  It
executes one op at a time at full-tensor granularity — the per-op rules in
:func:`eval_node` define the semantics of every expression op, and because
ops are pure, replaying a co-designed schedule order through the same rules
must match natural-order evaluation bit-for-bit.  Buffer residency is a
planning/execution concept that never reaches these rules: overbooked
prefix pins (``core.lowering.ResidentSlice``) change how the pallas
backend lays out a CSR operand, not what an spmv computes, so this
backend stays the unchanged oracle for prefix-pinned plans too.

Relocated from ``frontends/reference.py`` (which keeps the deterministic
feed generator); ``repro.frontends`` re-exports :func:`evaluate` /
:func:`execute_plan` so existing imports keep working.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from ..testing import faults
from .base import Executor, plan_order, plan_program


def csr_row_ids(indptr, nnz: int):
    """Row id of every stored CSR entry, from ``indptr`` — the one rule
    both the reference spmv and the sharded spmv use, so their
    per-row summation order can never drift apart."""
    import jax.numpy as jnp
    return jnp.searchsorted(indptr, jnp.arange(nnz, dtype=indptr.dtype),
                            side="right") - 1


def eval_node(node, ins: List[Any]):
    """Reference rule for one expression op (``ins`` in operand order).

    Contractions through XLA run at ``Precision.HIGHEST``: on a TPU the
    default precision multiplies float32 operands in bfloat16 passes,
    below the precision a float32 program states.  Other platforms
    compute float32 contractions in full precision either way, so their
    results do not change.  A ``matmul`` of two NumPy arrays stays on the
    host's BLAS, as it always ran."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    hi = lax.Precision.HIGHEST
    op = node.op
    if op == "matmul":
        if isinstance(ins[0], np.ndarray) and isinstance(ins[1], np.ndarray):
            return ins[0] @ ins[1]
        return jnp.matmul(ins[0], ins[1], precision=hi)
    if op == "einsum":
        return jnp.einsum(node.param("spec"), *ins, precision=hi)
    if op == "dot":
        return jnp.dot(ins[0], ins[1], precision=hi)
    if op == "norm":
        v = jnp.ravel(ins[0])
        return jnp.sqrt(jnp.dot(v, v, precision=hi))
    if op == "add":
        return ins[0] + ins[1]
    if op == "sub":
        return ins[0] - ins[1]
    if op == "mul":
        return ins[0] * ins[1]
    if op == "div":
        return ins[0] / ins[1]
    if op == "neg":
        return -ins[0]
    if op == "axpy":
        return ins[0] * ins[1] + ins[2]
    if op == "stencil2d":
        u = ins[0]
        out = 0.25 * (jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0)
                      + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1))
        if len(ins) > 1:
            out = out + 0.25 * float(node.param("h2", 1.0)) * ins[1]
        return out
    if op == "gather":
        return jnp.take(ins[0], ins[1], axis=0)
    if op == "spmv":
        # CSR SpMV via explicit gather + segment sum: one multiply-add per
        # stored entry, rows resolved from indptr — the scipy-free rule
        # every sparse backend is validated against
        import jax
        indptr, indices, data, x = ins
        seg = csr_row_ids(indptr, data.shape[0])
        return jax.ops.segment_sum(data * jnp.take(x, indices, axis=0),
                                   seg, num_segments=node.shape[0])
    raise NotImplementedError(f"reference rule missing for op {op!r}")


def execute_plan(program, *, order: Optional[Sequence[str]] = None,
                 feeds: Optional[Dict[str, Any]] = None,
                 seed: int = 0, return_all: bool = False) -> Dict[str, Any]:
    """Execute the program's ops in ``order`` (default: build order).

    ``order`` is the flattened schedule from a co-designed plan; it must be
    a topological permutation of the program's ops — validated here, since
    a schedule that reads an unproduced tensor is a lowering bug, not a
    numerics question.
    """
    vals: Dict[str, Any] = {}
    op_names = program.schedulable_order()
    order = list(order) if order is not None else op_names
    if sorted(order) != sorted(op_names):
        raise ValueError(f"order is not a permutation of {program.name!r} "
                         "ops")
    if feeds is None:
        from ..frontends.reference import make_feeds
        feeds = make_feeds(program, seed)
    else:
        feeds = dict(feeds)
    for nd in program.leaves():
        if nd.name not in feeds:
            raise KeyError(f"feeds missing leaf {nd.name!r}")
        vals[nd.name] = feeds[nd.name]
    # free dead intermediates as execution passes their last consumer —
    # paper-scale grids (jacobi2d n=4096 keeps 64 MiB per sweep) would
    # otherwise all stay resident until the end of the run
    last_use: Dict[str, int] = {}
    for step, nname in enumerate(order):
        for t in program.nodes[nname].inputs:
            last_use[t] = step
    keep = set(program.outputs) if not return_all else set(vals) | set(order)
    for step, nname in enumerate(order):
        node = program.nodes[nname]
        missing = [i for i in node.inputs if i not in vals]
        if missing:
            raise ValueError(f"schedule order not topological: {nname} "
                             f"reads unproduced {missing}")
        vals[nname] = eval_node(node, [vals[i] for i in node.inputs])
        if not return_all:
            for t in set(node.inputs):
                if last_use[t] == step and t not in keep:
                    del vals[t]
    if return_all:
        return vals
    return {o: vals[o] for o in program.outputs}


def evaluate(program, feeds: Optional[Dict[str, Any]] = None, *,
             seed: int = 0, return_all: bool = False) -> Dict[str, Any]:
    """Reference evaluation in the program's natural (build) order."""
    return execute_plan(program, order=None, feeds=feeds, seed=seed,
                        return_all=return_all)


class ReferenceExecutor(Executor):
    """Replay the co-designed schedule order through the interpreter."""

    name = "reference"

    def compile(self, plan):
        # fault-injection site (docs/robustness.md): exec.compile@reference
        faults.check("exec.compile", backend=self.name)
        sharded = getattr(plan, "sharded", None)
        if sharded is not None and sharded.n_shards > 1:
            # mesh-partitioned plan: the sharded oracle replays the same
            # per-op rules under shard_map, gathering reduction operands
            # whole so results stay bitwise-identical to this backend
            from .sharded import ShardedReference
            return ShardedReference(plan)
        program = plan_program(plan)
        order = plan_order(plan)

        def fn(feeds):
            return execute_plan(program, order=order, feeds=feeds)
        return fn
