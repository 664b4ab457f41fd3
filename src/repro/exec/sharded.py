"""Mesh-sharded plan execution for partitioned co-designed plans.

A :class:`~repro.core.lowering.ShardedExecPlan` (``partition_plan``) proves
a co-designed plan splits into K contiguous row blocks; this module makes
the split run.  Two executables, mirroring the single-device pair:

``ShardedReference``
    The bitwise oracle.  It *simulates* the mesh on the host: every
    row-sharded tensor is a list of K local blocks, collectives are exact
    host-driven data movement (gather = concatenate in shard order, halo
    = neighbour boundary rows), and every op evaluates **eagerly**
    through the same per-op rules as
    :func:`~repro.exec.reference.eval_node` — per shard block for
    row-local ops, once on gathered-whole operands for reductions.
    Eager per-op dispatch is what makes bitwise identity *possible*: any
    whole-body traced execution (jit or eager ``shard_map`` — both trace)
    lets XLA:CPU contract mul+add chains into FMAs at codegen (below
    HLO, so even ``lax.optimization_barrier`` cannot stop it), which
    perturbs elementwise ops like ``axpy`` by 1 ulp against the eager
    unsharded oracle.  The simulated mesh keeps each op's dispatch
    identical to the single-device reference, so results are
    bitwise-equal by construction — and the oracle needs no physical
    devices, so partition semantics are testable without
    ``--xla_force_host_platform_device_count``.

``ShardedProgram``
    The real distributed pallas path.  The localized execution plan
    (rows and row tiles divided by K) drives the existing
    :class:`_StreamCall` kernels in ``defer_finalize`` mode: each
    shard's kernel emits raw reduction partials, the driver ``psum``\\ s
    them across the mesh (then applies the norm sqrt) and replays the
    pass's scalar epilogue chain — all inside ONE
    ``jax.jit(shard_map(...))`` per solve, so the single-dispatch
    guarantee survives distribution.  Cross-shard exchanges: contraction
    right-hand sides and spmv ``x`` vectors gather whole
    (``all_gather``), stencil sweeps trade one halo row with each mesh
    neighbour (``ppermute``), CSR triples localize at trace time by
    slicing each shard's indptr-aligned entry window out of the
    (zero-padded) replicated triple.

Reduction partials reassociate across shards (and the one-jit trace
contracts FMAs), so sharded pallas results carry the same documented
tolerance as single-device pallas vs reference
(``docs/execution_backends.md``).  Feed donation is disabled for sharded
programs (the replicated CSR operands outlive their first read).

A ``ShardedProgram`` runs on one mesh (``launch.mesh.make_solver_mesh``,
built once per shard count and shared) and says how each leaf is laid out
on it (:meth:`ShardedProgram.feed_shardings`).  A row-sharded feed given
as a device array must already be laid out so: the dispatch refuses any
other layout rather than let ``jit`` reshard it, which for an operator
larger than one chip would pass it through one device.  Host arrays are
placed row block by row block.
"""
from __future__ import annotations

import dataclasses
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Set

from .. import obs
from ..launch.mesh import make_solver_mesh
from .base import plan_program
from .pallas import (_DISPATCHES, _TRACES, _UNITS, _StreamCall,
                     _unit_needed, count_matvec_tilings, pass_counts,
                     _tiling_of)
from .reference import csr_row_ids, eval_node

_COLLECTIVE_B = obs.registry().counter(
    "exec.collective_bytes", "bytes each shard receives through the "
    "collectives of one dispatch (op: all_gather | psum | ppermute; "
    "all_gather and psum count the other shards' parts, ppermute the "
    "block it delivers), counted from the traced program, per compiled "
    "program (scope label)", unit="B")


class FeedShardingError(ValueError):
    """A row-sharded feed is a device array laid out otherwise than the
    plan runs it."""


# --------------------------------------------------------------------------
# shared shard-local rules
# --------------------------------------------------------------------------

def _localize_csr(env: Dict[str, Any], lay, axis: str) -> None:
    """Replace a CSR triple's replicated global arrays in ``env`` with this
    shard's indptr-aligned window.

    indices/data are padded with ``pad_entries`` zeros *before* slicing,
    so the window never clamps near the tail; positions past a shard's
    true entry count resolve (via the rebased local indptr) to local row
    id ``rows_per_shard`` and are dropped by the out-of-range row mask
    every consumer already applies."""
    import jax.numpy as jnp
    from jax import lax

    rows_loc = lay.slices[0].rows
    r0 = lax.axis_index(axis) * rows_loc
    ip = env[lay.indptr]
    ip_loc = lax.dynamic_slice(ip, (r0,), (rows_loc + 1,))
    e0 = ip_loc[0]
    pad = lay.pad_entries
    ix = jnp.concatenate(
        [env[lay.indices], jnp.zeros((pad,), env[lay.indices].dtype)])
    dv = jnp.concatenate(
        [env[lay.data], jnp.zeros((pad,), env[lay.data].dtype)])
    env[lay.indptr] = ip_loc - e0
    env[lay.indices] = lax.dynamic_slice(ix, (e0,), (pad,))
    env[lay.data] = lax.dynamic_slice(dv, (e0,), (pad,))


def _stencil_shard(node, ins: List[Any], axis: str, n_shards: int,
                   note: Callable[[str, Any], None]):
    """The 5-point stencil rule on one row block: interior columns roll
    locally, the two boundary rows arrive from the mesh neighbours
    (circular, matching ``jnp.roll``'s wrap).  Term order matches
    :func:`eval_node` exactly, so the sharded reference stays bitwise."""
    import jax.numpy as jnp
    from jax import lax

    u = ins[0]
    fwd = [(j, (j + 1) % n_shards) for j in range(n_shards)]
    bwd = [(j, (j - 1) % n_shards) for j in range(n_shards)]
    prev_last = lax.ppermute(u[-1:, :], axis, fwd)    # shard j-1's last row
    next_first = lax.ppermute(u[:1, :], axis, bwd)    # shard j+1's first row
    note("ppermute", prev_last)
    note("ppermute", next_first)
    down = jnp.concatenate([prev_last, u[:-1, :]], axis=0)   # roll(u, 1, 0)
    up = jnp.concatenate([u[1:, :], next_first], axis=0)     # roll(u, -1, 0)
    out = 0.25 * (down + up + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1))
    if len(ins) > 1:
        out = out + 0.25 * float(node.param("h2", 1.0)) * ins[1]
    return out


def _partition_specs(program, sharded):
    """(leaf names, leaf in_specs, out names, out specs) for the shard_map
    wrapper: row-sharded names split on the mesh axis, everything else
    (scalars, CSR triples, off-row operands) replicated."""
    from jax.sharding import PartitionSpec as P

    shard_set = set(sharded.sharded)
    leaves = [nd.name for nd in program.leaves()]
    in_specs = tuple(P(sharded.axis) if n in shard_set else P()
                     for n in leaves)
    outs = list(program.outputs)
    out_specs = tuple(P(sharded.axis) if n in shard_set else P()
                      for n in outs)
    return leaves, in_specs, outs, out_specs


# --------------------------------------------------------------------------
# the sharded reference oracle
# --------------------------------------------------------------------------

def _stencil_block(node, u_parts: List[Any], k: int, f_loc) -> Any:
    """One row block of the 5-point stencil on the simulated mesh: the
    boundary rows come from the neighbour blocks (circular, matching
    ``jnp.roll``'s wrap); term order matches :func:`eval_node` exactly."""
    import jax.numpy as jnp

    n_shards = len(u_parts)
    u = u_parts[k]
    prev_last = u_parts[(k - 1) % n_shards][-1:, :]
    next_first = u_parts[(k + 1) % n_shards][:1, :]
    down = jnp.concatenate([prev_last, u[:-1, :]], axis=0)   # roll(u, 1, 0)
    up = jnp.concatenate([u[1:, :], next_first], axis=0)     # roll(u, -1, 0)
    out = 0.25 * (down + up + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1))
    if f_loc is not None:
        out = out + 0.25 * float(node.param("h2", 1.0)) * f_loc
    return out


class ShardedReference:
    """Bitwise sharded oracle: the reference rules on a simulated mesh.

    Row-sharded tensors live as lists of K per-shard blocks; every op
    dispatches **eagerly** (exactly like the unsharded reference), with
    collectives as exact host-side data movement — see the module
    docstring for why this, and not a traced ``shard_map``, is what a
    bitwise oracle requires."""

    def __init__(self, plan):
        from .base import plan_order

        self.program = plan_program(plan)
        self.sharded = plan.sharded
        self.order = plan_order(plan)
        self.leaf_names = [nd.name for nd in self.program.leaves()]
        self.out_names = list(self.program.outputs)

    def __call__(self, feeds: Dict[str, Any]) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp

        sharded, program = self.sharded, self.program
        shard_set = set(sharded.sharded)
        halo = set(sharded.halo)
        lay_of = {lay.data: lay for lay in sharded.csr}
        K = sharded.n_shards
        rows_loc = sharded.rows_per_shard

        # env: replicated value, or list of K per-shard row blocks
        env: Dict[str, Any] = {}
        for leaf in self.leaf_names:
            if leaf not in feeds:
                raise KeyError(f"feeds missing leaf {leaf!r}")
            v = jnp.asarray(feeds[leaf])
            env[leaf] = ([v[k * rows_loc:(k + 1) * rows_loc]
                          for k in range(K)]
                         if leaf in shard_set else v)
        # CSR triples: each shard's indptr-aligned entry window out of the
        # zero-padded replicated triple (same layout the pallas path slices
        # at trace time)
        csr_loc: Dict[str, List[Any]] = {}
        for lay in sharded.csr:
            ip, ix = env[lay.indptr], env[lay.indices]
            dv, pad = env[lay.data], lay.pad_entries
            ixp = jnp.concatenate([ix, jnp.zeros((pad,), ix.dtype)])
            dvp = jnp.concatenate([dv, jnp.zeros((pad,), dv.dtype)])
            csr_loc[lay.indptr] = []
            csr_loc[lay.indices] = []
            csr_loc[lay.data] = []
            for k in range(K):
                e0 = lay.entry_starts[k]
                r0 = k * rows_loc
                csr_loc[lay.indptr].append(ip[r0:r0 + rows_loc + 1] - e0)
                csr_loc[lay.indices].append(ixp[e0:e0 + pad])
                csr_loc[lay.data].append(dvp[e0:e0 + pad])

        def full(name):
            """Gathered-whole value: concatenate blocks in shard order."""
            v = env[name]
            return jnp.concatenate(v) if isinstance(v, list) else v

        def local(name, k):
            v = env[name]
            return v[k] if isinstance(v, list) else v

        for nname in self.order:
            nd = program.nodes[nname]
            ins = nd.inputs
            if nd.op == "spmv":
                lay = lay_of[ins[2]]
                x = full(ins[3])
                parts = []
                for k in range(K):
                    ip_k = csr_loc[ins[0]][k]
                    seg = csr_row_ids(ip_k, lay.pad_entries)
                    prod = csr_loc[ins[2]][k] * jnp.take(
                        x, csr_loc[ins[1]][k], axis=0)
                    # padding rows resolve to local row id == rows_loc and
                    # are dropped by segment_sum's out-of-range mask
                    parts.append(jax.ops.segment_sum(
                        prod, seg, num_segments=rows_loc))
                env[nname] = parts
            elif nd.op in ("dot", "norm") or (
                    nd.op in ("matmul", "einsum") and nd.shape == ()):
                # reductions run once on gathered-whole operands: the
                # dispatch is identical to the single-device rule
                env[nname] = eval_node(nd, [full(t) for t in ins])
            elif nd.op in ("matmul", "einsum"):
                rhs = full(ins[1])
                env[nname] = [eval_node(nd, [local(ins[0], k), rhs])
                              for k in range(K)]
            elif nname in halo:
                u_parts = env[ins[0]]
                env[nname] = [
                    _stencil_block(nd, u_parts, k,
                                   local(ins[1], k) if len(ins) > 1
                                   else None)
                    for k in range(K)]
            elif nname in shard_set:
                env[nname] = [eval_node(nd, [local(t, k) for t in ins])
                              for k in range(K)]
            else:
                env[nname] = eval_node(nd, [env[t] for t in ins])
        return {o: full(o) for o in self.out_names}


# --------------------------------------------------------------------------
# the sharded pallas single program
# --------------------------------------------------------------------------

def _local_view(program, sharded):
    """The per-shard view of the expression program: row-sharded names
    take their local shapes, CSR members take their localized window
    shapes, and gathered operands are rewired to ``<name>@g`` alias leaves
    that keep the *global* shape (the driver materializes them with
    ``all_gather``)."""
    rows_loc = sharded.rows_per_shard
    shard_set = set(sharded.sharded)
    gathered = set(sharded.gathered)
    csr_shapes: Dict[str, tuple] = {}
    for lay in sharded.csr:
        csr_shapes[lay.indptr] = (rows_loc + 1,)
        csr_shapes[lay.indices] = (lay.pad_entries,)
        csr_shapes[lay.data] = (lay.pad_entries,)

    nodes: Dict[str, Any] = {}
    for name, nd in program.nodes.items():
        shape = tuple(nd.shape)
        if name in csr_shapes:
            shape = csr_shapes[name]
        elif name in shard_set:
            shape = (rows_loc,) + shape[1:]
        inputs = tuple(nd.inputs)
        if nd.op in ("matmul", "einsum") and nd.shape != () \
                and inputs[1] in gathered:
            inputs = (inputs[0], inputs[1] + "@g")
        elif nd.op == "spmv" and inputs[3] in gathered:
            inputs = inputs[:3] + (inputs[3] + "@g",)
        if shape != tuple(nd.shape) or inputs != tuple(nd.inputs):
            nd = dataclasses.replace(nd, shape=shape, inputs=inputs)
        nodes[name] = nd
    for g in sharded.gathered:
        nodes[g + "@g"] = dataclasses.replace(
            program.nodes[g], name=g + "@g", op="input", inputs=())
    return SimpleNamespace(nodes=nodes, outputs=tuple(program.outputs))


class _InlineUnit:
    """A block/jnp unit inlined into the shard body: reference rules per
    op on the shard's rows, stencil sweeps through the halo exchange, and
    rank-0 reductions ``psum``-combined across the mesh.  (Sharded plans
    skip ``_BlockCall``: a whole-array pallas block would need the full
    grid, which is exactly what sharding removes.)"""

    def __init__(self, view, ops, needed: Set[str], halo: Set[str],
                 axis: str, n_shards: int,
                 collectives: "_Collectives"):
        from .pallas import _group_io

        self.nodes = [view.nodes[o] for o in ops]
        in_names, self.out_names = _group_io(view, self.nodes, needed)
        # a value gathered whole after this unit produced it is gathered
        # here, not before the unit runs
        produced = {nd.name for nd in self.nodes}
        self.in_names = [n for n in in_names
                         if not (n.endswith("@g") and n[:-2] in produced)]
        self.halo = halo
        self.axis = axis
        self.n_shards = n_shards
        self.coll = collectives

    def apply(self, env: Dict[str, Any], dtype=None) -> Dict[str, Any]:
        import jax.numpy as jnp
        from jax import lax
        coll = self.coll
        vals = {n: env[n] for n in self.in_names}
        for nd in self.nodes:
            for t in nd.inputs:
                if t not in vals:           # gathered in-unit product
                    vals[t] = coll.all_gather(vals[t[:-2]])
            if nd.name in self.halo:
                vals[nd.name] = _stencil_shard(
                    nd, [vals[t] for t in nd.inputs], self.axis,
                    self.n_shards, coll.note)
            elif nd.op == "norm":
                x = jnp.ravel(vals[nd.inputs[0]])
                vals[nd.name] = jnp.sqrt(coll.psum(jnp.dot(
                    x, x, precision=lax.Precision.HIGHEST)))
            elif nd.op == "dot" or (nd.op in ("matmul", "einsum")
                                    and nd.shape == ()):
                vals[nd.name] = coll.psum(
                    eval_node(nd, [vals[t] for t in nd.inputs]))
            else:
                vals[nd.name] = eval_node(nd,
                                          [vals[t] for t in nd.inputs])
        return {n: vals[n] for n in self.out_names}


class _Collectives:
    """The shard body's collectives.  Each notes, into ``sink`` (the tally
    of the trace in progress), the bytes a shard receives by it: the
    other shards' parts for ``all_gather`` and ``psum``, the delivered
    block for ``ppermute``."""

    def __init__(self, axis: str, n_shards: int):
        self.axis = axis
        self.n_shards = n_shards
        self.sink: Dict[str, int] = {}

    def note(self, op: str, block, parts: int = 1) -> None:
        self.sink[op] = (self.sink.get(op, 0)
                         + parts * block.size * block.dtype.itemsize)

    def all_gather(self, v):
        from jax import lax
        self.note("all_gather", v, self.n_shards - 1)
        return lax.all_gather(v, self.axis, tiled=True)

    def psum(self, v):
        from jax import lax
        self.note("psum", v, self.n_shards - 1)
        return lax.psum(v, self.axis)


def _float_dtype(vals):
    """The dtype a program resolves from its leaves: the promoted type of
    the floating ones (float32 without any)."""
    import jax.numpy as jnp
    floats = [v.dtype for v in vals if jnp.issubdtype(v.dtype, jnp.floating)]
    return jnp.result_type(*floats) if floats else jnp.dtype(jnp.float32)


class ShardedProgram:
    """One whole-plan jitted ``shard_map`` executable for a partitioned
    plan: ``feeds (global) -> {output: value (global)}``.

    Structure mirrors :class:`~repro.exec.pallas._SingleProgram` — the
    localized units trace inside a single jit (rolled loops as
    ``lax.fori_loop``), and ``stats`` counts one dispatch per solve.
    Each dispatch adds its dense contraction passes to
    ``exec.matvec_tiling`` and the bytes its collectives bring each shard
    to ``exec.collective_bytes`` (tallied when the program traced)."""

    def __init__(self, plan):
        program = plan_program(plan)
        sharded = plan.sharded
        self.sharded = sharded
        ep = sharded.local
        units, roll = ep.units, ep.roll
        # "read outside the unit" is a dataflow property of the GLOBAL
        # program (the renamed @g aliases are driver-materialized views,
        # not dataflow), so needed-sets come from the original wiring
        needed, _ = _unit_needed(program, units)
        if roll is not None:
            updates = {sl.update for sl in roll.slots}
            inits = {sl.init for sl in roll.slots if sl.init is not None}
            for ui in range(roll.first, roll.first + roll.per_iter):
                needed[ui] = needed[ui] | (updates & set(units[ui].ops))
            for ui in range(roll.first):
                needed[ui] = needed[ui] | (inits & set(units[ui].ops))
            pro = range(roll.first)
            tmpl = range(roll.first, roll.first + roll.per_iter)
            epi = range(roll.stop, len(units))
        else:
            pro, tmpl, epi = range(len(units)), (), ()

        view = _local_view(program, sharded)
        halo = set(sharded.halo)
        self._coll = _Collectives(sharded.axis, sharded.n_shards)

        def build(i):
            u = units[i]
            if u.kind == "stream":
                return _StreamCall(view, u.sp, needed[i],
                                   defer_finalize=True)
            return _InlineUnit(view, u.ops, needed[i], halo,
                               sharded.axis, sharded.n_shards, self._coll)

        self._pro = [build(i) for i in pro]
        self._tmpl = [build(i) for i in tmpl]
        self._epi = [build(i) for i in epi]
        self.roll = roll
        self.leaf_names, in_specs, self.out_names, out_specs = \
            _partition_specs(program, sharded)
        self._scope = obs.next_scope("pallas")
        for i in (*pro, *tmpl, *epi):
            _UNITS.inc(backend="pallas", kind=units[i].kind,
                       scope=self._scope)
        self.matvec_tilings = pass_counts(self, _tiling_of)
        # {dtype name: {op: bytes a shard receives per dispatch}}, from
        # the trace at that dtype
        self._collective_bytes: Dict[str, Dict[str, int]] = {}

        if roll is not None:
            tmpl_ops = {o for i in tmpl for o in units[i].ops}
            reads = {sl.read for sl in roll.slots if sl.read is not None}
            ext: List[str] = []
            for call in self._tmpl:
                for n in call.in_names:
                    # @g aliases are re-gathered inside the loop body from
                    # their base value; the base is what must be carried in
                    base = n[:-2] if n.endswith("@g") else n
                    if base not in tmpl_ops and base not in reads \
                            and base not in ext:
                        ext.append(base)
            assert all(sl.update in tmpl_ops for sl in roll.slots)
            self._tmpl_ext = ext
            self._slot_shapes = [view.nodes[sl.update].shape
                                 for sl in roll.slots]

        import jax
        from jax.sharding import NamedSharding
        self.mesh = make_solver_mesh(sharded.n_shards, axis=sharded.axis)
        self._shardings = {leaf: NamedSharding(self.mesh, spec)
                           for leaf, spec in zip(self.leaf_names, in_specs)}
        self._row_sharded = set(sharded.sharded) & set(self.leaf_names)
        # no donation: the replicated CSR triples and gathered operands
        # outlive their first read inside the shard body
        # the replication check cannot see through pallas calls mixed
        # with collectives, so it stays off
        self._jit = jax.jit(jax.shard_map(
            self._traced, mesh=self.mesh, in_specs=tuple(in_specs),
            out_specs=tuple(out_specs), check_vma=False))

    def feed_shardings(self) -> Dict[str, Any]:
        """``{leaf: NamedSharding}`` on this program's mesh: row blocks
        over the mesh axis for row-sharded leaves, replicated for the
        rest.  A caller builds a large operand in place on these
        (``docs/distributed.md``)."""
        return dict(self._shardings)

    def _check_feeds(self, args) -> None:
        """Refuse a row-sharded device feed laid out otherwise than
        :meth:`feed_shardings` says (``jit`` would reshard it)."""
        import jax
        for leaf, v in zip(self.leaf_names, args):
            want = self._shardings[leaf]
            if (leaf in self._row_sharded and isinstance(v, jax.Array)
                    and not v.sharding.is_equivalent_to(want, v.ndim)):
                raise FeedShardingError(
                    f"feed {leaf!r} is laid out as {v.sharding}, but the "
                    f"plan runs it in row blocks over mesh axis "
                    f"{self.sharded.axis!r} ({want}); build it in place "
                    f"on plan.feed_shardings()[{leaf!r}] or pass a host "
                    f"array (docs/distributed.md)")

    @property
    def stats(self) -> Dict[str, int]:
        return {
            "traces": int(_TRACES.value(backend="pallas",
                                        scope=self._scope)),
            "dispatches": int(_DISPATCHES.value(backend="pallas",
                                                scope=self._scope)),
        }

    # -- per-unit driver (inside the shard_map trace) -------------------
    def _run_call(self, call, env: Dict[str, Any], dtype) -> None:
        import jax.numpy as jnp

        for n in call.in_names:
            if n.endswith("@g") and n not in env:
                env[n] = self._coll.all_gather(env[n[:-2]])
        out = call.apply(env, dtype)
        if isinstance(call, _StreamCall) and call.defer:
            norm = call.norm_reductions
            for n in call.red_out:
                v = self._coll.psum(out[n])
                out[n] = jnp.sqrt(v) if n in norm else v
            env.update(out)
            # the pass's scalar chain (eager + epilogue), replayed on the
            # combined reductions — replicated, so every shard agrees
            for nd in call.finalize_nodes:
                env[nd.name] = eval_node(nd,
                                         [env[t] for t in nd.inputs])
        else:
            env.update(out)

    # -- the traced shard body ------------------------------------------
    def _traced(self, *leaf_vals):
        import jax.numpy as jnp
        _TRACES.inc(backend="pallas", scope=self._scope)
        dtype = _float_dtype(leaf_vals)
        tally: Dict[str, int] = {}
        self._coll.sink = tally
        env: Dict[str, Any] = {}
        for name, v in zip(self.leaf_names, leaf_vals):
            env[name] = (jnp.asarray(v, dtype)
                         if jnp.issubdtype(v.dtype, jnp.floating) else v)
        for lay in self.sharded.csr:
            _localize_csr(env, lay, self.sharded.axis)
        for call in self._pro:
            self._run_call(call, env, dtype)
        if self.roll is not None:
            from jax import lax
            slots = self.roll.slots
            base = {n: env[n] for n in self._tmpl_ext}
            per_iter: Dict[str, int] = {}

            def body(_, carry):
                per_iter.clear()            # one iteration's collectives
                self._coll.sink = per_iter
                env_l = dict(base)
                for sl, v in zip(slots, carry):
                    if sl.read is not None:
                        env_l[sl.read] = v
                for call in self._tmpl:
                    self._run_call(call, env_l, dtype)
                return tuple(env_l[sl.update] for sl in slots)

            carry = tuple(
                env[sl.init] if sl.init is not None
                else jnp.zeros(shape, dtype)
                for sl, shape in zip(slots, self._slot_shapes))
            carry = lax.fori_loop(0, self.roll.n_iters, body, carry)
            for sl, v in zip(slots, carry):
                env[sl.final] = v
            for op, b in per_iter.items():
                tally[op] = tally.get(op, 0) + b * self.roll.n_iters
            self._coll.sink = tally
        for call in self._epi:
            self._run_call(call, env, dtype)
        self._collective_bytes[jnp.dtype(dtype).name] = tally
        return tuple(env[o] for o in self.out_names)

    # -- the dispatch ---------------------------------------------------
    def __call__(self, feeds: Dict[str, Any]) -> Dict[str, Any]:
        import jax.numpy as jnp
        args = []
        for leaf in self.leaf_names:
            if leaf not in feeds:
                raise KeyError(f"feeds missing leaf {leaf!r}")
            args.append(feeds[leaf])
        with obs.span("exec.feed_check"):
            self._check_feeds(args)
        _DISPATCHES.inc(backend="pallas", scope=self._scope)
        count_matvec_tilings(self)
        with obs.span("exec.launch"):
            outs = self._jit(*args)
        sent = self._collective_bytes.get(jnp.dtype(_float_dtype(args)).name,
                                          {})
        for op, b in sent.items():
            _COLLECTIVE_B.inc(b, backend="pallas", op=op, scope=self._scope)
        return dict(zip(self.out_names, outs))
