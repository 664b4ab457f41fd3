"""The ``pallas`` execution backend: whole-plan single-program executables.

A compiled plan executes as **one jitted device program**: every stream /
block / jnp unit of the execution plan (``core.lowering.plan_execution``)
is traced inside a single ``jax.jit``, so a ``run()`` is exactly one device
dispatch — no per-unit Python driver, no scalar round-trips between
kernels, no per-call ``result_type``/``asarray`` conversion.  The pieces:

* ``stream`` units run ``pl.pallas_call`` with a 1-D grid over row tiles of
  the unit's shared streamed length.  Rank-1 vectors enter every kernel as
  ``(1, n)`` rows streamed in ``(1, tile)`` blocks (lane-dense, which is
  what Mosaic tiles); matrices stream ``(tile, cols)`` blocks.  Contraction
  right-hand sides use a *constant index map*, so Pallas keeps them
  resident in VMEM across every grid step — the execution-level image of
  the plan's explicit-region pins.  A matvec ``A @ x`` is computed as the
  row ``x · A_tileᵀ`` so its result lands in the vector layout.  Rank-0
  values live in SMEM: tile-invariant scalars are inputs, and dot/norm
  reductions accumulate into ``(1, 1)`` SMEM outputs across the pass.
  *Eager* scalars (rank-0 glue whose in-pass inputs are tile-invariant,
  e.g. ``nalpha = -alpha``) are computed before the kernel and read from
  SMEM, so tiled ops use them without a pass break; reduction-derived
  scalar epilogues (``beta = rs'/rs``, a norm's ``sqrt``) run after it.
* CSR SpMV ops run inside stream passes on a padded per-tile entry
  layout: tile ``t`` owns exactly its own rows' entries, padded to ``B``
  slots (static, from the operand's pattern meta).  The layout — column
  ids and values as contiguous per-tile windows, and each row's slot
  range inside its window — is derived once per dispatch from the CSR
  leaves; each pass gathers ``values * x[cols]`` in XLA (Mosaic cannot
  gather by column index in-kernel) and the kernel sums each tile's rows
  as one MXU product with a one-hot row matrix built from those ranges —
  no scatter.  Feeds must match the pattern meta the plan was built for
  (:func:`core.lowering.check_csr_feeds` refuses those that do not).
* ``block`` units hold whole arrays as single blocks (stencil halos).
* ``jnp`` units — irregular gathers, >2-operand einsums, working sets
  over one kernel's VMEM — inline the reference rules straight into the
  trace; ``explain()`` names each one's reason.
* Adjacent units fused by the residency planner execute as one pass, so
  operands resident across former pass/group boundaries are not
  re-streamed (``core.lowering.fuse_units``).
* When the frontend recorded iteration bodies and
  ``core.lowering.detect_rolled_loop`` proved the scheduled units repeat
  them, the repeated segment runs as ``lax.fori_loop`` over one compiled
  body — ``cg(iters=64)`` traces one iteration, not 64.

Dtype is resolved once per trace from the leaf avals (jit retraces on a
dtype change); feeds are donated to the executable where the backend
supports it (never consuming caller-owned device buffers — those are
copied first); dead intermediates need no runtime ``del``: inside one
traced program, XLA's buffer liveness frees them.

The PR-3 per-unit driver is kept as the ``pallas-perunit`` backend — one
dispatch per unit, runtime freeing — as the A/B baseline TABLE 8 measures
the single-program speedup against.

On a TPU every kernel compiles through Mosaic with the grid marked
``arbitrary`` (accumulation makes steps order-dependent) and a scoped-VMEM
limit taken from the tile plan; on any other platform kernels run with
``interpret=True``, so CI exercises the same lowering.
``CELLO_PALLAS_INTERPRET=0`` forces Mosaic off the chip (compiling for a
described TPU); interpret mode is never taken on a TPU.  Donation follows
the platform too; ``CELLO_PALLAS_DONATE=0/1`` overrides it.  Kernels run
up to 32-bit floats on the chip: a float64 program raises
:class:`KernelDtypeError` before it reaches Mosaic.

Numerics: tiled reductions re-associate the sum (per-tile partials), and
contractions run on the MXU at ``Precision.HIGHEST``, so outputs match the
``reference`` backend within the tolerances documented in
``docs/execution_backends.md`` rather than bitwise.  Everything
elementwise, block kernels and jnp units use the reference rules verbatim.
"""
from __future__ import annotations

import os
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from .. import obs
from ..testing import faults
from ..core.lowering import (STREAM_EINSUMS, ExecPlan, GroupKernel,
                             StreamPass, check_csr_feeds, csr_tile_entries,
                             flatten_units, kernel_block_bytes,
                             plan_execution, select_group_kernels)
from .base import Executor, plan_groups, plan_program
from .reference import eval_node

_TRACES = obs.registry().counter(
    "exec.traces", "jit trace-time Python body executions, per compiled "
    "program (scope label)")
_DISPATCHES = obs.registry().counter(
    "exec.dispatches", "device dispatches, per compiled program "
    "(scope label)")
_FEED_COPY_B = obs.registry().counter(
    "exec.feed_copy_bytes", "bytes of caller-owned device feeds copied "
    "before the executable consumes (donates) them, per compiled program "
    "(scope label)", unit="B")
_UNITS = obs.registry().counter(
    "exec.units", "execution units built at compile, by kind "
    "(stream | block | jnp)")

_BACKEND_PROBE: Optional[str] = None

#: ``jax.named_scope`` names of the device work that runs outside the
#: kernels: the spmv's ``values * x[cols]`` gather, and the CSR operand's
#: per-tile layout.  They must not start with ``cello_``, the kernels'
#: prefix.  A device trace names ops by their HLO instruction alone;
#: :meth:`_SingleProgram.device_scopes` maps those names back to scopes.
GATHER_SCOPE = "spmv_gather"
LAYOUT_SCOPE = "csr_layout"
DEVICE_SCOPES = (GATHER_SCOPE, LAYOUT_SCOPE)

_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                     r'metadata=\{[^}]*?op_name="([^"]*)"')

#: headroom above a kernel's planned VMEM for Mosaic's internal scratch,
#: and the ceiling no kernel asks past (a TPU v5e core has 128 MiB)
_VMEM_HEADROOM = 8 << 20
_VMEM_DEFAULT = 16 << 20
_VMEM_CEILING = 100 << 20


class KernelDtypeError(TypeError):
    """A dtype the TPU kernels do not run reached Mosaic (float64: the
    chip has no fp64 vector unit).  Raised at trace time, so no backend
    substitutes for the kernel unless the caller configured one."""


def _default_backend() -> str:
    """``jax.default_backend()``, probed once per process (the probe
    imports jax and touches the platform registry — too slow per call)."""
    global _BACKEND_PROBE
    if _BACKEND_PROBE is None:
        import jax
        _BACKEND_PROBE = jax.default_backend()
    return _BACKEND_PROBE


def default_solver_backend() -> str:
    """The backend a solve that names none runs on: the compiled kernels
    on a TPU, the op-by-op ``reference`` oracle on every other platform
    (where pallas kernels could only be interpreted)."""
    return "pallas" if _default_backend() == "tpu" else "reference"


def _env_flag(name: str) -> Optional[bool]:
    env = os.environ.get(name)
    if env is None or not env.strip():
        return None                      # unset/empty: use the default
    return env.strip().lower() not in ("0", "false", "no")


def use_interpret() -> bool:
    """Interpret Pallas kernels unless we are actually on a TPU (CI and
    laptops exercise the same lowering through the interpreter).
    ``CELLO_PALLAS_INTERPRET=0`` compiles through Mosaic anywhere (the
    compile-only rehearsal for a described chip); asking for interpret
    mode on a TPU is an error, never a silent slow path."""
    env = _env_flag("CELLO_PALLAS_INTERPRET")
    on_tpu = _default_backend() == "tpu"
    if env and on_tpu:
        raise RuntimeError("CELLO_PALLAS_INTERPRET asks for interpret mode "
                           "on a TPU; kernels compile through Mosaic there")
    return (not on_tpu) if env is None else env


def use_donation() -> bool:
    """Donate leaf feeds into the executable (dead after their last read).
    Off on CPU, where XLA ignores donation and warns."""
    env = _env_flag("CELLO_PALLAS_DONATE")
    if env is not None:
        return env
    return _default_backend() != "cpu"


def _vmem_limit(planned_bytes: int) -> int:
    return min(max(planned_bytes + _VMEM_HEADROOM, _VMEM_DEFAULT),
               _VMEM_CEILING)


def _pallas_call_kwargs(name: str, dtype, planned_vmem: int,
                        grid_dims: int) -> Dict[str, Any]:
    """Interpret mode off the chip; otherwise Mosaic compiler params — the
    grid stays sequential (accumulating reductions make grid steps
    order-dependent) and the scoped-VMEM limit follows the tile plan."""
    import jax.numpy as jnp
    if use_interpret():
        return {"interpret": True, "name": name}
    if jnp.dtype(dtype).itemsize > 4:
        raise KernelDtypeError(
            f"kernel {name!r}: {jnp.dtype(dtype).name} does not run on the "
            "TPU kernels (no fp64 vector unit); solve in float32, or run "
            "the plan on the reference backend explicitly")
    from jax.experimental.pallas import tpu as pltpu
    return {"name": name, "compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * grid_dims,
        vmem_limit_bytes=_vmem_limit(planned_vmem))}


def _row_shape(shape) -> tuple:
    """The kernel-side shape of an array: rank-1 vectors are ``(1, n)``
    rows, rank-0 values ``(1, 1)`` SMEM cells."""
    if len(shape) == 0:
        return (1, 1)
    return (1,) + tuple(shape) if len(shape) == 1 else tuple(shape)


# --------------------------------------------------------------------------
# node classification inside a streaming pass
# --------------------------------------------------------------------------

def _classify_nodes(nodes) -> Dict[str, str]:
    """"tiled" | "reduce" | "eager" | "epilogue" per node of one pass.

    ``eager`` scalars have tile-invariant in-pass inputs and are computed
    before the kernel; ``epilogue`` scalars depend on an in-pass reduction
    and are computed after it.
    """
    classes: Dict[str, str] = {}
    late: Set[str] = set()
    for nd in nodes:
        if nd.op in ("dot", "norm") or (nd.op in ("matmul", "einsum")
                                        and nd.shape == ()):
            classes[nd.name] = "reduce"
            late.add(nd.name)
        elif nd.shape == ():
            if any(t in late for t in nd.inputs):
                classes[nd.name] = "epilogue"
                late.add(nd.name)
            else:
                classes[nd.name] = "eager"
        else:
            classes[nd.name] = "tiled"
    return classes


# --------------------------------------------------------------------------
# kernel builders (one per ExecUnit kind)
# --------------------------------------------------------------------------

class _StreamCall:
    """One tile-streaming ``pl.pallas_call`` for a :class:`StreamPass`.

    With ``defer_finalize=True`` (sharded execution) the call returns raw
    per-shard reduction partials: no ``sqrt`` on norm accumulators, no
    epilogue — the sharded program combines partials with ``psum`` and
    replays the epilogue (:attr:`finalize_nodes`) inside the
    ``shard_map`` trace."""

    def __init__(self, program, sp: StreamPass, needed: Set[str], *,
                 defer_finalize: bool = False):
        self.nodes = [program.nodes[o] for o in sp.ops]
        self.sp = sp
        self.defer = defer_finalize
        produced = {nd.name for nd in self.nodes}
        self.shapes = {n: tuple(program.nodes[n].shape)
                       for nd in self.nodes for n in (*nd.inputs, nd.name)}
        self.classes = _classify_nodes(self.nodes)

        in_names: List[str] = []
        stream_in: List[str] = []
        res_in: List[str] = []
        scalar_in: List[str] = []

        def _want(name: str, bucket: List[str]):
            if name not in bucket:
                bucket.append(name)

        tr = sp.tile_rows
        # per spmv: per-tile entry values and each row's slot range, built
        # from loop-invariant layouts derived once per dispatch
        self.spmv: Dict[str, Tuple[str, str]] = {}
        self.tile_in: List[str] = []
        self.derived: Dict[str, Callable] = {}
        for nd in self.nodes:
            for t in nd.inputs:
                if t not in produced:
                    _want(t, in_names)
            cls = self.classes[nd.name]
            if nd.op == "spmv":
                ipn = nd.inputs[0]
                entries = csr_tile_entries(program.nodes[ipn].params,
                                           sp.rows, tr)
                lay = f"{ipn}@t{tr}"
                self.derived[lay] = _csr_tiles_fn(*nd.inputs[:3], tr,
                                                  entries)
                self.spmv[nd.name] = (lay, nd.inputs[3])
                self.shapes[f"{nd.name}@vals"] = \
                    (sp.rows // tr, 1, entries)
                self.tile_in += [f"{nd.name}@vals", f"{nd.name}@first",
                                 f"{nd.name}@stop"]
            elif cls == "tiled" and nd.op in ("matmul", "einsum"):
                rhs = STREAM_EINSUMS[nd.param("spec")]
                if nd.inputs[1 - rhs] not in produced:
                    _want(nd.inputs[1 - rhs], stream_in)
                _want(nd.inputs[rhs], res_in)
            elif cls in ("tiled", "reduce"):
                for t in nd.inputs:
                    if self.shapes[t] == ():
                        _want(t, scalar_in)     # external or eager scalar
                    elif t not in produced:
                        _want(t, stream_in)
        self.in_names = in_names
        self.stream_in, self.res_in, self.scalar_in = \
            stream_in, res_in, scalar_in
        self.eager = [nd for nd in self.nodes
                      if self.classes[nd.name] == "eager"]
        self.epilogue = [nd for nd in self.nodes
                         if self.classes[nd.name] == "epilogue"]
        # reductions always need an output to accumulate into; streamed
        # values only when read outside this pass
        self.red_out = [nd.name for nd in self.nodes
                        if self.classes[nd.name] == "reduce"]
        self.stream_out = [nd.name for nd in self.nodes
                           if self.classes[nd.name] == "tiled"
                           and nd.name in needed]
        self.needed = needed
        self._built: Dict[Any, Callable] = {}

    def _build(self, dtype):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        tr = self.sp.tile_rows
        n_tiles = self.sp.rows // tr
        nodes, classes = self.nodes, self.classes
        ins = self.stream_in + self.tile_in + self.res_in + self.scalar_in
        n_in = len(ins)
        stream_out_set = set(self.stream_out)
        hi = lax.Precision.HIGHEST

        def kernel(*refs):
            i = pl.program_id(0)
            # by position: a contraction's resident RHS may also stream
            # elsewhere in the pass (``p`` in ``A @ p`` and ``x + a * p``)
            it = iter(refs[:n_in])
            sref, tref, rref, cref = (
                {n: next(it) for n in names}
                for names in (self.stream_in, self.tile_in, self.res_in,
                              self.scalar_in))
            oref = dict(zip(self.red_out + self.stream_out, refs[n_in:]))
            tiles: Dict[str, Any] = {}

            def val(name):                 # streamed tile or SMEM scalar
                if name in cref:
                    return cref[name][0, 0]
                if name not in tiles:
                    tiles[name] = sref[name][...]
                return tiles[name]

            for nd in nodes:
                cls = classes[nd.name]
                if cls == "tiled":
                    if nd.op == "spmv":         # one-hot row sum, MXU
                        vals = tref[f"{nd.name}@vals"][...]     # (1, B)
                        first = tref[f"{nd.name}@first"][...]   # (1, tr)
                        stop = tref[f"{nd.name}@stop"][...]
                        slot = lax.broadcasted_iota(
                            jnp.int32, (vals.shape[-1], tr), 0)
                        onehot = (slot >= first) & (slot < stop)
                        v = jnp.dot(vals, onehot.astype(dtype),
                                    precision=hi,
                                    preferred_element_type=dtype)
                    elif nd.op in ("matmul", "einsum"):
                        spec = nd.param("spec")
                        rhs = STREAM_EINSUMS[spec]
                        lhs = val(nd.inputs[1 - rhs])
                        right = rref[nd.inputs[rhs]][...]
                        if spec == "ab,b->a":   # row x (1, m) . A_tile^T
                            v = lax.dot_general(
                                right, lhs, (((1,), (1,)), ((), ())),
                                precision=hi, preferred_element_type=dtype)
                        else:
                            v = jnp.dot(lhs, right, precision=hi,
                                        preferred_element_type=dtype)
                    else:
                        v = eval_node(nd, [val(t) for t in nd.inputs])
                    tiles[nd.name] = v
                    if nd.name in stream_out_set:
                        oref[nd.name][...] = v.astype(dtype)
                elif cls == "reduce":
                    a = val(nd.inputs[0])
                    b = a if nd.op == "norm" else val(nd.inputs[1])
                    _accumulate(oref[nd.name], jnp.sum(a * b), i)

        def stream_spec(shape):
            if len(shape) == 1:
                return pl.BlockSpec((1, tr), lambda i: (0, i))
            return pl.BlockSpec((tr,) + shape[1:],
                                lambda i: (i,) + (0,) * (len(shape) - 1))

        def full_spec(shape):
            shape = _row_shape(shape)
            return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

        def tile_spec(name):
            if not name.endswith("@vals"):      # rows' slot bounds
                return pl.BlockSpec((1, tr), lambda i: (0, i))
            return pl.BlockSpec((None, 1, self.shapes[name][-1]),
                                lambda i: (i, 0, 0))

        smem = pl.BlockSpec((1, 1), lambda i: (0, 0),
                            memory_space=pltpu.SMEM)
        in_specs = ([stream_spec(self.shapes[n]) for n in self.stream_in]
                    + [tile_spec(n) for n in self.tile_in]
                    + [full_spec(self.shapes[n]) for n in self.res_in]
                    + [smem] * len(self.scalar_in))
        out_specs = ([smem] * len(self.red_out)
                     + [stream_spec(self.shapes[n])
                        for n in self.stream_out])
        out_shape = ([jax.ShapeDtypeStruct((1, 1), dtype)
                      for _ in self.red_out]
                     + [jax.ShapeDtypeStruct(_row_shape(self.shapes[n]),
                                             dtype)
                        for n in self.stream_out])
        return pl.pallas_call(
            kernel, grid=(n_tiles,), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            **_pallas_call_kwargs(f"cello_stream_{self.sp.ops[0]}", dtype,
                                  self.sp.vmem_bytes, 1))

    # -- drivers --------------------------------------------------------
    def apply(self, env: Dict[str, Any], dtype) -> Dict[str, Any]:
        """Run (or trace) this pass over ``env`` at a resolved ``dtype``."""
        import jax
        import jax.numpy as jnp
        vals: Dict[str, Any] = {}

        def get(n):
            return vals[n] if n in vals else env[n]

        for nd in self.eager:
            vals[nd.name] = eval_node(nd, [get(t) for t in nd.inputs])
        if self.red_out or self.stream_out:
            call = self._built.get(dtype)
            if call is None:
                call = self._built[dtype] = self._build(dtype)
            tile_args = []
            for lay, x in self.spmv.values():
                cols, data, first, stop = (
                    env[lay] if lay in env else self.derived[lay](env, dtype))
                with jax.named_scope(GATHER_SCOPE):
                    vals_t = data * jnp.asarray(env[x], dtype)[cols]
                tile_args += [vals_t, first, stop]
            row = [jnp.reshape(jnp.asarray(env[n], dtype),
                               _row_shape(self.shapes[n]))
                   for n in self.stream_in + self.res_in]
            args = (row[:len(self.stream_in)] + tile_args
                    + row[len(self.stream_in):]
                    + [jnp.reshape(jnp.asarray(get(n), dtype), (1, 1))
                       for n in self.scalar_in])
            outs = call(*args)
            for n, v in zip(self.red_out + self.stream_out, outs):
                vals[n] = jnp.reshape(v, self.shapes[n])
        if not self.defer:
            for n in self.norm_reductions:
                vals[n] = jnp.sqrt(vals[n])
            for nd in self.epilogue:
                vals[nd.name] = eval_node(nd, [get(t) for t in nd.inputs])
            keep = self.needed
        else:           # the caller finalizes: partials + eager scalars
            keep = (self.needed | set(self.red_out)
                    | {nd.name for nd in self.eager})
        return {n: v for n, v in vals.items() if n in keep}

    @property
    def finalize_nodes(self):
        """The epilogue nodes a deferring caller must replay after
        combining reduction partials, in pass order."""
        return list(self.epilogue)

    @property
    def norm_reductions(self) -> Set[str]:
        """Reduction outputs that are *squared* partials until the sqrt
        (after the pass, or after the cross-shard sum when deferred)."""
        return {nd.name for nd in self.nodes
                if nd.op == "norm" and self.classes[nd.name] == "reduce"}

    def __call__(self, env: Dict[str, Any]) -> Dict[str, Any]:
        import jax.numpy as jnp
        dtype = jnp.result_type(*(env[n].dtype for n in self.in_names))
        return self.apply(env, dtype)


def _csr_tiles_fn(indptr: str, indices: str, data: str, tile_rows: int,
                  entries: int) -> Callable:
    """A function building one CSR operand's padded per-tile layout:
    column ids and values as ``(tiles, 1, entries)`` windows, each
    starting at its tile's first entry, and every row's slot range
    ``[first, stop)`` inside its tile's window as ``(1, rows)`` vectors.
    Slots past a tile's last row hold the next tile's entries (or zeros),
    which no row's range covers.  Windows are contiguous slices, so the
    layout needs no per-entry search."""
    def build(env, dtype):
        import jax
        with jax.named_scope(LAYOUT_SCOPE):
            return _csr_tiles(env, dtype)

    def _csr_tiles(env, dtype):
        import jax
        import jax.numpy as jnp
        from jax import lax
        ip = jnp.asarray(env[indptr])
        rows = ip.shape[0] - 1
        start = ip[:-1:tile_rows]                     # (tiles,)
        base = jnp.broadcast_to(start[:, None], (rows // tile_rows,
                                                 tile_rows)).reshape(rows)

        def windows(a):
            # padded so no window is clamped back into the array
            a = jnp.concatenate([a, jnp.zeros((entries,), a.dtype)])
            return jax.vmap(lambda s: lax.dynamic_slice(
                a, (s,), (entries,)))(start)[:, None, :]

        first = (ip[:-1] - base)[None, :].astype(jnp.int32)
        stop = (ip[1:] - base)[None, :].astype(jnp.int32)
        return (windows(jnp.asarray(env[indices])),
                windows(jnp.asarray(env[data], dtype)), first, stop)
    return build


def _accumulate(ref, part, i):
    from jax.experimental import pallas as pl

    @pl.when(i == 0)
    def _():
        ref[0, 0] = part

    @pl.when(i > 0)
    def _():
        ref[0, 0] = ref[0, 0] + part


def _group_io(program, nodes, needed: Set[str]):
    """(external inputs, needed outputs) for one op group, in op order."""
    produced = {nd.name for nd in nodes}
    in_names: List[str] = []
    for nd in nodes:
        for t in nd.inputs:
            if t not in produced and t not in in_names:
                in_names.append(t)
    return in_names, [nd.name for nd in nodes if nd.name in needed]


class _BlockCall:
    """Whole-array single-block kernel for halo (stencil) groups."""

    def __init__(self, program, ops: Sequence[str], needed: Set[str]):
        self.nodes = [program.nodes[o] for o in ops]
        self.in_names, self.out_names = _group_io(program, self.nodes,
                                                  needed)
        self.shapes = {n: tuple(program.nodes[n].shape)
                       for nd in self.nodes for n in (*nd.inputs, nd.name)}
        self._built: Dict[Any, Callable] = {}

    def _build(self, dtype):
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        n_in = len(self.in_names)
        scalars = {n for n in self.in_names if self.shapes[n] == ()}

        def kernel(*refs):
            vals = {n: r[0, 0] if n in scalars else r[...]
                    for n, r in zip(self.in_names, refs[:n_in])}
            for nd in self.nodes:
                vals[nd.name] = eval_node(nd,
                                          [vals[t] for t in nd.inputs])
            for n, r in zip(self.out_names, refs[n_in:]):
                r[...] = vals[n]

        planned = 2 * sum(kernel_block_bytes(self.shapes[n])
                          for n in (*self.in_names, *self.out_names))
        return pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM if n in scalars
                                   else pltpu.VMEM)
                      for n in self.in_names],
            out_shape=[jax.ShapeDtypeStruct(_row_shape(self.shapes[n]),
                                            dtype)
                       for n in self.out_names],
            **_pallas_call_kwargs(f"cello_block_{self.nodes[0].name}",
                                  dtype, planned, 0))

    def apply(self, env: Dict[str, Any], dtype) -> Dict[str, Any]:
        import jax.numpy as jnp
        call = self._built.get(dtype)
        if call is None:
            call = self._built[dtype] = self._build(dtype)
        outs = call(*[jnp.reshape(jnp.asarray(env[n], dtype),
                                  _row_shape(self.shapes[n]))
                      for n in self.in_names])
        return {n: jnp.reshape(v, self.shapes[n])
                for n, v in zip(self.out_names, outs)}

    def __call__(self, env: Dict[str, Any]) -> Dict[str, Any]:
        import jax.numpy as jnp
        dtype = jnp.result_type(*(env[n].dtype for n in self.in_names))
        return self.apply(env, dtype)


class _JnpCall:
    """jax.numpy fallback for one non-streamable group.  Inside a
    single-program trace it inlines straight into the outer jit; driven
    standalone (``pallas-perunit``) it jits itself lazily on first call, so
    compiling a plan never eagerly builds closures for units a rolled loop
    may subsume."""

    def __init__(self, program, ops: Sequence[str], needed: Set[str]):
        self.nodes = [program.nodes[o] for o in ops]
        self.in_names, self.out_names = _group_io(program, self.nodes,
                                                  needed)
        self._fn = None                    # jitted lazily (standalone only)

    def _f(self, *args):
        vals = dict(zip(self.in_names, args))
        for nd in self.nodes:
            vals[nd.name] = eval_node(nd, [vals[t] for t in nd.inputs])
        return tuple(vals[n] for n in self.out_names)

    def apply(self, env: Dict[str, Any], dtype=None) -> Dict[str, Any]:
        outs = self._f(*[env[n] for n in self.in_names])
        return dict(zip(self.out_names, outs))

    def __call__(self, env: Dict[str, Any]) -> Dict[str, Any]:
        if self._fn is None:
            import jax
            self._fn = jax.jit(self._f)
        outs = self._fn(*[env[n] for n in self.in_names])
        return dict(zip(self.out_names, outs))


def _build_call(program, unit, needed: Set[str]):
    if unit.kind == "stream":
        return _StreamCall(program, unit.sp, needed)
    if unit.kind == "block":
        return _BlockCall(program, unit.ops, needed)
    return _JnpCall(program, unit.ops, needed)


# --------------------------------------------------------------------------
# plan plumbing shared by both pallas drivers
# --------------------------------------------------------------------------

def _plan_explicit_bytes(plan) -> int:
    sched = (plan.codesigned.best.schedule
             if plan.codesigned is not None else None)
    return sched.config.explicit_bytes if sched is not None else 0


def _plan_kernels(plan, groups) -> Tuple[GroupKernel, ...]:
    kernels = getattr(plan, "group_kernels", ()) or ()
    if len(kernels) == len(groups):
        return tuple(kernels)
    return select_group_kernels(plan.trace.graph, groups,
                                _plan_explicit_bytes(plan))


def _plan_exec(plan, program, kernels) -> ExecPlan:
    """The plan's carried :class:`ExecPlan` when it matches the kernel
    selection, else a freshly computed one."""
    ep = getattr(plan, "exec_plan", None)
    if ep is not None:
        flat = [o for u in ep.units for o in u.ops]
        if flat == [o for gk in kernels for o in gk.ops]:
            return ep
    return plan_execution(plan.trace.graph, kernels,
                          _plan_explicit_bytes(plan), program=program)


def _unit_needed(program, units
                 ) -> Tuple[List[Set[str]], Dict[str, List[int]]]:
    """Per-unit "read outside this unit" sets over the straight-line unit
    sequence (program outputs always count), plus the tensor -> consuming
    unit indices map they were derived from."""
    outputs = set(program.outputs)
    consumers: Dict[str, List[int]] = {}
    for ui, unit in enumerate(units):
        for o in unit.ops:
            for t in program.nodes[o].inputs:
                consumers.setdefault(t, []).append(ui)
    needed = [{o for o in unit.ops
               if o in outputs or any(c > ui for c in consumers.get(o, ()))}
              for ui, unit in enumerate(units)]
    return needed, consumers


# --------------------------------------------------------------------------
# the single-program executable
# --------------------------------------------------------------------------

class _SingleProgram:
    """One whole-plan jitted executable: ``feeds -> {output: value}``.

    All units trace inside a single ``jax.jit``; a detected rolled loop
    runs as ``lax.fori_loop`` over the template body's calls.  ``stats``
    counts traces (Python body executions under jit) and device dispatches
    (calls of the one jitted function) — the one-dispatch guarantee is
    ``dispatches == runs`` with ``traces`` staying at 1 per dtype.
    """

    def __init__(self, plan):
        program = plan_program(plan)
        groups = plan_groups(plan)
        kernels = _plan_kernels(plan, groups)
        ep = _plan_exec(plan, program, kernels)
        self.exec_plan = ep
        self._program = program
        units, roll = ep.units, ep.roll
        needed, _ = _unit_needed(program, units)
        if roll is not None:
            # loop-carried values must leave their kernels even when the
            # straight-line view says nothing later reads them
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
        self._pro = [_build_call(program, units[i], needed[i]) for i in pro]
        self._tmpl = [_build_call(program, units[i], needed[i])
                      for i in tmpl]
        self._epi = [_build_call(program, units[i], needed[i]) for i in epi]
        self.roll = roll
        self.leaf_names = [nd.name for nd in program.leaves()]
        self.out_names = list(program.outputs)
        # counters live on the global registry under this program's unique
        # scope label, so per-program exactness survives sharing one
        # registry definition across every compiled program
        self._scope = obs.next_scope("pallas")
        for i in (*pro, *tmpl, *epi):
            _UNITS.inc(backend="pallas", kind=units[i].kind,
                       scope=self._scope)

        if roll is not None:
            tmpl_ops = {o for i in tmpl for o in units[i].ops}
            reads = {sl.read for sl in roll.slots if sl.read is not None}
            ext: List[str] = []
            for call in self._tmpl:
                for n in (*call.in_names, *getattr(call, "derived", ())):
                    if n not in tmpl_ops and n not in reads \
                            and n not in ext:
                        ext.append(n)
            # detect_rolled_loop guarantees every carry update is produced
            # by the template (it bails out otherwise)
            assert all(sl.update in tmpl_ops for sl in roll.slots)
            self._tmpl_ext = ext
            self._slot_shapes = [program.nodes[sl.update].shape
                                 for sl in roll.slots]

        self._donate = use_donation()
        # every leaf dies inside the program (outputs are op-produced)
        self.donate_argnums = tuple(range(len(self.leaf_names)))
        import jax
        kwargs = ({"donate_argnums": self.donate_argnums}
                  if self._donate else {})
        self._jit = jax.jit(self._traced, **kwargs)

    @property
    def stats(self) -> Dict[str, int]:
        """This program's counters, read back from the obs registry
        (``{"traces": ..., "dispatches": ...}``, dict-comparable)."""
        return {
            "traces": int(_TRACES.value(backend="pallas",
                                        scope=self._scope)),
            "dispatches": int(_DISPATCHES.value(backend="pallas",
                                                scope=self._scope)),
        }

    # -- the traced program --------------------------------------------
    def _traced(self, *leaf_vals):
        import jax.numpy as jnp
        _TRACES.inc(backend="pallas", scope=self._scope)
        float_dts = [v.dtype for v in leaf_vals
                     if jnp.issubdtype(v.dtype, jnp.floating)]
        # dtype resolved once per trace from the leaf avals; integer
        # leaves (gather indices) keep their own dtype
        dtype = jnp.result_type(*float_dts) if float_dts else jnp.float32
        env: Dict[str, Any] = {}
        for name, v in zip(self.leaf_names, leaf_vals):
            env[name] = (jnp.asarray(v, dtype)
                         if jnp.issubdtype(v.dtype, jnp.floating) else v)
        # loop-invariant layouts (CSR per-tile entries) once per dispatch,
        # outside any rolled loop
        for call in (*self._pro, *self._tmpl, *self._epi):
            for name, build in getattr(call, "derived", {}).items():
                if name not in env:
                    env[name] = build(env, dtype)
        for call in self._pro:
            env.update(call.apply(env, dtype))
        if self.roll is not None:
            from jax import lax
            slots = self.roll.slots
            base = {n: env[n] for n in self._tmpl_ext}

            def body(_, carry):
                env_l = dict(base)
                for sl, v in zip(slots, carry):
                    if sl.read is not None:
                        env_l[sl.read] = v
                for call in self._tmpl:
                    env_l.update(call.apply(env_l, dtype))
                return tuple(env_l[sl.update] for sl in slots)

            # output-only slots (init=None) seed with zeros: their carry-in
            # is never read, only their final generation leaves the loop
            carry = tuple(
                env[sl.init] if sl.init is not None
                else jnp.zeros(shape, dtype)
                for sl, shape in zip(slots, self._slot_shapes))
            carry = lax.fori_loop(0, self.roll.n_iters, body, carry)
            for sl, v in zip(slots, carry):
                env[sl.final] = v
        for call in self._epi:
            env.update(call.apply(env, dtype))
        # no runtime freeing: inside one traced program, XLA buffer
        # liveness retires dead intermediates
        return tuple(env[o] for o in self.out_names)

    # -- the pure core (serve.BatchedPlan batches through this) ---------
    def pure(self, feeds: Dict[str, Any]) -> Dict[str, Any]:
        """The traced program as a pure ``feeds -> {output: value}``
        callable: no donation, no dispatch counting, no outer jit — safe
        to compose under a caller's ``jax.jit`` / ``jax.vmap``
        (:meth:`Executor.compile_pure`).  ``stats["traces"]`` still counts
        Python body executions (a trace-time-only side effect), so batched
        wrappers can assert they retrace only per (batch size, dtype)."""
        for leaf in self.leaf_names:
            if leaf not in feeds:
                raise KeyError(f"feeds missing leaf {leaf!r}")
        outs = self._traced(*[feeds[n] for n in self.leaf_names])
        return dict(zip(self.out_names, outs))

    # -- the dispatch ---------------------------------------------------
    def __call__(self, feeds: Dict[str, Any]) -> Dict[str, Any]:
        for leaf in self.leaf_names:
            if leaf not in feeds:
                raise KeyError(f"feeds missing leaf {leaf!r}")
        with obs.span("exec.feed_check"):
            check_csr_feeds(self.exec_plan.units, self._program, feeds)
        args = [feeds[leaf] for leaf in self.leaf_names]
        if self._donate:
            with obs.span("exec.feed_copy"):
                # donation must never consume a caller-owned buffer
                args, copied = _own_feeds(args)
            if copied:
                _FEED_COPY_B.inc(copied, backend="pallas",
                                 scope=self._scope)
        _DISPATCHES.inc(backend="pallas", scope=self._scope)
        with obs.span("exec.launch"):
            outs = self._jit(*args)
        return dict(zip(self.out_names, outs))

    # -- what the device trace cannot name ------------------------------
    def leaf_shapes(self, dtype="float32") -> list:
        """``jax.ShapeDtypeStruct`` per leaf at its traced shape: int32
        CSR row pointers and column ids, ``dtype`` for every other leaf."""
        import jax
        import jax.numpy as jnp
        out = []
        for name in self.leaf_names:
            nd = self._program.nodes[name]
            dt = (jnp.int32 if nd.param("role") in ("indptr", "indices")
                  else dtype)
            out.append(jax.ShapeDtypeStruct(tuple(nd.shape), dt))
        return out

    def device_scopes(self, dtype="float32") -> Dict[str, str]:
        """``{HLO instruction name: scope}`` for the instructions of this
        program's compiled executable that carry one of
        :data:`DEVICE_SCOPES`, compiled for :meth:`leaf_shapes` (the
        compilation caches make this a lookup after a run at those
        shapes).  A device trace names each op by its instruction
        (``fusion.6``) and nothing else; this says which are the gather
        and which the layout build."""
        text = self._jit.lower(*self.leaf_shapes(dtype)).compile().as_text()
        return hlo_scopes(text)


def _own_feeds(args: list) -> Tuple[list, int]:
    """``args`` with every ``jax.Array`` replaced by a copy, and the bytes
    copied: the executable may then donate all of them."""
    import jax
    import jax.numpy as jnp
    out, copied = [], 0
    for v in args:
        if isinstance(v, jax.Array):
            v = jnp.array(v, copy=True)
            copied += v.nbytes
        out.append(v)
    return out, copied


def hlo_scopes(text: str, scopes: Sequence[str] = DEVICE_SCOPES
               ) -> Dict[str, str]:
    """``{instruction: scope}`` for each instruction of a compiled HLO
    module's text whose ``op_name`` metadata passes through one of
    ``scopes`` (the innermost, where they nest)."""
    pats = [(sc, re.compile(r"(?:^|[/(])" + re.escape(sc) + r"(?=[/)]|$)"))
            for sc in scopes]
    out: Dict[str, str] = {}
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        name, op_name = m.groups()
        found = [(hit.start(), sc) for sc, pat in pats
                 for hit in pat.finditer(op_name)]
        if found:
            out[name] = max(found)[1]
    return out


# --------------------------------------------------------------------------
# the executors
# --------------------------------------------------------------------------

class PallasExecutor(Executor):
    """Compile the whole plan into one jitted single-program executable."""

    name = "pallas"

    def compile(self, plan) -> "_SingleProgram":
        # fault-injection site (docs/robustness.md): exec.compile@pallas —
        # here as well as in the memoized run() driver, because
        # serve.BatchedPlan compiles through compile/compile_pure directly
        faults.check("exec.compile", backend=self.name)
        sharded = getattr(plan, "sharded", None)
        if sharded is not None and sharded.n_shards > 1:
            from .sharded import ShardedProgram
            return ShardedProgram(plan)
        return _SingleProgram(plan)

    def compile_pure(self, plan):
        faults.check("exec.compile", backend=self.name)
        sharded = getattr(plan, "sharded", None)
        if sharded is not None and sharded.n_shards > 1:
            raise ValueError(
                "mesh-sharded plans have no pure (vmap-composable) core; "
                "serve/batch them unsharded or run() them directly")
        # the single program's traced core, without the dispatch driver
        # (donation, counters, its own jit): composable under vmap
        return _SingleProgram(plan).pure


class PerUnitPallasExecutor(Executor):
    """The PR-3 driver: one dispatch per execution unit, runtime freeing.

    Kept as the measured A/B baseline for the single-program executable
    (TABLE 8) and as a debugging surface — each unit can be inspected in
    isolation.  Uses the *unfused* unit sequence: no cross-pass residency,
    no rolled loops.
    """

    name = "pallas-perunit"

    def compile(self, plan):
        program = plan_program(plan)
        groups = plan_groups(plan)
        kernels = _plan_kernels(plan, groups)
        units = flatten_units(kernels)
        needed, consumers = _unit_needed(program, units)
        calls = [_build_call(program, units[ui], needed[ui])
                 for ui in range(len(units))]
        scope = obs.next_scope("perunit")
        for unit in units:
            _UNITS.inc(backend=self.name, kind=unit.kind, scope=scope)

        outputs = set(program.outputs)
        last_use = {t: max(uis) for t, uis in consumers.items()}
        leaves = [nd.name for nd in program.leaves()]

        def fn(feeds):
            import jax.numpy as jnp
            env: Dict[str, Any] = {}
            for leaf in leaves:
                if leaf not in feeds:
                    raise KeyError(f"feeds missing leaf {leaf!r}")
                env[leaf] = jnp.asarray(feeds[leaf])
            check_csr_feeds(units, program, env)
            for ui, call in enumerate(calls):
                env.update(call(env))
                for t in [t for t, lu in last_use.items() if lu == ui]:
                    if t not in outputs and t in env:
                        del env[t]
            return {o: env[o] for o in program.outputs}
        return fn
