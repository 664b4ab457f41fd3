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
  A *column-blocked* pass (``StreamPass.tile_cols``: whole rows of its
  matrix fit no tile) runs on a ``(row tiles, column tiles)`` grid: each
  step streams a ``(tile, tile_cols)`` matrix block and the right-hand
  side's ``(1, tile_cols)`` block, the row tile's product accumulates in
  VMEM scratch, and the rest of the pass runs at the last column step.
  Its kernel is named ``cello_wide_<first op>``.
* CSR SpMV ops run inside stream passes on one of two layouts, both
  derived once per dispatch from the CSR leaves, outside any rolled loop.
  Where the operand's pattern meta fixes every row's column offsets to a
  small static set ``d_k`` (``laplacian5``, ``banded``), the *diagonal*
  layout: ``(K, n)`` values, row ``k`` holding each row's entry at offset
  ``d_k`` (zero where it has none), built by one Pallas pass
  (:func:`_dia_layout_fn`); the kernel reads ``x``'s tile with as many
  neighbour tiles as the largest offset needs and sums
  ``diag_k * x[i + d_k]`` over static slices of that window, on the VPU —
  no gather.  Otherwise the padded *per-tile* layout: tile ``t`` owns
  exactly its own rows' entries, padded to ``B`` slots (static, from the
  pattern meta), as column ids and values in contiguous per-tile windows
  plus each row's slot range inside its window; each pass gathers
  ``values * x[cols]`` in XLA (Mosaic cannot gather by column index
  in-kernel) and the kernel sums each tile's rows as one MXU product with
  a one-hot row matrix built from those ranges — no scatter.  Feeds must
  match the pattern meta the plan was built for
  (:func:`core.lowering.check_csr_feeds` refuses those that do not).
* Under ``jax.vmap`` (the pure core :class:`serve.BatchedPlan` batches)
  a stream pass whose matrix and diagonal-layout operands are unbatched
  runs as one *lane kernel*, ``cello_lanes_<first op>``: the same grid,
  each matrix tile read once and contracted with the ``(B, n)`` block of
  every lane's right-hand side, vectors in ``(B, tile)`` blocks, scalars
  and reductions in per-lane ``(B, 1)`` VMEM cells.  Other passes (a
  per-tile CSR spmv) take ``jax.vmap`` of the single-RHS kernel, which
  reads the operand once per lane.  The single dispatch never takes it.
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
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Set, Tuple)

from .. import obs
from ..testing import faults
from ..core.lowering import (STREAM_EINSUMS, ExecPlan, GroupKernel,
                             StreamPass, check_csr_feeds, csr_tile_entries,
                             dia_halo_tiles, dia_layout_bytes,
                             dia_layout_tile, flatten_units,
                             kernel_block_bytes, plan_execution,
                             select_group_kernels, spmv_offsets)
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
_SPMV_LAYOUT = obs.registry().counter(
    "exec.spmv_layout", "spmv passes each dispatch runs, by the layout its "
    "operand streams in (layout: dia | csr), per compiled program (scope "
    "label)")
_MATVEC_TILING = obs.registry().counter(
    "exec.matvec_tiling", "dense contraction passes each dispatch runs, by "
    "how their matrix is tiled (tiling: rows | blocked), per compiled "
    "program (scope label)")

_BACKEND_PROBE: Optional[str] = None

#: ``jax.named_scope`` names of the device work that runs outside the
#: stream kernels: the per-tile spmv's ``values * x[cols]`` gather, and
#: the CSR operand's layout build (per-tile windows, or the diagonal
#: layout).  They must not start with ``cello_``, the kernels' prefix.  A
#: device trace names ops by their HLO instruction alone;
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
#: lanes of a vreg: the diagonal layout's pass reads CSR entries in rows
#: of this many
_LANES = 128


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

class _Spmv(NamedTuple):
    """How one spmv of a stream pass reads its operand."""
    layout: str                      # "dia" | "csr"
    derived: str                     # env name of its loop-invariant layout
    x: str
    offsets: Tuple[int, ...] = ()    # dia: the diagonals' column offsets
    halo: int = 0                    # dia: x's neighbour tiles each side


class _StreamCall:
    """One tile-streaming ``pl.pallas_call`` for a :class:`StreamPass`.

    With ``defer_finalize=True`` (sharded execution) the call returns raw
    per-shard reduction partials: no ``sqrt`` on norm accumulators, no
    epilogue — the sharded program combines partials with ``psum`` and
    replays the epilogue (:attr:`finalize_nodes`) inside the
    ``shard_map`` trace.

    With ``lanes=True`` (the pure core a batched plan vmaps) the kernel
    call gets a batching rule: vmapped over B lanes while its matrix and
    diagonal-layout operands are unbatched, the pass runs as one *lane
    kernel*, ``cello_lanes_<first op>``, on the same grid, reading each
    matrix tile once and contracting it with the ``(B, n)`` block of every
    lane's resident vector; vectors move as ``(B, tile)`` blocks, scalars
    and reductions as per-lane ``(B, 1)`` VMEM cells.  Any other
    combination (a per-tile CSR spmv, a batched matrix, a contraction with
    a matrix right-hand side) runs ``jax.vmap`` of the single-RHS kernel,
    one read per lane.  :attr:`batched_read` records which one the
    last batched trace took."""

    def __init__(self, program, sp: StreamPass, needed: Set[str], *,
                 defer_finalize: bool = False, lanes: bool = False):
        self.nodes = [program.nodes[o] for o in sp.ops]
        self.sp = sp
        self.defer = defer_finalize
        self.lanes = lanes
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
        # per spmv, from loop-invariant layouts derived once per dispatch:
        # on the diagonal layout its (K, rows) diagonals and x's tile with
        # its neighbours; on the per-tile layout its entry values and each
        # row's slot range
        self.spmv: Dict[str, _Spmv] = {}
        self.tile_in: List[str] = []
        self.x_shift: Dict[str, int] = {}   # x tile input -> tile offset
        self.derived: Dict[str, Callable] = {}
        for nd in self.nodes:
            for t in nd.inputs:
                if t not in produced:
                    _want(t, in_names)
            cls = self.classes[nd.name]
            if nd.op == "spmv" and nd.name in sp.dia:
                ipn = nd.inputs[0]
                params = program.nodes[ipn].params
                offsets = spmv_offsets(params, sp.rows)
                halo = dia_halo_tiles(offsets, tr)
                lt = dia_layout_tile(sp.rows)
                lay = f"{ipn}@dia"
                self.derived[lay] = _dia_layout_fn(
                    *nd.inputs[:3], lt,
                    csr_tile_entries(params, sp.rows, lt), offsets)
                self.spmv[nd.name] = _Spmv("dia", lay, nd.inputs[3],
                                           offsets, halo)
                self.shapes[f"{nd.name}@dia"] = (len(offsets), sp.rows)
                self.tile_in.append(f"{nd.name}@dia")
                for j in range(-halo, halo + 1):
                    self.x_shift[f"{nd.name}@x{j}"] = j
                    self.tile_in.append(f"{nd.name}@x{j}")
            elif nd.op == "spmv":
                ipn = nd.inputs[0]
                entries = csr_tile_entries(program.nodes[ipn].params,
                                           sp.rows, tr)
                lay = f"{ipn}@t{tr}"
                self.derived[lay] = _csr_tiles_fn(*nd.inputs[:3], tr,
                                                  entries)
                self.spmv[nd.name] = _Spmv("csr", lay, nd.inputs[3])
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
        # the lane kernel: every streamed value a vector, every matrix a
        # whole-row contraction's left operand, no per-tile CSR spmv (its
        # gathered values differ per lane)
        self._lane_kernel_fits = (
            all(s.layout == "dia" for s in self.spmv.values())
            and all(len(self.shapes[nd.name]) == 1 for nd in self.nodes
                    if self.classes[nd.name] == "tiled")
            and all(len(self.shapes[t]) <= 1 or (
                nd.op in ("matmul", "einsum")
                and nd.param("spec") == "ab,b->a" and t == nd.inputs[0])
                    for nd in self.nodes for t in nd.inputs))
        # the values the kernel call reads, once each: its inputs, and
        # each spmv's layout and x; of them, the operands every lane reads
        # alike: matrices and diagonals
        self._lane_names = list(dict.fromkeys(
            [*stream_in, *res_in, *scalar_in,
             *(n for s in self.spmv.values() for n in (s.derived, s.x))]))
        self._lane_shared = ({n for n in self.stream_in + self.res_in
                              if len(self.shapes[n]) == 2}
                             | {s.derived for s in self.spmv.values()})
        self.batched_read: Optional[str] = (
            "shared" if self.red_out or self.stream_out else None)

    @property
    def kernel_name(self) -> str:
        """``cello_wide_<first op>`` for a column-blocked pass (its device
        ops are told apart from whole-row ones by the name),
        ``cello_stream_<first op>`` otherwise."""
        kind = "wide" if self.sp.tile_cols else "stream"
        return f"cello_{kind}_{self.sp.ops[0]}"

    @property
    def lanes_kernel_name(self) -> str:
        """The lane kernel's name, ``cello_lanes_<first op>``."""
        return f"cello_lanes_{self.sp.ops[0]}"

    def _build(self, dtype, lanes: Optional[int] = None):
        """The single-RHS kernel, or with ``lanes=B`` the lane kernel:
        every vector a ``(B, ...)`` block, scalars and reductions per-lane
        ``(B, 1)`` VMEM cells, matrices and diagonals as before."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        tr, tc = self.sp.tile_rows, self.sp.tile_cols
        n_tiles = self.sp.rows // tr
        nodes, classes = self.nodes, self.classes
        ins = self.stream_in + self.tile_in + self.res_in + self.scalar_in
        n_in = len(ins)
        outs = self.red_out + self.stream_out
        stream_out_set = set(self.stream_out)
        hi = lax.Precision.HIGHEST
        rb = lanes or 1                 # rows of every vector block

        def kshape(shape):
            """The kernel-side shape of an array at this build's lanes."""
            s = _row_shape(shape)
            return (rb,) + s[1:] if len(shape) < 2 else s
        # a column-blocked pass walks (row tile, column tile); its
        # contractions accumulate each row tile's product in VMEM scratch
        # and everything else runs once the last column tile is in
        matvecs = [nd for nd in nodes if classes[nd.name] == "tiled"
                   and nd.op in ("matmul", "einsum")] if tc else []
        n_cols = (self.shapes[self.res_in[0]][0] // tc) if tc else 1

        def contract(nd, val, rref):
            spec = nd.param("spec")
            rhs = STREAM_EINSUMS[spec]
            lhs = val(nd.inputs[1 - rhs])
            right = rref[nd.inputs[rhs]][...]
            if spec == "ab,b->a":       # row x (1, m) . A_tile^T
                return lax.dot_general(
                    right, lhs, (((1,), (1,)), ((), ())),
                    precision=hi, preferred_element_type=dtype)
            return jnp.dot(lhs, right, precision=hi,
                           preferred_element_type=dtype)

        def kernel(*refs):
            i = pl.program_id(0)
            # by position: a contraction's resident RHS may also stream
            # elsewhere in the pass (``p`` in ``A @ p`` and ``x + a * p``)
            it = iter(refs[:n_in])
            sref, tref, rref, cref = (
                {n: next(it) for n in names}
                for names in (self.stream_in, self.tile_in, self.res_in,
                              self.scalar_in))
            oref = dict(zip(outs, refs[n_in:n_in + len(outs)]))
            acc = dict(zip((nd.name for nd in matvecs),
                           refs[n_in + len(outs):]))
            tiles: Dict[str, Any] = {}

            def val(name):                 # streamed tile or scalar
                if name in cref:            # SMEM, or per lane (B, 1)
                    return (cref[name][0, 0] if lanes is None
                            else cref[name][...])
                if name not in tiles:
                    tiles[name] = sref[name][...]
                return tiles[name]

            def row_tile():
                for nd in nodes:
                    cls = classes[nd.name]
                    if cls == "tiled":
                        if nd.name in acc:      # the accumulated product
                            v = acc[nd.name][...]
                        elif nd.op == "spmv" and self.spmv[nd.name].layout \
                                == "dia":       # sum of shifted x, VPU
                            v = _dia_rows(nd.name, self.spmv[nd.name], tref,
                                          tr)
                        elif nd.op == "spmv":   # one-hot row sum, MXU
                            vals = tref[f"{nd.name}@vals"][...]   # (1, B)
                            first = tref[f"{nd.name}@first"][...]
                            stop = tref[f"{nd.name}@stop"][...]
                            slot = lax.broadcasted_iota(
                                jnp.int32, (vals.shape[-1], tr), 0)
                            onehot = (slot >= first) & (slot < stop)
                            v = jnp.dot(vals, onehot.astype(dtype),
                                        precision=hi,
                                        preferred_element_type=dtype)
                        elif nd.op in ("matmul", "einsum"):
                            v = contract(nd, val, rref)
                        else:
                            v = eval_node(nd, [val(t) for t in nd.inputs])
                        tiles[nd.name] = v
                        if nd.name in stream_out_set:
                            oref[nd.name][...] = v.astype(dtype)
                    elif cls == "reduce":
                        a = val(nd.inputs[0])
                        b = a if nd.op == "norm" else val(nd.inputs[1])
                        if lanes is None:
                            _accumulate(oref[nd.name], jnp.sum(a * b), i)
                        else:           # each lane's tile partial
                            _accumulate(oref[nd.name], jnp.sum(
                                a * b, axis=1, keepdims=True), i, ...)

            if not tc:
                row_tile()
                return
            j = pl.program_id(1)

            @pl.when(j == 0)
            def _():
                for ref in acc.values():
                    ref[...] = jnp.zeros_like(ref)

            for nd in matvecs:
                acc[nd.name][...] += contract(nd, val, rref)
            tiles.clear()       # the row tile's values load at its end

            @pl.when(j == n_cols - 1)
            def _():
                row_tile()

        def rows_map(f):
            """An index map of a block that moves with the row tile."""
            return (lambda i, j: f(i)) if tc else f

        def stream_spec(shape):
            if len(shape) == 1:
                return pl.BlockSpec((rb, tr), rows_map(lambda i: (0, i)))
            if tc:                              # a (tr, tc) matrix block
                return pl.BlockSpec((tr, tc), lambda i, j: (i, j))
            return pl.BlockSpec((tr,) + shape[1:],
                                lambda i: (i,) + (0,) * (len(shape) - 1))

        def full_spec(shape):
            if tc:                  # the right-hand side's column tile
                return pl.BlockSpec((rb, tc), lambda i, j: (0, j))
            shape = kshape(shape)
            return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

        def tile_spec(name):
            if name in self.x_shift:    # x's tile or a neighbour, clamped
                j = self.x_shift[name]
                return pl.BlockSpec((rb, tr), lambda i: (0, jnp.minimum(
                    jnp.maximum(i + j, 0), n_tiles - 1)))
            if name.endswith("@dia"):           # (K, tr) diagonals
                return pl.BlockSpec((self.shapes[name][0], tr),
                                    lambda i: (0, i))
            if not name.endswith("@vals"):      # rows' slot bounds
                return pl.BlockSpec((1, tr), lambda i: (0, i))
            return pl.BlockSpec((None, 1, self.shapes[name][-1]),
                                lambda i: (i, 0, 0))

        if lanes is None:
            cell = pl.BlockSpec((1, 1), rows_map(lambda i: (0, 0)),
                                memory_space=pltpu.SMEM)
        else:
            cell = pl.BlockSpec((rb, 1), rows_map(lambda i: (0, 0)))
        in_specs = ([stream_spec(self.shapes[n]) for n in self.stream_in]
                    + [tile_spec(n) for n in self.tile_in]
                    + [full_spec(self.shapes[n]) for n in self.res_in]
                    + [cell] * len(self.scalar_in))
        out_specs = ([cell] * len(self.red_out)
                     + [stream_spec(self.shapes[n])
                        for n in self.stream_out])
        out_shape = ([jax.ShapeDtypeStruct(kshape(()), dtype)
                      for _ in self.red_out]
                     + [jax.ShapeDtypeStruct(kshape(self.shapes[n]), dtype)
                        for n in self.stream_out])
        grid = (n_tiles, n_cols) if tc else (n_tiles,)
        name, vmem = self.kernel_name, self.sp.vmem_bytes
        if lanes is not None:
            name, vmem = self.lanes_kernel_name, vmem + self._lanes_vmem(rb)
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((rb, tr), dtype) for _ in matvecs],
            **_pallas_call_kwargs(name, dtype, vmem, len(grid)))

    def _lanes_vmem(self, lanes: int) -> int:
        """VMEM the lane kernel plans beyond the single-RHS one: vector
        blocks of ``lanes`` rows (the same bytes up to 8, one sublane
        tile) and the per-lane scalar and reduction cells, all double
        buffered, plus the wider accumulators."""
        tr, tc = self.sp.tile_rows, self.sp.tile_cols

        def more(cols: int) -> int:
            return (kernel_block_bytes((lanes, cols))
                    - kernel_block_bytes((cols,)))

        cells = len(self.scalar_in) + len(self.red_out)
        tiles = (sum(len(self.shapes[n]) == 1 for n in self.stream_in)
                 + len(self.x_shift) + len(self.stream_out))
        res = sum(more(tc or self.shapes[n][0]) for n in self.res_in)
        accs = sum(nd.op in ("matmul", "einsum") for nd in self.nodes
                   if tc and self.classes[nd.name] == "tiled")
        return (2 * (cells * kernel_block_bytes((lanes, 1))
                     + tiles * more(tr) + res) + accs * more(tr))

    # -- drivers --------------------------------------------------------
    def apply(self, env: Dict[str, Any], dtype) -> Dict[str, Any]:
        """Run (or trace) this pass over ``env`` at a resolved ``dtype``."""
        import jax.numpy as jnp
        vals: Dict[str, Any] = {}

        def get(n):
            return vals[n] if n in vals else env[n]

        for nd in self.eager:
            vals[nd.name] = eval_node(nd, [get(t) for t in nd.inputs])
        if self.red_out or self.stream_out:
            if self.lanes:
                outs = self._with_lanes(dtype)(
                    *(get(n) for n in self._lane_names))
            else:
                outs = self._run(env, get, dtype)
            vals.update(zip(self.red_out + self.stream_out, outs))
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

    def _kernel(self, dtype, lanes: Optional[int] = None):
        call = self._built.get((dtype, lanes))
        if call is None:
            call = self._built[(dtype, lanes)] = self._build(dtype, lanes)
        return call

    def _run(self, env: Dict[str, Any], get, dtype) -> tuple:
        """The single-RHS kernel over ``env``: its outputs at their own
        shapes, reductions first."""
        import jax
        import jax.numpy as jnp
        tile_args = []
        for s in self.spmv.values():
            lay = (env[s.derived] if s.derived in env
                   else self.derived[s.derived](env, dtype))
            if s.layout == "dia":
                x = jnp.reshape(jnp.asarray(env[s.x], dtype), (1, -1))
                tile_args += [lay] + [x] * (2 * s.halo + 1)
                continue
            cols, data, first, stop = lay
            with jax.named_scope(GATHER_SCOPE):
                vals_t = data * jnp.asarray(env[s.x], dtype)[cols]
            tile_args += [vals_t, first, stop]
        row = [jnp.reshape(jnp.asarray(env[n], dtype),
                           _row_shape(self.shapes[n]))
               for n in self.stream_in + self.res_in]
        args = (row[:len(self.stream_in)] + tile_args
                + row[len(self.stream_in):]
                + [jnp.reshape(jnp.asarray(get(n), dtype), (1, 1))
                   for n in self.scalar_in])
        outs = self._kernel(dtype)(*args)
        return tuple(jnp.reshape(v, self.shapes[n])
                     for n, v in zip(self.red_out + self.stream_out, outs))

    def _with_lanes(self, dtype) -> Callable:
        """:meth:`_run` over :attr:`_lane_names`' values, with a batching
        rule: the lane kernel where it fits and every shared operand is
        unbatched, else ``jax.vmap`` of the single-RHS kernel."""
        fn = self._built.get((dtype, "vmap"))
        if fn is not None:
            return fn
        import jax
        from jax.custom_batching import custom_vmap
        names = self._lane_names
        n_out = len(self.red_out) + len(self.stream_out)

        def single(*vals):
            env = dict(zip(names, vals))
            return self._run(env, env.__getitem__, dtype)

        def rule(axis_size, in_batched, *vals):
            batched = dict(zip(names, in_batched))
            if self._lane_kernel_fits and not any(
                    b for n in self._lane_shared
                    for b in jax.tree_util.tree_leaves(batched[n])):
                self.batched_read = "shared"
                outs = self._run_lanes(axis_size, dict(zip(names, vals)),
                                       dtype)
            else:
                self.batched_read = "per_lane"
                axes = [jax.tree_util.tree_map(lambda b: 0 if b else None,
                                               b) for b in in_batched]
                outs = jax.vmap(single, in_axes=axes)(*vals)
            return tuple(outs), (True,) * n_out

        fn = self._built[(dtype, "vmap")] = custom_vmap(single)
        fn.def_vmap(rule)
        return fn

    def _run_lanes(self, lanes: int, env: Dict[str, Any], dtype) -> tuple:
        """The lane kernel over ``env``, whose per-lane values carry a
        leading ``lanes`` axis (or none, and are broadcast to every lane):
        its outputs with the lanes axis, reductions first."""
        import jax.numpy as jnp

        def lane(n):
            return jnp.broadcast_to(jnp.asarray(env[n], dtype),
                                    (lanes,) + self.shapes[n])

        tile_args = []
        for s in self.spmv.values():            # diagonal layouts only
            tile_args += [env[s.derived]] + [lane(s.x)] * (2 * s.halo + 1)
        row = [jnp.asarray(env[n], dtype) if n in self._lane_shared
               else lane(n) for n in self.stream_in + self.res_in]
        args = (row[:len(self.stream_in)] + tile_args
                + row[len(self.stream_in):]
                + [jnp.reshape(lane(n), (lanes, 1))
                   for n in self.scalar_in])
        outs = self._kernel(dtype, lanes)(*args)
        return tuple(jnp.reshape(v, (lanes,) + self.shapes[n])
                     for n, v in zip(self.red_out + self.stream_out, outs))

    @property
    def matvec_tiling(self) -> Optional[str]:
        """``blocked`` (column-blocked) or ``rows`` (whole-row tiles) for a
        pass holding a dense contraction, ``None`` for any other pass."""
        if not any(nd.op in ("matmul", "einsum")
                   and self.classes[nd.name] == "tiled" for nd in self.nodes):
            return None
        return "blocked" if self.sp.tile_cols else "rows"

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


def _dia_rows(name: str, s: _Spmv, tref, tr: int):
    """One tile of a diagonal-layout spmv inside its stream kernel:
    ``Σ_k diag_k * x[i + d_k]``, each term a static slice of the window of
    ``x``'s tile and its neighbours, summed in the program's dtype."""
    import jax.numpy as jnp
    dia = tref[f"{name}@dia"][...]                          # (K, tr)
    win = jnp.concatenate([tref[f"{name}@x{j}"][...]
                           for j in range(-s.halo, s.halo + 1)], axis=1)
    mid = s.halo * tr                       # window column of row 0
    v = None
    for k, d in enumerate(s.offsets):
        term = dia[k:k + 1, :] * win[:, mid + d:mid + d + tr]
        v = term if v is None else v + term
    return v


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


def _dia_layout_fn(indptr: str, indices: str, data: str, tile_rows: int,
                   entries: int, offsets: Tuple[int, ...]) -> Callable:
    """A function building one CSR operand's diagonal layout: ``(K,
    rows)`` values, row ``k`` holding each row's entries at column offset
    ``offsets[k]`` (zero where it has none).  One Pallas pass over row
    tiles, with no per-entry gather or scatter: each tile's entries (at
    most ``entries``, which :func:`core.lowering.check_csr_feeds` holds
    feeds to) arrive as one window of whole 128-entry rows of the column
    ids and values; the one-hot row matrix of the entries' slot ranges
    gives each entry its row, its column minus that row picks its
    diagonal, and one MXU product with the same matrix sums the entries
    into place.  A row holding an entry on none of ``offsets`` reads NaN
    on every diagonal, so the spmv's answer in that row is NaN, never an
    answer missing the entry."""
    operand = re.sub(r"\W", "_", data.rsplit(".", 1)[0])
    window_rows = -(-entries // _LANES) + 1      # covers any start's offset
    built: Dict[Any, Callable] = {}

    def build(env, dtype):
        import jax
        import jax.numpy as jnp
        with jax.named_scope(LAYOUT_SCOPE):
            ip = jnp.asarray(env[indptr])
            rows = ip.shape[0] - 1
            # each tile's window starts at the 128-entry row holding its
            # first entry; slot ranges count from there
            row0 = ip[:-1].reshape(-1, tile_rows)[:, 0] // _LANES
            base = jnp.broadcast_to((row0 * _LANES)[:, None], (
                rows // tile_rows, tile_rows)).reshape(rows)
            first = (ip[:-1] - base)[None, :]
            stop = (ip[1:] - base)[None, :]

            def by_rows(a):
                # (128-entry rows, 1, 128), zero-padded so that every
                # tile's window lies inside
                m = -(-a.shape[0] // _LANES) + window_rows
                return jnp.pad(a, (0, m * _LANES - a.shape[0])).reshape(
                    m, 1, _LANES)

            call = built.get(dtype)
            if call is None:
                call = built[dtype] = _dia_layout_call(
                    f"cello_dia_{operand}", rows, tile_rows, window_rows,
                    entries, offsets, dtype)
            return call(row0, by_rows(jnp.asarray(env[indices])),
                        by_rows(jnp.asarray(env[data], dtype)), first, stop)
    return build


def _bf16_parts(v):
    """Float32 ``v`` as three bfloat16 arrays whose float32 sum is ``v``
    exactly: a product with a 0/1 matrix is then exact in one bfloat16
    MXU pass per part."""
    import jax.numpy as jnp
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return [hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)]


def _dia_layout_call(name: str, rows: int, tr: int, window_rows: int,
                     entries: int, offsets: Tuple[int, ...],
                     dtype) -> Callable:
    """The Pallas pass of :func:`_dia_layout_fn`: each tile's first window
    row (scalar-prefetched), the ``(rows of 128, 1, 128)`` column ids and
    values, and the slot ranges in; ``(K, rows)`` diagonals out."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bf16 = jnp.bfloat16
    n_diag = len(offsets)
    slots = window_rows * _LANES
    wide = jnp.dtype(dtype).itemsize > 4      # interpret mode only

    def kernel(row0_ref, cols_ref, vals_ref, first_ref, stop_ref, out_ref):
        i = pl.program_id(0)
        slot = lax.broadcasted_iota(jnp.int32, (slots, tr), 0)
        onehot = ((slot >= first_ref[...])
                  & (slot < stop_ref[...])).astype(bf16)  # (slots, tr)
        # each entry's row in the tile, and whether it has one: the rows
        # [j // 32, j % 32, 1] (exact in bfloat16) against the one-hot
        j = lax.broadcasted_iota(jnp.int32, (8, tr), 1)
        r = lax.broadcasted_iota(jnp.int32, (8, tr), 0)
        lhs = jnp.where(r == 0, j // 32,
                        jnp.where(r == 1, j % 32, (r == 2).astype(
                            jnp.int32))).astype(bf16)
        pos = lax.dot_general(lhs, onehot, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        owned = pos[2:3] > 0.5                              # (1, slots)

        def lanes(ref):                       # (window_rows, 1, 128) ->
            return jnp.concatenate(           # (1, slots)
                [ref[k] for k in range(window_rows)], axis=1)

        off = lanes(cols_ref) - (i * tr + (pos[0:1] * 32 + pos[1:2])
                                 .astype(jnp.int32))
        vals = lanes(vals_ref)
        parts = [vals] if wide else _bf16_parts(vals.astype(jnp.float32))
        picked, hit = [], jnp.zeros(owned.shape, bool)
        for d in offsets:
            on = owned & (off == d)
            hit = hit | on
            picked += [jnp.where(on, p, jnp.zeros_like(p)) for p in parts]
        picked.append((owned & ~hit).astype(parts[0].dtype))
        picked += [jnp.zeros_like(parts[0])] * (-len(picked) % 16)
        acc = jnp.dot(jnp.concatenate(picked, axis=0),
                      onehot.astype(dtype) if wide else onehot,
                      precision=lax.Precision.HIGHEST if wide else None,
                      preferred_element_type=dtype if wide
                      else jnp.float32)                      # (R, tr)
        n = len(parts)
        diag = jnp.concatenate(
            [sum(acc[k * n + q:k * n + q + 1] for q in range(n))
             for k in range(n_diag)], axis=0)
        stray = acc[n_diag * n:n_diag * n + 1] > 0.5
        out_ref[...] = jnp.where(stray, jnp.nan, diag).astype(dtype)

    window = pl.BlockSpec(
        (pl.Element(window_rows), pl.Element(1), pl.Element(_LANES)),
        lambda i, row0: (row0[i], 0, 0))
    row = pl.BlockSpec((1, tr), lambda i, row0: (0, i))
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((n_diag, rows), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tr,),
            in_specs=[window, window, row, row],
            out_specs=pl.BlockSpec((n_diag, tr), lambda i, row0: (0, i))),
        **_pallas_call_kwargs(name, dtype,
                              dia_layout_bytes(tr, entries, n_diag), 1))


def _accumulate(ref, part, i, at=(0, 0)):
    """Sum each row tile's ``part`` into ``ref[at]`` in row-tile order: an
    SMEM scalar, or (``at=...``) a lane kernel's ``(B, 1)`` VMEM cell."""
    from jax.experimental import pallas as pl

    @pl.when(i == 0)
    def _():
        ref[at] = part

    @pl.when(i > 0)
    def _():
        ref[at] = ref[at] + part


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


def _build_call(program, unit, needed: Set[str], lanes: bool = False):
    if unit.kind == "stream":
        return _StreamCall(program, unit.sp, needed, lanes=lanes)
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

    ``lanes=True`` builds the pure core a batched plan vmaps: its stream
    passes carry the lane kernel's batching rule (:class:`_StreamCall`).
    """

    def __init__(self, plan, *, lanes: bool = False):
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
        self._pro, self._tmpl, self._epi = (
            [_build_call(program, units[i], needed[i], lanes) for i in part]
            for part in (pro, tmpl, epi))
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
        # spmv and dense contraction passes per dispatch, by layout and
        # tiling: a rolled body runs n_iters times
        self.spmv_layouts = pass_counts(
            self, lambda c: [s.layout for s in getattr(c, "spmv",
                                                       {}).values()])
        self.matvec_tilings = pass_counts(self, _tiling_of)

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

    def batched_passes(self) -> Dict[str, int]:
        """``{shared | per_lane: stream passes}`` one batched dispatch of
        this pure core runs, by whether each pass read its streamed
        operand once for all lanes (the lane kernel, or a pass no lane
        input reaches) or once per lane, as the last vmapped trace
        decided."""
        return pass_counts(self, lambda c: [c.batched_read] if getattr(
            c, "batched_read", None) else [])

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
        for layout, n in self.spmv_layouts.items():
            _SPMV_LAYOUT.inc(n, backend="pallas", layout=layout,
                             scope=self._scope)
        count_matvec_tilings(self)
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
        and which the layout build (a plan whose spmvs all run on the
        diagonal layout has no gather)."""
        text = self._jit.lower(*self.leaf_shapes(dtype)).compile().as_text()
        return hlo_scopes(text)


class _PureCore:
    """:meth:`PallasExecutor.compile_pure`'s callable: a lane-batching
    program's :meth:`~_SingleProgram.pure`, with its
    :meth:`~_SingleProgram.batched_passes` beside it."""

    def __init__(self, program: _SingleProgram):
        self.program = program
        self.batched_passes = program.batched_passes

    def __call__(self, feeds: Dict[str, Any]) -> Dict[str, Any]:
        return self.program.pure(feeds)


def _tiling_of(call) -> List[str]:
    tiling = getattr(call, "matvec_tiling", None)
    return [] if tiling is None else [tiling]


def pass_counts(prog, labels: Callable[[Any], List[str]]) -> Dict[str, int]:
    """``{label: passes}`` one dispatch of ``prog`` (a single-device or
    sharded program) runs, from the labels ``labels(call)`` of each of its
    calls: prologue and epilogue once, a rolled body once per
    iteration."""
    out: Dict[str, int] = {}
    n_iters = prog.roll.n_iters if prog.roll is not None else 0
    for calls, times in ((prog._pro, 1), (prog._epi, 1),
                         (prog._tmpl, n_iters)):
        for call in calls:
            for label in labels(call):
                out[label] = out.get(label, 0) + times
    return out


def count_matvec_tilings(prog) -> None:
    """Add one dispatch's dense contraction passes to
    ``exec.matvec_tiling``."""
    for tiling, n in prog.matvec_tilings.items():
        _MATVEC_TILING.inc(n, backend="pallas", tiling=tiling,
                           scope=prog._scope)


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
        # (donation, counters, its own jit): composable under vmap, where
        # each stream pass reads a shared operand once for all lanes
        return _PureCore(_SingleProgram(plan, lanes=True))


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
