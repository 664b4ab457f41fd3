"""Per-architecture layer DAG builders for the CELLO co-designer.

These graphs are the *analysis-level* view of one transformer block (plus
optional embedding/logits stages): enough fidelity in shapes/FLOPs/bytes for
the schedule × buffer co-design and the speedup/energy tables, at tensor
granularity.  The *execution-level* view is `repro.models` + `repro.kernels`;
`core.policy` connects the two (fusion groups found here select kernels and
remat save-sets there).

Graphs are assembled through :meth:`OpGraph.build`'s value-flow builder:
every op returns the name of the tensor it produced, and downstream ops take
those returned values, so the DAG wiring is carried by data flow rather than
by re-derived string keys.

Conventions:
  * batch and sequence are flattened where attention doesn't need them apart,
  * GQA is modelled with K/V at their true (smaller) kv-head sizes while the
    score/PV contractions carry full-head FLOPs (broadcast is free),
  * data-dependent ops (MoE top-k routing/dispatch) are marked ``irregular``
    — the co-designer must leave their reuse to the implicit region,
  * recurrences (RG-LRU, WKV6) are ``scan`` ops — unfusable with neighbours
    except via their dedicated kernels; their *state* is a pin candidate.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Tuple

from ..configs.base import ArchConfig
from .graph import GraphBuilder, OpGraph, TensorKind

BF16 = 2
F32 = 4


def attention_block(b: GraphBuilder, cfg: ArchConfig, prefix: str, x: str,
                    batch: int, q_len: int, kv_len: int,
                    cross_kv: Optional[str] = None,
                    out_kind: TensorKind = TensorKind.INTERMEDIATE) -> str:
    """Standard (GQA / sliding-window / cross) attention sub-DAG. Returns the
    name of the block output tensor (pre-residual)."""
    d, h, kvh, e = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    bb, s = batch, q_len
    z = kv_len if cfg.window is None else min(kv_len, cfg.window)

    wq = b.weight(f"{prefix}.wq", (d, h * e))
    wo = b.weight(f"{prefix}.wo", (h * e, d))
    q = b.contract(f"{prefix}.q", [x, wq], f"{prefix}.q_out",
                   (bb, s, h, e), 2 * bb * s * d * h * e)

    if cross_kv is None:
        wk = b.weight(f"{prefix}.wk", (d, kvh * e))
        wv = b.weight(f"{prefix}.wv", (d, kvh * e))
        k_t = b.contract(f"{prefix}.k", [x, wk], f"{prefix}.k_out",
                         (bb, z, kvh, e), 2 * bb * z * d * kvh * e)
        v_t = b.contract(f"{prefix}.v", [x, wv], f"{prefix}.v_out",
                         (bb, z, kvh, e), 2 * bb * z * d * kvh * e)
    else:
        # cross-attention: K/V come from the (pinned-candidate) image tensor
        k_t = v_t = cross_kv

    # scores + softmax + PV: FLOPs carry full h heads (GQA broadcast free)
    scores = b.contract(f"{prefix}.scores", [q, k_t], f"{prefix}.scores_out",
                        (bb, h, s, z), 2 * bb * h * s * z * e)
    probs = b.elementwise(f"{prefix}.softmax", [scores], f"{prefix}.probs",
                          flops_per_elem=5)
    pv = b.contract(f"{prefix}.pv", [probs, v_t], f"{prefix}.pv_out",
                    (bb, s, h, e), 2 * bb * h * s * z * e)
    return b.contract(f"{prefix}.o", [pv, wo], f"{prefix}.attn_out",
                      (bb, s, d), 2 * bb * s * h * e * d, out_kind=out_kind)


def mlp_block(b: GraphBuilder, cfg: ArchConfig, prefix: str, x: str,
              tokens: int, out_kind: TensorKind = TensorKind.INTERMEDIATE) -> str:
    d, f = cfg.d_model, cfg.d_ff
    gated = cfg.activation in ("swiglu", "geglu")
    if cfg.is_moe:
        return moe_block(b, cfg, prefix, x, tokens, out_kind)
    w_up = b.weight(f"{prefix}.w_up", (d, (2 if gated else 1) * f))
    w_down = b.weight(f"{prefix}.w_down", (f, d))
    h = b.contract(f"{prefix}.up", [x, w_up], f"{prefix}.h",
                   (tokens, (2 if gated else 1) * f),
                   2 * tokens * d * (2 if gated else 1) * f)
    a = b.elementwise(f"{prefix}.act", [h], f"{prefix}.a",
                      flops_per_elem=4, out_shape=(tokens, f))
    return b.contract(f"{prefix}.down", [a, w_down], f"{prefix}.mlp_out",
                      (tokens, d), 2 * tokens * f * d, out_kind=out_kind)


def moe_block(b: GraphBuilder, cfg: ArchConfig, prefix: str, x: str,
              tokens: int, out_kind: TensorKind = TensorKind.INTERMEDIATE) -> str:
    """Top-k MoE FFN.  Routing/dispatch are data-dependent ⇒ irregular:
    their reuse must live in the implicit region (the CELLO showcase)."""
    d, f, E, k = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.top_k
    gated = cfg.activation in ("swiglu", "geglu")
    w_router = b.weight(f"{prefix}.w_router", (d, E))
    w_up_e = b.weight(f"{prefix}.w_up_e", (E, d, (2 if gated else 1) * f))
    w_down_e = b.weight(f"{prefix}.w_down_e", (E, f, d))
    logits = b.contract(f"{prefix}.router", [x, w_router],
                        f"{prefix}.logits", (tokens, E), 2 * tokens * d * E,
                        dtype_bytes=F32)
    gates = b.elementwise(f"{prefix}.topk", [logits], f"{prefix}.gates",
                          flops_per_elem=2, out_shape=(tokens, k),
                          dtype_bytes=F32, irregular=True)
    # dispatch: gather tokens to experts (data-dependent addressing)
    xe = b.elementwise(f"{prefix}.dispatch", [x, gates], f"{prefix}.xe",
                       flops_per_elem=0, out_shape=(tokens * k, d),
                       irregular=True, spec="gather")
    h = b.contract(f"{prefix}.up", [xe, w_up_e], f"{prefix}.h",
                   (tokens * k, (2 if gated else 1) * f),
                   2 * tokens * k * d * (2 if gated else 1) * f)
    a = b.elementwise(f"{prefix}.act", [h], f"{prefix}.a",
                      flops_per_elem=4, out_shape=(tokens * k, f))
    ye = b.contract(f"{prefix}.down", [a, w_down_e], f"{prefix}.ye",
                    (tokens * k, d), 2 * tokens * k * f * d)
    # combine: weighted scatter-add back to token order (data-dependent)
    return b.elementwise(f"{prefix}.combine", [ye, gates],
                         f"{prefix}.mlp_out", flops_per_elem=2 * k,
                         out_shape=(tokens, d), irregular=True, spec="gather",
                         out_kind=out_kind)


def rglru_block(b: GraphBuilder, cfg: ArchConfig, prefix: str, x: str,
                batch: int, seq: int) -> str:
    """RG-LRU recurrent block (recurrentgemma): gated linear recurrence."""
    d = cfg.d_model
    bb, s = batch, seq
    wx, wgate, wa, wout = b.weights(prefix, ("wx", "wgate", "wa", "wout"),
                                    (d, d))
    xb = b.contract(f"{prefix}.proj", [x, wx], f"{prefix}.xb",
                    (bb, s, d), 2 * bb * s * d * d)
    g = b.contract(f"{prefix}.gates", [x, wgate, wa], f"{prefix}.g",
                   (bb, s, 2 * d), 2 * bb * s * d * 2 * d)
    # the recurrence itself: sequential along s => 'scan' op
    h = b.scan(f"{prefix}.scan", [xb, g], f"{prefix}.h",
               (bb, s, d), flops_per_elem=8)
    return b.contract(f"{prefix}.out", [h, wout], f"{prefix}.rglru_out",
                      (bb, s, d), 2 * bb * s * d * d)


def rwkv_block(b: GraphBuilder, cfg: ArchConfig, prefix: str, x: str,
               batch: int, seq: int) -> str:
    """RWKV6 time-mix: r/k/v/g projections + WKV6 recurrence + output."""
    d = cfg.d_model
    bb, s = batch, seq
    H, e = cfg.n_heads, cfg.resolved_head_dim
    wr, wk, wv, wg, wo, ww = b.weights(
        prefix, ("wr", "wk", "wv", "wg", "wo", "ww"), (d, d))
    rkvg = b.contract(f"{prefix}.rkvg", [x, wr, wk, wv, wg, ww],
                      f"{prefix}.rkvg_out", (bb, s, 5 * d),
                      2 * bb * s * d * 5 * d)
    # WKV6 recurrence: per head, state (e x e) updated per step
    wkv = b.scan(f"{prefix}.wkv", [rkvg], f"{prefix}.wkv_out",
                 (bb, s, d), flops=2 * bb * s * H * e * e * 4)
    return b.contract(f"{prefix}.out", [wkv, wo], f"{prefix}.rwkv_out",
                      (bb, s, d), 2 * bb * s * d * d)


def layer_graph(cfg: ArchConfig, batch: int, seq: int, *,
                layer_kind: Optional[str] = None,
                include_residuals: bool = True) -> OpGraph:
    """One transformer block as an OpGraph (the CELLO unit of analysis).

    The residual stream exhibits the paper's "complex reuse": ``x`` feeds the
    norm AND the residual add (two consumers, different distances); the block
    output feeds the next norm and the next residual add likewise.
    """
    kind = layer_kind or cfg.layer_kinds()[0]
    d = cfg.d_model
    tokens = batch * seq
    with OpGraph.build(f"{cfg.name}:{kind}:b{batch}s{seq}") as b:
        x = b.input("x", (batch, seq, d))
        ln1_w = b.weight("ln1.w", (d,))
        ln2_w = b.weight("ln2.w", (d,))
        x_n1 = b.elementwise("ln1", [x, ln1_w], "x_n1", flops_per_elem=6)

        if kind == "attn":
            y = attention_block(b, cfg, "attn", x_n1, batch, seq, seq)
        elif kind == "xattn":
            img_kv = b.input("img_kv", (batch, cfg.vision_seq,
                                        2 * cfg.n_kv_heads *
                                        cfg.resolved_head_dim))
            y = attention_block(b, cfg, "xattn", x_n1, batch, seq,
                                cfg.vision_seq, cross_kv=img_kv)
        elif kind == "rglru":
            y = rglru_block(b, cfg, "rglru", x_n1, batch, seq)
        elif kind == "rwkv":
            y = rwkv_block(b, cfg, "rwkv", x_n1, batch, seq)
        else:
            raise ValueError(kind)

        if include_residuals:
            src = b.elementwise("res1", [x, y], "x_mid", flops_per_elem=1)
        else:
            src = y
        x_n2 = b.elementwise("ln2", [src, ln2_w], "x_n2", flops_per_elem=6)
        m = mlp_block(b, cfg, "mlp", x_n2, tokens)
        if include_residuals:
            b.elementwise("res2", [src, m], "x_out", flops_per_elem=1,
                          out_kind=TensorKind.OUTPUT,
                          out_shape=(batch, seq, d))
    return b.graph


def decode_graph(cfg: ArchConfig, batch: int, kv_len: int) -> OpGraph:
    """Single-token decode step for one layer: KV-cache reuse pattern.

    The cache is an INPUT consumed by scores/PV and extended (OUTPUT) — the
    canonical multi-distance reuse tensor for serving.
    """
    kind = next((k for k in cfg.layer_kinds() if k in ("attn", "rwkv")),
                cfg.layer_kinds()[0])
    d, h, kvh, e = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.resolved_head_dim)
    bb = batch
    z = kv_len if cfg.window is None else min(kv_len, cfg.window)
    with OpGraph.build(f"{cfg.name}:decode:b{batch}kv{kv_len}") as b:
        x = b.input("x", (bb, 1, d))
        ln1_w = b.weight("ln1.w", (d,))
        x_n1 = b.elementwise("ln1", [x, ln1_w], "x_n1", flops_per_elem=6)
        if kind == "rwkv":
            state = b.input("state", (bb, cfg.n_heads, e, e), dtype_bytes=F32)
            wr, wk, wv, wo = b.weights("t", ("wr", "wk", "wv", "wo"), (d, d))
            rkv = b.contract("t.rkv", [x_n1, wr, wk, wv], "t.rkv_out",
                             (bb, 1, 3 * d), 2 * bb * d * 3 * d)
            ty = b.scan("t.wkv", [rkv, state], "t.y", (bb, 1, d),
                        flops=2 * bb * cfg.n_heads * e * e * 4)
            b.elementwise("t.state_new", [rkv, state], "state_out",
                          flops_per_elem=2, out_shape=(bb, cfg.n_heads, e, e),
                          dtype_bytes=F32, out_kind=TensorKind.OUTPUT)
            y = b.contract("t.o", [ty, wo], "attn_out", (bb, 1, d),
                           2 * bb * d * d)
        else:
            k_cache = b.input("k_cache", (bb, z, kvh, e))
            v_cache = b.input("v_cache", (bb, z, kvh, e))
            wq = b.weight("attn.wq", (d, h * e))
            wk = b.weight("attn.wk", (d, kvh * e))
            wv = b.weight("attn.wv", (d, kvh * e))
            wo = b.weight("attn.wo", (h * e, d))
            q = b.contract("attn.q", [x_n1, wq], "q", (bb, 1, h, e),
                           2 * bb * d * h * e)
            b.contract("attn.kv_new", [x_n1, wk, wv], "kv_new",
                       (bb, 1, 2 * kvh, e), 4 * bb * d * kvh * e,
                       out_kind=TensorKind.OUTPUT)
            scores = b.contract("attn.scores", [q, k_cache], "scores",
                                (bb, h, 1, z), 2 * bb * h * z * e)
            probs = b.elementwise("attn.softmax", [scores], "probs",
                                  flops_per_elem=5)
            ctx = b.contract("attn.pv", [probs, v_cache], "ctx",
                             (bb, 1, h, e), 2 * bb * h * z * e)
            y = b.contract("attn.o", [ctx, wo], "attn_out", (bb, 1, d),
                           2 * bb * h * e * d)
        x_mid = b.elementwise("res1", [x, y], "x_mid", flops_per_elem=1)
        ln2_w = b.weight("ln2.w", (d,))
        x_n2 = b.elementwise("ln2", [x_mid, ln2_w], "x_n2", flops_per_elem=6)
        m = mlp_block(b, cfg, "mlp", x_n2, bb)
        b.elementwise("res2", [x_mid, m], "x_out", flops_per_elem=1,
                      out_kind=TensorKind.OUTPUT, out_shape=(bb, 1, d))
    return b.graph


# ---------------------------------------------------------------------------
# group -> kernel-shape selection (execution backends)
# ---------------------------------------------------------------------------
#
# A co-designed plan's fusion groups are *claims*: "these ops run as one
# tile-streaming pass through the explicit region".  The execution backends
# (`repro.exec`) make the claim real; this selection decides, per group,
# which kernel shape the claim lowers to:
#
#   ``stream`` — `pl.pallas_call` passes with a 1-D grid over row tiles of
#                the pass's shared streamed length; contraction right-hand
#                sides stay resident in VMEM across every tile (constant
#                index map), rank-0 dot/norm reductions accumulate across
#                grid steps into SMEM, and scalar epilogues run after the
#                pass.  A group usually lowers to ONE pass; it splits into
#                sequential passes exactly where a contraction (or an
#                spmv) reads a vector produced earlier in the same group
#                (the value must fully materialize first).  A CSR spmv
#                whose pattern meta fixes every row's column offsets to a
#                small static set (``laplacian5``, ``banded``) streams a
#                diagonal layout: ``(K, n)`` values, one row per offset,
#                built once per dispatch from the CSR entries, and the
#                kernel sums ``diag_k * x[i + d_k]`` over static slices of
#                ``x``'s tile and its neighbours.  Any other spmv streams
#                its entries in a padded per-tile layout (tile ``t`` owns
#                exactly its own rows' entries, padded to ``B`` slots —
#                static, from the operand's pattern meta): XLA gathers
#                ``data * x[indices]`` into that layout and the kernel sums
#                each tile's rows as one MXU product with a one-hot row
#                matrix (Mosaic cannot gather in-kernel).
#   ``block``  — one `pl.pallas_call` with whole arrays as single blocks:
#                stencil sweeps need halo rows, so they cannot row-stream
#                without overlap; the explicit region holds the full grid.
#   ``jnp``    — jax.numpy units inlined into the program for shapes no
#                Mosaic kernel expresses (irregular gathers, scans,
#                >2-operand einsums, mixed streamed lengths, CSR operands
#                without pattern meta, working sets over one kernel's
#                VMEM); ``reason`` records why, and ``explain()`` prints
#                it.
#
# Tiles are planned for Mosaic (the TPU kernel compiler): rank-1 vectors
# enter kernels as ``(1, n)`` rows, so a row tile is a multiple of 128
# lanes unless one tile covers the whole pass, and every kernel's
# double-buffered working set fits one kernel's VMEM budget.  A pass whose
# whole rows fit no tile (a dense matvec at n >= 32768: 128 rows of 65536
# columns are 32 MiB) is *column-blocked*: its grid also walks column
# tiles of the matrix and of the contraction's right-hand side, and the
# row tile's product accumulates across them (``StreamPass.tile_cols``).

#: einsum specs the tile-streamer lowers: LHS streams row tiles, RHS stays
#: resident (spec -> index of the resident operand)
STREAM_EINSUMS = {"ab,b->a": 1, "ab,bc->ac": 1}
#: rank-0 contraction of two streamed vectors (rank-1 @ rank-1)
REDUCE_EINSUMS = ("a,a->",)

#: lane-aligned row tiles, largest first
_TILE_ROW_CANDIDATES = (1024, 512, 256, 128)
#: widest column tile of a column-blocked pass: a ``(256, 8192)`` float32
#: block (8 MiB) is the whole-row block of the n=8192 matvec, whose rows
#: DMA as 32 KiB segments; narrower tiles are taken only where this one
#: fits no row tile
WIDE_TILE_COLS = 8192
_LANE, _SUBLANE = 128, 8
#: element width kernels are planned for: TPU kernels run fp32 (an fp64
#: program is refused before it reaches Mosaic — see ``repro.exec.pallas``)
KERNEL_ITEMSIZE = 4
#: VMEM one kernel may plan for.  A TPU v5e core has 128 MiB of VMEM and a
#: 16 MiB default scoped limit; the executor raises the limit to the
#: planned bytes plus headroom (``repro.exec.pallas._vmem_limit``).
KERNEL_VMEM_BYTES = 32 << 20
#: the least a kernel plans for, whatever the explicit region: streaming
#: double buffers are not pins, and an all-implicit split still streams
#: (4 MiB holds a 256-row spmv tile of a 5-point operand)
KERNEL_VMEM_FLOOR = 4 << 20
#: most diagonals an spmv operand may have and still take the diagonal
#: layout: its kernel unrolls one static slice of ``x`` per diagonal
DIA_MAX_OFFSETS = 32
#: rows per grid step, at most, of the pass building a diagonal layout:
#: its one-hot row matrix is ``(B + 128, tile)`` with ``B`` the tile's
#: entries, so its work per row grows with the tile, while a smaller tile
#: pays more grid steps (the 1024² Laplacian's layout on one TPU v5e:
#: 4.4, 4.2 and 5.8 ms at 128, 256 and 512 rows)
DIA_LAYOUT_TILE_ROWS = 256


def kernel_block_bytes(shape) -> int:
    """VMEM bytes of one buffer holding a block of ``shape``, padded to the
    (8, 128) tiling (rank-0 values live in SMEM, rank-1 as ``(1, n)``)."""
    if not shape:
        return 0
    dims = (1,) + tuple(shape) if len(shape) == 1 else tuple(shape)
    lead = math.prod(dims[:-2])
    sub = -(-dims[-2] // _SUBLANE) * _SUBLANE
    lane = -(-dims[-1] // _LANE) * _LANE
    return lead * sub * lane * KERNEL_ITEMSIZE


@dataclasses.dataclass(frozen=True)
class ResidentSlice:
    """A contiguous, indptr-aligned row window of a mesh-partitioned plan:
    shard ``k`` owns rows ``[row0, row0 + rows)`` of the global problem —
    the ``entries`` CSR entries starting at ``entry0`` (produced by
    :func:`partition_plan`)."""
    tensors: Tuple[str, ...]        # the triple members covered (in order)
    rows: int                       # rows in this window (indptr-aligned)
    total_rows: int
    entries: int                    # nnz entries inside the window
    total_entries: int
    row0: int = 0                   # first global row of the window
    entry0: int = 0                 # first global CSR entry of the window

    @property
    def frac(self) -> float:
        return self.rows / max(1, self.total_rows)

    def describe(self) -> str:
        return f"rows[{self.row0}:{self.row0 + self.rows}]"


@dataclasses.dataclass(frozen=True)
class StreamPass:
    """One tile-streaming pallas pass over a slice of a fusion group."""
    ops: Tuple[str, ...]
    rows: int                       # streamed leading-dim length
    tile_rows: int                  # rows per grid step (divides ``rows``)
    resident: Tuple[str, ...]       # operands held in VMEM across all tiles
    #                                 (column-blocked: streamed per column
    #                                 tile instead)
    reductions: Tuple[str, ...]     # rank-0 accumulators in this pass
    vmem_bytes: int = 0             # planned double-buffered working set
    spmv: Tuple[str, ...] = ()      # CSR spmv ops
    dia: Tuple[str, ...] = ()       # those on the diagonal layout (the
    #                                 rest: the per-tile entry layout)
    tile_cols: Optional[int] = None  # matrix columns per grid step of a
    #                                 column-blocked pass (None: whole rows)

    def tiling(self) -> str:
        """``rows/tile`` as ``explain()`` prints it, ``/<cols>c`` added
        for a column-blocked pass."""
        cols = f"/{self.tile_cols}c" if self.tile_cols else ""
        return f"{self.rows}r/{self.tile_rows}t{cols}"


@dataclasses.dataclass(frozen=True)
class GroupKernel:
    """The kernel shape selected for one fusion group."""
    ops: Tuple[str, ...]
    kind: str                       # "stream" | "block" | "jnp"
    passes: Tuple[StreamPass, ...] = ()   # populated for kind == "stream"
    reason: str = ""                # why a jnp fallback was selected

    def describe(self) -> str:
        if self.kind == "stream":
            bits = []
            for p in self.passes:
                res = f" res={'+'.join(p.resident)}" if p.resident else ""
                red = f" acc={'+'.join(p.reductions)}" if p.reductions \
                    else ""
                sp = (" spmv=" + "+".join(
                    f"{o}:{'dia' if o in p.dia else 'csr'}"
                    for o in p.spmv)) if p.spmv else ""
                bits.append(f"{p.tiling()}{res}{red}{sp}")
            tag = " | ".join(bits)
            n = len(self.passes)
            label = ("pallas-spmv" if any(p.spmv for p in self.passes)
                     else "pallas-stream")
            return (f"{label}[{tag}]" if n == 1
                    else f"{label}[{n} passes: {tag}]")
        if self.kind == "block":
            return "pallas-block[halo ops, full-array block]"
        return f"jnp-fallback({self.reason})"


def _pick_tile_rows(rows: int, per_row_bytes: int, resident_bytes: int,
                    budget: int, extra=None) -> Optional[int]:
    """Largest Mosaic-legal row tile whose double-buffered working set
    ``2 * (tile * per_row_bytes + resident_bytes)`` (plus ``extra(tile)``
    when given) fits ``budget``, or ``None`` when none does.  Legal tiles
    divide ``rows`` and are a multiple of 128 lanes, or cover all of
    ``rows`` in one tile."""
    cands = {t for t in _TILE_ROW_CANDIDATES if rows % t == 0}
    if rows <= _TILE_ROW_CANDIDATES[0] or not cands:
        cands.add(rows)
    for t in sorted(cands, reverse=True):
        need = 2 * (t * per_row_bytes + resident_bytes)
        if extra is not None:
            need += extra(t)
        if need <= budget:
            return t
    return None


def csr_tile_entries(params, rows: int, tile_rows: int) -> Optional[int]:
    """Slots per tile of the padded per-tile CSR layout: the most entries
    any ``tile_rows``-row tile holds, rounded up to 128 lanes — from the
    operand's pattern meta (``params``: a CSR leaf's params or graph
    meta), or ``None`` without it."""
    get = params.get if hasattr(params, "get") else dict(params).get
    if get("pattern") is None:
        return None
    return _tile_entries(get("pattern"), rows, tile_rows, get("density"),
                         get("bandwidth"))


def spmv_offsets(params, rows: int) -> Optional[Tuple[int, ...]]:
    """The diagonal offsets an spmv operand runs on — the static offset
    set of its pattern meta (``params``, as for :func:`csr_tile_entries`;
    :func:`repro.frontends.sparse.pattern_offsets`) — or ``None`` when it
    runs on the padded per-tile layout: no such set, more than
    :data:`DIA_MAX_OFFSETS` diagonals, or no row tile to build the
    diagonal layout with."""
    from ..frontends.sparse import pattern_offsets
    get = params.get if hasattr(params, "get") else dict(params).get
    if get("pattern") is None or dia_layout_tile(rows) is None:
        return None
    offsets = pattern_offsets(get("pattern"), rows, get("bandwidth"))
    if offsets is None or len(offsets) > DIA_MAX_OFFSETS:
        return None
    return offsets


def dia_layout_tile(rows: int) -> Optional[int]:
    """Rows per grid step of the pass that builds a diagonal layout from
    the CSR entries: the largest legal tile up to
    :data:`DIA_LAYOUT_TILE_ROWS`, or ``None`` when ``rows`` has none."""
    if rows <= DIA_LAYOUT_TILE_ROWS:
        return rows
    return next((t for t in _TILE_ROW_CANDIDATES
                 if t <= DIA_LAYOUT_TILE_ROWS and rows % t == 0), None)


def dia_halo_tiles(offsets, tile_rows: int) -> int:
    """Neighbour tiles of ``x`` a diagonal-layout spmv reads on each side
    of its own tile."""
    return -(-max(abs(d) for d in offsets) // tile_rows)


def check_csr_feeds(units, program, feeds) -> None:
    """Refuse CSR feeds that do not fit the per-tile layout their spmv
    passes were planned with (``units``: an :class:`ExecPlan`'s units);
    a diagonal-layout spmv builds its layout from per-tile windows of
    :func:`dia_layout_tile` rows.  A row tile holding more entries than
    its padded slots — feeds whose rows are spread differently from the
    pattern meta the plan was traced with — would otherwise lose the
    excess entries silently."""
    import numpy as np
    checked = set()
    for unit in units:
        sp = unit.sp
        for op in (sp.spmv if sp is not None else ()):
            ipn = program.nodes[op].inputs[0]
            tile = dia_layout_tile(sp.rows) if op in sp.dia \
                else sp.tile_rows
            if (ipn, tile) in checked or ipn not in feeds:
                continue
            checked.add((ipn, tile))
            slots = csr_tile_entries(program.nodes[ipn].params, sp.rows,
                                     tile)
            starts = np.asarray(feeds[ipn][::tile])
            most = int(np.max(np.diff(starts)))
            if most > slots:
                raise ValueError(
                    f"CSR feed {ipn!r} puts {most} entries in one "
                    f"{tile}-row tile; the plan was lowered for "
                    f"at most {slots} (its pattern meta): trace the plan "
                    f"for this operand's pattern")


@functools.lru_cache(maxsize=256)
def _tile_entries(pattern, rows, tile_rows, density, bandwidth) -> int:
    from ..frontends.sparse import row_counts
    counts = row_counts(pattern, rows, density=density, bandwidth=bandwidth)
    per_tile = counts.reshape(-1, tile_rows).sum(axis=1)
    return int(-(-max(int(per_tile.max()), 1) // _LANE) * _LANE)


def _spmv_tile_bytes(tile_rows: int, entries: int) -> int:
    """VMEM of one spmv in a pass: its double-buffered ``(1, B)`` values
    block and ``(1, tile)`` row slot bounds (first, stop), and the
    ``(B, tile)`` one-hot row matrix with its iota and mask temporaries."""
    return (2 * kernel_block_bytes((entries,))
            + 2 * 2 * kernel_block_bytes((tile_rows,))
            + 3 * tile_rows * entries * KERNEL_ITEMSIZE)


def _dia_tile_bytes(tile_rows: int, n_offsets: int, halo: int) -> int:
    """VMEM of one diagonal-layout spmv in a pass: its double-buffered
    ``(K, tile)`` diagonals block and ``2·halo + 1`` ``(1, tile)`` tiles
    of ``x``, their concatenated window, and the running sum with one
    product."""
    x_tile = kernel_block_bytes((tile_rows,))
    return (2 * kernel_block_bytes((n_offsets, tile_rows))
            + 3 * (2 * halo + 1) * x_tile + 2 * x_tile)


def dia_layout_bytes(tile_rows: int, entries: int, n_offsets: int) -> int:
    """VMEM of the pass building one operand's diagonal layout: its
    double-buffered column-id and value windows (the tile's ``B`` entries
    in whole 128-entry rows, one row more than ``B`` fills), ``(1, tile)``
    slot bounds and ``(K, tile)`` output block, and the one-hot row matrix
    over the window with its iota and mask temporaries."""
    window = -(-entries // _LANE) + 1
    return (2 * 2 * kernel_block_bytes((window, 1, _LANE))
            + 2 * 2 * kernel_block_bytes((tile_rows,))
            + 2 * kernel_block_bytes((n_offsets, tile_rows))
            + 3 * tile_rows * window * _LANE * KERNEL_ITEMSIZE)


def _row_bytes(shape) -> int:
    """VMEM bytes one streamed row of a tensor of ``shape`` takes in a
    row-tiled block (a rank-1 row pads to 8 sublanes)."""
    if len(shape) == 1:
        return _SUBLANE * KERNEL_ITEMSIZE
    return kernel_block_bytes((_SUBLANE,) + tuple(shape[1:])) // _SUBLANE


def select_group_kernels(graph: OpGraph, groups, explicit_bytes: int
                         ) -> Tuple[GroupKernel, ...]:
    """Pick a kernel shape for every fusion group of a frontend plan.

    Pure graph-level classification (shapes + op specs); the expression
    semantics needed to *execute* each shape live in ``repro.exec``.
    ``explicit_bytes`` (the plan's explicit region) bounds each kernel's
    VMEM budget, kept between :data:`KERNEL_VMEM_FLOOR` and
    :data:`KERNEL_VMEM_BYTES`.
    """
    return tuple(_select_one(graph, list(g), explicit_bytes)
                 for g in groups)


def _kernel_budget(explicit_bytes: int) -> int:
    return min(KERNEL_VMEM_BYTES, max(explicit_bytes, KERNEL_VMEM_FLOOR))


def _finalizes_late(graph: OpGraph, op, late: set) -> bool:
    """True when ``op``'s value only exists on the pass's *final* grid step:
    rank-0 reductions (dot/norm/`a,a->` accumulate across tiles), and any
    scalar computed from one (the ``beta = rs'/rs`` epilogues)."""
    if graph.tensors[op.output].shape != ():
        return False
    if op.spec == "reduce" or op.is_einsum:
        return True
    return any(t in late for t in op.inputs)


def _segment_group(graph: OpGraph, group) -> list:
    """Split a group into streaming passes.  A new pass starts where an op
    needs a value that only exists once the current pass *completes*:

    * a contraction whose resident operand was produced earlier in the
      group (the vector must fully materialize before it can sit in VMEM),
    * a tiled op reading an in-pass rank-0 value that *finalizes on the
      last tile* — a reduction, or a scalar chained off one.  A scalar
      whose in-pass inputs are all tile-invariant (``nalpha = -alpha`` with
      ``alpha`` external) is recomputed per tile instead ("eager" scalar),
      so it does NOT force a pass break; this is what lets the residency
      planner fuse ``x``/``r`` updates with the neg/axpy glue between them.

    ``fusable()`` never emits groups that need the late-scalar break, but
    ``select_group_kernels`` is public API and must be safe for any group
    handed to it.
    """
    segments, cur, produced, late = [], [], set(), set()
    for oname in group:
        op = graph.ops[oname]
        needs_break = False
        if op.is_einsum and op.spec in STREAM_EINSUMS:
            needs_break = op.inputs[STREAM_EINSUMS[op.spec]] in produced
        if op.spec == "spmv":
            # x is read beyond the tile's own rows (by column index, or
            # its neighbour tiles): it must materialize
            needs_break = op.inputs[3] in produced
        if not needs_break and graph.tensors[op.output].shape != ():
            needs_break = any(t in late for t in op.inputs)
        if needs_break and cur:
            segments.append(cur)
            cur, produced, late = [], set(), set()
        cur.append(oname)
        produced.add(op.output)
        if _finalizes_late(graph, op, late):
            late.add(op.output)
    if cur:
        segments.append(cur)
    return segments


def _select_one(graph: OpGraph, group, explicit_bytes: int) -> GroupKernel:
    ops = [graph.ops[o] for o in group]
    gops = tuple(group)

    for op in ops:
        if op.irregular or op.spec in ("gather", "scan"):
            return GroupKernel(gops, "jnp",
                               reason=f"{op.name}: irregular/scan reuse")

    # stencil sweeps need halo rows -> whole-array block kernel; they may
    # chain with same-shape elementwise ops inside the group
    if any(op.spec == "stencil2d" for op in ops):
        shapes = {graph.tensors[op.output].shape for op in ops}
        if len(shapes) != 1 or not all(op.spec in ("stencil2d", "ew")
                                       for op in ops):
            return GroupKernel(gops, "jnp",
                               reason="stencil mixed with non-halo ops")
        names = {t for op in ops for t in (*op.inputs, op.output)}
        need = 2 * sum(kernel_block_bytes(graph.tensors[t].shape)
                       for t in names)
        if need > _kernel_budget(explicit_bytes):
            return GroupKernel(gops, "jnp",
                               reason=f"whole-array block needs "
                               f"{need >> 20} MiB of VMEM")
        return GroupKernel(gops, "block")

    passes = []
    for seg in _segment_group(graph, group):
        sp = _classify_pass(graph, seg, explicit_bytes)
        if isinstance(sp, str):                    # rejection reason
            return GroupKernel(gops, "jnp", reason=sp)
        passes.append(sp)
    return GroupKernel(gops, "stream", passes=tuple(passes))


def _classify_pass(graph: OpGraph, seg, explicit_bytes: int):
    """One segment -> :class:`StreamPass`, or a rejection-reason string."""
    ops = [graph.ops[o] for o in seg]
    produced = {op.output for op in ops}
    rows = None
    per_row = 0
    resident = []
    reductions = []
    spmvs = []
    streamed_seen = set()

    def _stream(tname) -> bool:
        """Account ``tname`` as streamed; False on row-count clash."""
        nonlocal rows, per_row
        shape = graph.tensors[tname].shape
        if rows is None:
            rows = shape[0]
        elif rows != shape[0]:
            return False
        if tname not in streamed_seen:
            streamed_seen.add(tname)
            per_row += _row_bytes(shape)
        return True

    for op in ops:
        oshape = graph.tensors[op.output].shape
        if op.is_einsum and op.spec in REDUCE_EINSUMS:
            if not all(_stream(t) for t in op.inputs):
                return f"{op.name}: mixed row counts"
            reductions.append(op.output)
        elif op.is_einsum:
            rhs = STREAM_EINSUMS.get(op.spec)
            if rhs is None:
                return f"{op.name}: einsum {op.spec!r} beyond the streamer"
            if op.inputs[rhs] in produced:
                return f"{op.name}: contraction RHS produced in-pass"
            if not _stream(op.inputs[1 - rhs]) or not _stream(op.output):
                return f"{op.name}: mixed row counts"
            if op.inputs[rhs] not in resident:
                resident.append(op.inputs[rhs])
        elif op.spec == "spmv":
            if op.inputs[3] in produced:
                return f"{op.name}: spmv operand produced in-pass"
            if not _stream(op.output):
                return f"{op.name}: mixed row counts"
            spmvs.append(op)
        elif op.spec == "reduce":
            if any(len(graph.tensors[t].shape) != 1 for t in op.inputs):
                return f"{op.name}: non-vector reduction"
            if not all(_stream(t) for t in op.inputs):
                return f"{op.name}: mixed row counts"
            reductions.append(op.output)
        elif op.spec == "ew":
            if oshape == ():        # scalar epilogue (beta = rs'/rs, ...)
                continue
            for t in list(op.inputs) + [op.output]:
                if graph.tensors[t].shape == ():
                    continue        # broadcast scalar operand
                if graph.tensors[t].shape != oshape:
                    return f"{op.name}: operand shape mismatch"
                if not _stream(t):
                    return f"{op.name}: mixed row counts"
        else:
            return f"{op.name}: op spec {op.spec!r}"

    if rows is None:                # nothing streams: scalar-only group
        return "scalar-only group"

    metas = [graph.tensors[op.inputs[0]].meta for op in spmvs]
    if any(dict(m).get("pattern") is None for m in metas):
        return "spmv operand carries no CSR pattern meta"

    offsets = [spmv_offsets(m, rows) for m in metas]

    def spmv_bytes(t: int) -> int:
        return sum(_spmv_tile_bytes(t, csr_tile_entries(m, rows, t))
                   if offs is None else
                   _dia_tile_bytes(t, len(offs), dia_halo_tiles(offs, t))
                   for m, offs in zip(metas, offsets))

    res_bytes = sum(kernel_block_bytes(graph.tensors[t].shape)
                    for t in resident)
    budget = _kernel_budget(explicit_bytes)
    tile = _pick_tile_rows(rows, per_row, res_bytes, budget,
                           spmv_bytes if spmvs else None)
    tile_cols = None
    if tile is not None:
        vmem = (2 * (tile * per_row + res_bytes)
                + (spmv_bytes(tile) if spmvs else 0))
    else:
        block = _column_block(graph, ops, streamed_seen, resident, rows,
                              budget)
        if block is None:
            return (f"no {rows}-row tiling fits {budget >> 20} MiB of VMEM "
                    f"({res_bytes >> 20} MiB resident)")
        tile, tile_cols, vmem = block
    return StreamPass(ops=tuple(seg), rows=rows, tile_rows=tile,
                      resident=tuple(resident), reductions=tuple(reductions),
                      vmem_bytes=vmem,
                      spmv=tuple(op.name for op in spmvs),
                      dia=tuple(op.name for op, offs in zip(spmvs, offsets)
                                if offs is not None),
                      tile_cols=tile_cols)


def _column_block(graph: OpGraph, ops, streamed, resident, rows: int,
                  budget: int) -> Optional[Tuple[int, int, int]]:
    """``(tile_rows, tile_cols, vmem_bytes)`` of a column-blocked pass, or
    ``None`` where the pass cannot be blocked: it must hold an ``ab,b->a``
    contraction, no other contraction and no spmv, and every matrix it
    streams must be such a contraction's left operand, all of one width.

    Each grid step reads a ``(tile_rows, tile_cols)`` block of every
    matrix and the matching ``(1, tile_cols)`` block of every right-hand
    side, and adds the row tile's partial product into a ``(1,
    tile_rows)`` VMEM accumulator; the pass's vectors move with the row
    tile only.  The column tile is the widest lane-aligned divisor of the
    width up to :data:`WIDE_TILE_COLS` with which some row tile fits
    ``budget``, and the row tile the largest that then fits, so the
    working set no longer grows with the width."""
    if any(op.spec == "spmv" or (op.is_einsum and op.spec != "ab,b->a"
                                 and op.spec not in REDUCE_EINSUMS)
           for op in ops):
        return None
    matvecs = [op for op in ops if op.is_einsum and op.spec == "ab,b->a"]
    mats = {op.inputs[0] for op in matvecs}
    if not matvecs or any(len(graph.tensors[t].shape) > 1
                          for t in streamed if t not in mats):
        return None
    widths = {graph.tensors[t].shape[1] for t in mats}
    if len(widths) != 1:
        return None
    (cols,) = widths
    vec_row = sum(_row_bytes(graph.tensors[t].shape)
                  for t in streamed if t not in mats)

    def accumulators(tr: int) -> int:
        return len(matvecs) * kernel_block_bytes((tr,))

    widest = min(cols, WIDE_TILE_COLS)
    tiles = {t for t in (_LANE << k for k in range(widest.bit_length()))
             if t <= widest and cols % t == 0}
    if cols <= WIDE_TILE_COLS:
        tiles.add(cols)
    for tc in sorted(tiles, reverse=True):
        per_row = vec_row + len(mats) * tc * KERNEL_ITEMSIZE
        rhs = len(resident) * kernel_block_bytes((tc,))
        tr = _pick_tile_rows(rows, per_row, rhs, budget, accumulators)
        if tr is not None:
            return tr, tc, 2 * (tr * per_row + rhs) + accumulators(tr)
    return None


# ---------------------------------------------------------------------------
# execution planning: fused dispatch units, cross-pass residency, rolled loops
# ---------------------------------------------------------------------------
#
# ``select_group_kernels`` answers "what kernel shape does each fusion group
# lower to"; this layer answers "how does the whole plan execute as ONE
# program".  Three decisions live here:
#
#   * **units** — the flat dispatch sequence (stream groups contribute one
#     unit per pass);
#   * **residency planning** — adjacent units sharing the same streamed
#     length fuse into a single pass when no value must materialize between
#     them, so streamed operands are read once and resident operands are
#     carried across what used to be pass *and group* boundaries (the
#     execution image of the explicit region persisting across the group
#     order) instead of being re-streamed per unit;
#   * **rolled loops** — when the frontend recorded per-iteration bodies
#     (``Program.iteration``) and the scheduled unit sequence repeats them
#     verbatim, the repeated segment is described once plus a trip count,
#     so an executor can run it as ``lax.fori_loop`` over one compiled body
#     instead of dispatching every unrolled copy.

@dataclasses.dataclass(frozen=True)
class ExecUnit:
    """One execution dispatch unit: a streaming pass, a whole-array block
    kernel, or a jnp-fallback group slice."""
    ops: Tuple[str, ...]
    kind: str                           # "stream" | "block" | "jnp"
    sp: Optional[StreamPass] = None     # populated for kind == "stream"
    groups: Tuple[int, ...] = ()        # originating fusion-group indices
    fused: int = 1                      # pre-fusion units merged into this

    def describe(self) -> str:
        extra = ""
        if self.sp is not None:
            extra = f" {self.sp.tiling()}"
            if self.sp.resident:
                extra += f" res={'+'.join(self.sp.resident)}"
        if self.fused > 1:
            extra += f" (fused x{self.fused})"
        return f"{self.kind}[{'+'.join(self.ops)}]{extra}"


@dataclasses.dataclass(frozen=True)
class ResidentSpan:
    """A tensor held resident (constant index map) over a unit range."""
    tensor: str
    first: int                          # first unit index (inclusive)
    last: int                           # last unit index (inclusive)


@dataclasses.dataclass(frozen=True)
class CarrySlot:
    """One loop-carried value of a rolled iteration segment."""
    update: str            # template node whose value advances the slot
    final: str             # unrolled name the slot holds after the loop
    init: Optional[str] = None   # pre-loop env name seeding the slot
    #                              (None: seed with zeros — the slot is
    #                              only read after its first update)
    read: Optional[str] = None   # name the template reads it as (None:
    #                              output-only slot, threaded for the final)


@dataclasses.dataclass(frozen=True)
class RolledLoop:
    """A detected repeated iteration segment of the unit sequence: units
    ``[first, first + per_iter)`` are the template body; executing it
    ``n_iters`` times with the carry rebinding below reproduces units
    ``[first, first + per_iter * n_iters)`` exactly."""
    first: int
    per_iter: int
    n_iters: int
    slots: Tuple[CarrySlot, ...]

    @property
    def stop(self) -> int:
        """Index one past the last unit the rolled segment replaces."""
        return self.first + self.per_iter * self.n_iters


def flatten_units(kernels) -> Tuple[ExecUnit, ...]:
    """The flat dispatch sequence of a kernel selection (stream groups
    contribute one unit per pass, in order)."""
    units: List[ExecUnit] = []
    for gi, gk in enumerate(kernels):
        if gk.kind == "stream":
            for sp in gk.passes:
                units.append(ExecUnit(sp.ops, "stream", sp, (gi,)))
        else:
            units.append(ExecUnit(tuple(gk.ops), gk.kind, None, (gi,)))
    return tuple(units)


def _merge_candidate(graph: OpGraph, unit: ExecUnit) -> bool:
    """Streaming passes merge; so do scalar-only jnp groups (their rank-0
    chains become eager/epilogue scalars of the absorbing pass)."""
    if unit.kind == "stream":
        return True
    if unit.kind != "jnp":
        return False
    return all(graph.ops[o].spec == "ew" and not graph.ops[o].irregular
               and graph.tensors[graph.ops[o].output].shape == ()
               for o in unit.ops)


def fuse_units(graph: OpGraph, units, explicit_bytes: int
               ) -> Tuple[ExecUnit, ...]:
    """The cross-pass residency planner: greedily merge adjacent units into
    one streaming pass wherever re-segmentation proves no value has to
    materialize at the old boundary.  Merged units stream each operand once
    for all their ops and keep resident operands in place across the former
    pass/group boundaries instead of re-streaming them."""
    fused: List[ExecUnit] = []
    for unit in units:
        prev = fused[-1] if fused else None
        if (prev is not None and _merge_candidate(graph, prev)
                and _merge_candidate(graph, unit)):
            ops = list(prev.ops) + list(unit.ops)
            segs = _segment_group(graph, ops)
            if len(segs) == 1:
                sp = _classify_pass(graph, segs[0], explicit_bytes)
                if isinstance(sp, StreamPass):
                    fused[-1] = ExecUnit(tuple(ops), "stream", sp,
                                         prev.groups + unit.groups,
                                         prev.fused + unit.fused)
                    continue
        fused.append(unit)
    return tuple(fused)


def resident_spans(units) -> Tuple[ResidentSpan, ...]:
    """Unit-index span each resident operand is held over."""
    first: Dict[str, int] = {}
    last: Dict[str, int] = {}
    for ui, unit in enumerate(units):
        if unit.sp is None:
            continue
        for t in unit.sp.resident:
            first.setdefault(t, ui)
            last[t] = ui
    return tuple(ResidentSpan(t, first[t], last[t]) for t in sorted(first))


def _build_sigma(program) -> Optional[Dict[str, str]]:
    """The iteration-successor renaming: node at position ``j`` of body
    ``i`` ↦ node at position ``j`` of body ``i+1``.  Only equal-length
    consecutive bodies contribute (GMRES's growing Arnoldi bodies simply
    produce a partial map the matcher then rejects)."""
    bodies = [list(b) for b in program.iteration_bodies()]
    if len(bodies) < 2:
        return None
    sigma: Dict[str, str] = {}
    for a, b in zip(bodies, bodies[1:]):
        if len(a) == len(b):
            sigma.update(zip(a, b))
    return sigma or None


def _unit_matches(program, sigma: Dict[str, str], ua: ExecUnit,
                  ub: ExecUnit) -> bool:
    """Is ``ub`` exactly the σ-image of ``ua``?  Ops map positionally
    through σ, node structure is identical, and every operand is either
    σ-renamed or the same loop-invariant name."""
    if ua.kind != ub.kind or len(ua.ops) != len(ub.ops):
        return False
    if (ua.sp is None) != (ub.sp is None):
        return False
    if ua.sp is not None and (ua.sp.rows != ub.sp.rows
                              or ua.sp.tile_rows != ub.sp.tile_rows
                              or ua.sp.tile_cols != ub.sp.tile_cols):
        return False
    for o, o2 in zip(ua.ops, ub.ops):
        if sigma.get(o) != o2:
            return False
        na, nb = program.nodes[o], program.nodes[o2]
        if (na.op != nb.op or na.shape != nb.shape
                or na.dtype_bytes != nb.dtype_bytes
                or na.params != nb.params
                or len(na.inputs) != len(nb.inputs)):
            return False
        for ta, tb in zip(na.inputs, nb.inputs):
            if tb != sigma.get(ta, ta):
                return False
    return True


def detect_rolled_loop(program, units) -> Optional[RolledLoop]:
    """Find the repeated per-iteration segment of a scheduled unit sequence.

    ``program`` is an expression ``Program`` (duck-typed: needs
    ``iteration_bodies()``, ``nodes`` and ``outputs``) whose builders
    recorded the unrolled solver-iteration bodies.  Those bodies define the
    successor renaming σ (:func:`_build_sigma`); detection then *proves*
    unit-level periodicity — a period ``P`` and region where every unit is
    exactly the σ-image of the unit ``P`` places earlier — so it tolerates
    schedules that phase-shift work across iteration boundaries (BiCGStab's
    deferred ``x`` update).  Iteration 0 typically stays unrolled: CG's
    ``p0`` aliases ``r0``, so its wiring differs from every later
    iteration's.  Returns the roll with the largest unit savings, or
    ``None`` when no period survives the proof.
    """
    if program is None:
        return None
    sigma = _build_sigma(program)
    if sigma is None:
        return None
    total = len(units)

    best: Optional[Tuple[int, int, int, int]] = None   # (saved, first, P, n)
    for P in range(1, total // 2 + 1):
        # every maximal run of σ-matches units[t] -> units[t+P]: a run over
        # t ∈ [a, c] makes units[a, c+P+1) periodic with period P.  All
        # runs matter — the final unrolled iteration often schedules
        # differently (CG fuses the last x-update into it), leaving a
        # trivial run at the tail next to the real one
        t = total - P - 1
        while t >= 0:
            if not _unit_matches(program, sigma, units[t], units[t + P]):
                t -= 1
                continue
            c = t
            while t > 0 and _unit_matches(program, sigma,
                                          units[t - 1], units[t - 1 + P]):
                t -= 1
            a = t
            n = (c + P + 1 - a) // P     # whole periods in the region
            a = (c + P + 1) - P * n      # truncate the partial leading one
            saved = (n - 1) * P
            if n >= 2 and (best is None or saved > best[0]):
                best = (saved, a, P, n)
            t -= 1
    if best is None:
        return None
    _, first, P, n = best

    # carry slots: template reads whose σ-image the template itself
    # produces thread through the loop; σ-mapped reads produced elsewhere
    # defeat the roll; σ-less reads are loop-invariant
    template = units[first:first + P]
    products = [o for u in template for o in u.ops]
    prod_set = set(products)
    reads: List[str] = []
    for u in template:
        for o in u.ops:
            for t in program.nodes[o].inputs:
                if t not in prod_set and t not in reads:
                    reads.append(t)

    def sig_pow(name: str, k: int) -> Optional[str]:
        for _ in range(k):
            name = sigma.get(name)
            if name is None:
                return None
        return name

    final_of: Dict[str, str] = {}
    for o in products:
        f = sig_pow(o, n - 1)
        if f is None:
            return None
        final_of[o] = f

    slots: List[CarrySlot] = []
    updates: set = set()
    for t in reads:
        st = sigma.get(t)
        if st is None:
            continue                     # loop-invariant operand
        if st not in prod_set:
            return None                  # next-generation value produced
        #                                  outside the template
        slots.append(CarrySlot(update=st, final=final_of[st],
                               init=t, read=t))
        updates.add(st)

    # products the epilogue (or the program outputs) read must come from
    # the final rolled generation; thread them as output-only slots
    region_products = {o for u in units[first:first + P * n] for o in u.ops}
    needed_after = set(program.outputs)
    for u in units[first + P * n:]:
        for o in u.ops:
            needed_after.update(program.nodes[o].inputs)
    final_to_template = {f: o for o, f in final_of.items()}
    for f in sorted(needed_after & region_products):
        o = final_to_template.get(f)
        if o is None:
            return None                  # a mid-generation value escapes
        if o not in updates:
            updates.add(o)
            slots.append(CarrySlot(update=o, final=f, init=None,
                                   read=None))
    if not slots:
        return None                      # iterations that carry nothing
    return RolledLoop(first=first, per_iter=P, n_iters=n,
                      slots=tuple(slots))


@dataclasses.dataclass(frozen=True)
class ExecPlan:
    """Execution-level plan for one compiled frontend plan: the fused
    dispatch units, the residency spans they imply, and the rolled
    iteration segment (when one was proven)."""
    units: Tuple[ExecUnit, ...]
    roll: Optional[RolledLoop]
    spans: Tuple[ResidentSpan, ...]
    n_prefuse: int                      # unit count before residency fusion

    def describe(self) -> str:
        bits = [f"{len(self.units)} units"]
        if len(self.units) != self.n_prefuse:
            bits.append(f"fused from {self.n_prefuse} passes")
        if self.roll is not None:
            r = self.roll
            bits.append(f"units[u{r.first}..u{r.first + r.per_iter - 1}] "
                        f"rolled x{r.n_iters}")
        carried = [sp for sp in self.spans if sp.last > sp.first]
        if carried:
            bits.append("resident across units: " + ", ".join(
                f"{sp.tensor}[u{sp.first}..u{sp.last}]" for sp in carried))
        return "; ".join(bits)


def plan_execution(graph: OpGraph, kernels, explicit_bytes: int,
                   program=None) -> ExecPlan:
    """Units → residency fusion → rolled-loop detection, in that order.
    ``program`` (the frontend expression DAG) is optional; without it the
    plan is straight-line."""
    units = flatten_units(kernels)
    n_pre = len(units)
    fused = fuse_units(graph, units, explicit_bytes)
    roll = detect_rolled_loop(program, fused)
    return ExecPlan(units=fused, roll=roll, spans=resident_spans(fused),
                    n_prefuse=n_pre)


# ---------------------------------------------------------------------------
# mesh partitioning: contiguous row-block shards of an ExecPlan
# ---------------------------------------------------------------------------
#
# A co-designed :class:`ExecPlan` runs its streamed passes over one global
# leading dimension.  :func:`partition_plan` splits that dimension into K
# contiguous row blocks — one per device of a 1-D ``jax.sharding.Mesh`` —
# and proves the split is sound for every unit of the plan:
#
#   * dense streamed operands split into equal row blocks (a shard is a
#     :class:`ResidentSlice` with a nonzero ``row0``, reusing the
#     overbooked-pin machinery rather than re-inventing it);
#   * CSR operands split at *indptr-aligned* row boundaries: the exact
#     per-shard entry windows come from the deterministic pattern
#     generators (``frontends.sparse.row_counts``), padded to one static
#     per-shard width so every shard traces the same program;
#   * contraction right-hand sides and spmv ``x`` vectors are exchanged
#     whole (``all_gather``) before each pass — the gathered-x exchange;
#   * ``stencil2d`` sweeps exchange one halo row with each mesh neighbour
#     (``ppermute``) instead of gathering the grid;
#   * rank-0 dot/norm reductions combine per-shard partials with ``psum``
#     (the reference oracle instead gathers operands whole so its sharded
#     results stay bitwise-identical to the single-device rules).
#
# Shapes the row-block story cannot express raise
# :class:`PlanPartitionError` — loudly, at lower time, never at dispatch.

class PlanPartitionError(ValueError):
    """A co-designed plan cannot be split into contiguous row blocks."""


@dataclasses.dataclass(frozen=True)
class CsrShardLayout:
    """Static row-block split of one CSR operand triple.

    ``entry_starts[k]`` is the global CSR entry index of shard ``k``'s
    first row (``entry_starts[K] == nnz``) — by construction the value of
    ``indptr[k * rows_per_shard]``, so every boundary is indptr-aligned.
    At dispatch each shard slices ``pad_entries`` entries starting at its
    boundary out of the (zero-padded) global indices/data, so all shards
    share one static shape; positions past a shard's true window resolve
    to local row id ``rows_per_shard`` and are dropped by the same
    out-of-range mask the tile kernels already apply."""
    indptr: str
    indices: str
    data: str
    rows: int                        # global row count
    nnz: int                         # global stored entries
    entry_starts: Tuple[int, ...]    # len n_shards + 1, indptr-aligned
    pad_entries: int                 # static per-shard entry window
    slices: Tuple[ResidentSlice, ...]   # shard k's row/entry window

    def describe(self) -> str:
        blocks = "/".join(str(b - a) for a, b in
                          zip(self.entry_starts, self.entry_starts[1:]))
        return (f"csr[{self.data}: {self.rows}r {self.nnz}nnz -> "
                f"{blocks} entries, pad {self.pad_entries}]")


@dataclasses.dataclass(frozen=True)
class ShardedExecPlan:
    """A partitioned execution plan: the single-device plan, its localized
    (per-shard) twin, and everything an executor needs to wire the
    exchanges — which names are row-sharded, which get gathered whole,
    which ops halo-exchange, and which rank-0 values psum."""
    base: ExecPlan                   # global plan (unchanged)
    local: ExecPlan                  # per-shard plan: rows / tiles ÷ K
    n_shards: int
    axis: str                        # mesh axis name
    rows: int                        # global streamed leading dim
    shards: Tuple[ResidentSlice, ...]      # shard k's row block
    csr: Tuple[CsrShardLayout, ...]        # per CSR operand triple
    sharded: Tuple[str, ...]         # names split along their leading dim
    gathered: Tuple[str, ...]        # row-sharded names exchanged whole
    halo: Tuple[str, ...]            # ops needing halo exchange
    reduced: Tuple[str, ...]         # rank-0 values combined across shards

    @property
    def rows_per_shard(self) -> int:
        return self.rows // self.n_shards

    def is_sharded(self, name: str) -> bool:
        return name in self._sharded_set

    @property
    def _sharded_set(self):
        return set(self.sharded)

    def describe(self) -> str:
        bits = [f"{self.n_shards} shards x {self.rows_per_shard} rows "
                f"over '{self.axis}'"]
        if self.gathered:
            bits.append("gather=" + "+".join(self.gathered))
        if self.reduced:
            bits.append("psum=" + "+".join(self.reduced))
        if self.halo:
            bits.append("halo=" + "+".join(self.halo))
        for lay in self.csr:
            bits.append(lay.describe())
        return "; ".join(bits)


def _localize_tile(tile_rows: int, rows_loc: int) -> int:
    """The per-shard row tile: the largest Mosaic-legal tile of the local
    row count (a lane-aligned divisor, or the whole shard) not exceeding
    the global tile — the shard's working set never grows."""
    for t in sorted({tile_rows, *_TILE_ROW_CANDIDATES}, reverse=True):
        if t <= min(tile_rows, rows_loc) and rows_loc % t == 0 \
                and (t % _LANE == 0 or t == rows_loc):
            return t
    return rows_loc


def _localize_pass(sp: StreamPass, n_shards: int) -> StreamPass:
    """The pass on one shard's row block; a column-blocked pass keeps its
    column tile, since a shard holds whole rows of the global width."""
    rows_loc = sp.rows // n_shards
    return dataclasses.replace(
        sp, rows=rows_loc, tile_rows=_localize_tile(sp.tile_rows, rows_loc))


def _csr_layout(program, node, n_shards: int) -> CsrShardLayout:
    """Indptr-aligned entry windows for one spmv's CSR triple, derived
    from the deterministic pattern meta on the triple's leaves."""
    from ..frontends.sparse import row_counts
    indptr, indices, data = node.inputs[:3]
    rows = int(node.shape[0])
    nnz = int(program.nodes[indices].shape[0])
    leaf = program.nodes[indptr]
    pattern = leaf.param("pattern")
    if pattern is None:
        raise PlanPartitionError(
            f"spmv '{node.name}': CSR operand '{data}' carries no pattern "
            f"meta; cannot compute indptr-aligned shard boundaries")
    try:
        counts = row_counts(pattern, rows,
                            density=leaf.param("density"),
                            bandwidth=leaf.param("bandwidth"))
    except Exception as e:                       # unknown pattern/params
        raise PlanPartitionError(
            f"spmv '{node.name}': unusable CSR pattern meta "
            f"({pattern!r}): {e}") from e
    cum = [0]
    for c in counts:
        cum.append(cum[-1] + int(c))
    if cum[-1] != nnz:
        raise PlanPartitionError(
            f"spmv '{node.name}': pattern meta predicts {cum[-1]} entries "
            f"but '{indices}' holds {nnz}")
    rows_loc = rows // n_shards
    starts = tuple(cum[k * rows_loc] for k in range(n_shards + 1))
    widest = max(b - a for a, b in zip(starts, starts[1:]))
    pad = max(8, -(-widest // 8) * 8)
    slices = tuple(
        ResidentSlice(tensors=(indptr, indices, data), rows=rows_loc,
                      total_rows=rows, entries=starts[k + 1] - starts[k],
                      total_entries=nnz, row0=k * rows_loc,
                      entry0=starts[k])
        for k in range(n_shards))
    return CsrShardLayout(indptr=indptr, indices=indices, data=data,
                          rows=rows, nnz=nnz, entry_starts=starts,
                          pad_entries=pad, slices=slices)


def partition_plan(exec_plan: ExecPlan, mesh_axes, *,
                   program) -> ShardedExecPlan:
    """Split a co-designed :class:`ExecPlan` into contiguous row blocks.

    ``mesh_axes`` is either the shard count ``K`` or an ``(axis, K)``
    pair naming the 1-D mesh axis.  ``program`` is the frontend
    expression :class:`~repro.frontends.expr.Program` the plan was
    lowered from — partitioning needs its op/shape/CSR-meta view.

    Raises :class:`PlanPartitionError` for anything the row-block story
    cannot express: ragged or mixed row counts, einsums other than
    ``ab,b->a`` / ``a,a->``, irregular gathers/scans, or CSR operands
    without consistent deterministic pattern meta."""
    axis, n_shards = (("shards", mesh_axes) if isinstance(mesh_axes, int)
                      else (mesh_axes[0], int(mesh_axes[1])))
    if n_shards < 1:
        raise PlanPartitionError(f"shard count must be >= 1, got {n_shards}")
    if program is None:
        raise PlanPartitionError(
            "partitioning needs the frontend expression program "
            "(plan was lowered without one)")

    rows: Optional[int] = None

    def claim_rows(n: int, what: str) -> None:
        nonlocal rows
        if rows is None:
            rows = n
        elif rows != n:
            raise PlanPartitionError(
                f"{what}: leading dim {n} != plan row dim {rows}; "
                f"mixed streamed lengths cannot share one row split")

    csr: Dict[str, CsrShardLayout] = {}
    gathered: List[str] = []
    halo: List[str] = []
    reduced: List[str] = []

    def gather_whole(name: str) -> None:
        shape = program.nodes[name].shape
        if shape and shape[0] == rows and name not in gathered:
            gathered.append(name)

    for unit in exec_plan.units:
        if unit.kind == "stream":
            claim_rows(unit.sp.rows, f"pass {'+'.join(unit.sp.ops)}")
        for o in unit.ops:
            nd = program.nodes[o]
            if nd.irregular or nd.op in ("gather", "scan"):
                raise PlanPartitionError(
                    f"op '{o}' ({nd.op}) is data-dependent; "
                    f"irregular addressing has no contiguous row split")
            if nd.shape != ():
                claim_rows(nd.shape[0], f"op '{o}'")
            if nd.op == "spmv":
                data = nd.inputs[2]
                if data not in csr:
                    csr[data] = _csr_layout(program, nd, n_shards)
                gather_whole(nd.inputs[3])
            elif nd.op in ("matmul", "einsum") and nd.shape != ():
                spec = nd.param("spec")
                if spec != "ab,b->a":
                    raise PlanPartitionError(
                        f"op '{o}': einsum {spec!r} has no row-block "
                        f"split (only 'ab,b->a' contractions and "
                        f"'a,a->' reductions shard)")
                gather_whole(nd.inputs[1])
            elif (nd.op in ("dot", "norm")
                  or (nd.op in ("matmul", "einsum") and nd.shape == ())):
                # rank-0 reductions over streamed vectors: per-shard
                # partials combine with psum (scalar ew epilogues
                # recompute replicated from those, no exchange)
                if o not in reduced:
                    reduced.append(o)
            elif nd.op == "stencil2d":
                halo.append(o)

    if rows is None:
        raise PlanPartitionError("plan has no streamed rows to shard")
    if rows % n_shards:
        raise PlanPartitionError(
            f"{rows} rows do not split evenly over {n_shards} shards")

    rows_loc = rows // n_shards
    csr_members = {m for lay in csr.values()
                   for m in (lay.indptr, lay.indices, lay.data)}
    sharded = tuple(
        n for n, nd in program.nodes.items()
        if nd.shape and nd.shape[0] == rows and n not in csr_members)

    # spmv passes run inline per shard: a shard's CSR entry window is
    # dynamic (indexed by the mesh position), so no static per-tile
    # layout exists for it
    local_units = tuple(
        dataclasses.replace(u, kind="jnp", sp=None) if u.kind == "stream"
        and u.sp.spmv else
        dataclasses.replace(u, sp=_localize_pass(u.sp, n_shards))
        if u.kind == "stream" else u
        for u in exec_plan.units)
    local = dataclasses.replace(exec_plan, units=local_units)

    shards = tuple(
        ResidentSlice(tensors=(), rows=rows_loc, total_rows=rows,
                      entries=0, total_entries=0, row0=k * rows_loc)
        for k in range(n_shards))
    return ShardedExecPlan(
        base=exec_plan, local=local, n_shards=n_shards, axis=axis,
        rows=rows, shards=shards, csr=tuple(csr.values()),
        sharded=sharded, gathered=tuple(gathered), halo=tuple(halo),
        reduced=tuple(reduced))
