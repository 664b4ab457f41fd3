"""Frozen stage artifacts for the staged `cello` compilation pipeline.

Each :class:`~repro.api.session.Session` stage returns one of these:

    Session.trace()    -> TracedGraph
    TracedGraph.analyze()   -> AnalyzedGraph
    AnalyzedGraph.codesign()-> CoDesigned
    CoDesigned.lower()      -> CompiledPlan

Artifacts are frozen dataclasses with compact reprs; each keeps a reference
to its session so the stages chain, but all the decision state is in the
artifact itself (inspect, cache, or compare them freely).
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any, Dict, Optional, Tuple

from ..configs.base import ArchConfig
from ..core.graph import OpGraph
from ..core.lowering import ExecPlan, GroupKernel, ShardedExecPlan
from ..core.policy import CelloPlan
from ..core.reuse import ReuseAnalysis
from ..core.schedule import CoDesignResult, EvaluatedSchedule
from .config import UNSET as _UNSET

if TYPE_CHECKING:                                      # pragma: no cover
    from ..frontends.expr import Program
    from .session import Session


@dataclasses.dataclass(frozen=True)
class TracedGraph:
    """Stage 1: the analysis-level op DAG for one (arch, phase, shape).

    ``Session.trace`` memoizes these per shape, so the carried ``graph``
    is shared between repeat calls — treat it as read-only; to experiment
    with graph edits, build your own via ``OpGraph.build()``.

    Frontend-built traces (``trace(workload=...)`` / ``Session.from_graph``)
    use ``phase="hpc"`` and carry the source expression ``program`` so the
    lowered plan can be executed and validated numerically.
    """
    arch: str
    phase: str                # "train" | "prefill" | "decode" | "hpc"
    batch: int
    seq: Optional[int]                # train/prefill
    kv_len: Optional[int]             # decode
    layer_kind: Optional[str]
    graph: OpGraph = dataclasses.field(repr=False, compare=False)
    session: "Session" = dataclasses.field(repr=False, compare=False)
    # frontend (HPC) traces only
    program: Optional["Program"] = dataclasses.field(
        default=None, repr=False, compare=False)
    workload: Optional[str] = None
    wl_params: Tuple[Tuple[str, Any], ...] = ()

    @property
    def shape_key(self) -> str:
        if self.phase == "hpc":
            return ("-".join(f"{k}{v}" for k, v in self.wl_params)
                    or self.graph.name)
        span = f"s{self.seq}" if self.phase != "decode" else f"kv{self.kv_len}"
        return f"b{self.batch}{span}"

    def analyze(self) -> "AnalyzedGraph":
        return self.session.analyze(self)

    def codesign(self, config=None, **kwargs) -> "CoDesigned":
        """Convenience: codesign straight from the trace.  The reuse
        analysis is computed only if the search actually runs, so a disk
        cache hit skips it entirely."""
        return self.session.codesign(self, config, **kwargs)

    def __repr__(self) -> str:
        return (f"TracedGraph({self.arch!r}, phase={self.phase!r}, "
                f"{self.shape_key}, {len(self.graph.ops)} ops, "
                f"{self.graph.total_flops:.3e} FLOPs)")


@dataclasses.dataclass(frozen=True)
class AnalyzedGraph:
    """Stage 2: reuse distances/frequencies over the natural schedule."""
    trace: TracedGraph
    analysis: ReuseAnalysis = dataclasses.field(repr=False, compare=False)

    @property
    def session(self) -> "Session":
        return self.trace.session

    def reuse_of(self, tensor: str):
        return self.analysis.tensors[tensor]

    def pin_candidates(self):
        return self.analysis.ranked_pin_candidates()

    def codesign(self, config=None, **kwargs) -> "CoDesigned":
        return self.session.codesign(self, config, **kwargs)

    def __repr__(self) -> str:
        multi = sum(1 for t in self.analysis.tensors.values()
                    if t.frequency > 1)
        return (f"AnalyzedGraph({self.trace.arch!r}, "
                f"phase={self.trace.phase!r}, "
                f"{len(self.analysis.tensors)} tensors, "
                f"{multi} with reuse)")


@dataclasses.dataclass(frozen=True)
class CoDesigned:
    """Stage 3: the joint schedule × buffer decision (plus baselines)."""
    trace: TracedGraph
    result: CoDesignResult = dataclasses.field(repr=False, compare=False)
    strategy: str = "default"
    capacity_bytes: int = 0
    from_cache: bool = False

    @property
    def session(self) -> "Session":
        return self.trace.session

    # -- passthroughs to the underlying result -------------------------
    @property
    def best(self) -> EvaluatedSchedule:
        return self.result.best

    @property
    def baselines(self) -> Dict[str, EvaluatedSchedule]:
        return self.result.baselines

    @property
    def split_sweep(self):
        return self.result.split_sweep

    def speedup(self, baseline: str = "seq-implicit") -> float:
        return self.result.speedup(baseline)

    def energy_ratio(self, baseline: str = "seq-implicit") -> float:
        return self.result.energy_ratio(baseline)

    def lower(self, config=None, *, seq: Optional[int] = None,
              backend: Optional[str] = None,
              mesh=None) -> "CompiledPlan":
        return self.session.lower(self, config, seq=seq, backend=backend,
                                  mesh=mesh)

    def __repr__(self) -> str:
        s = self.best.schedule
        return (f"CoDesigned({self.trace.arch!r}, phase={self.trace.phase!r}, "
                f"split={s.config.explicit_frac:.3f}, "
                f"{len(s.groups)} groups, {len(s.pins)} pins, "
                f"speedup={self.speedup():.2f}x"
                f"{', cached' if self.from_cache else ''})")


@dataclasses.dataclass(frozen=True)
class CompiledPlan:
    """Stage 4: the lowered execution plan, ready to serve or train.

    ``.serve()`` / ``.train()`` drive the JAX execution stack with this
    plan; ``.report()`` returns the headline co-design numbers and
    ``.explain()`` a human-readable schedule/pin/split summary.

    Frontend (HPC) plans carry ``cfg=None``: they execute through
    :meth:`run`, which hands the plan to a registered execution backend
    (``repro.exec``) — ``reference`` replays the scheduled op order through
    the jax.numpy interpreter, ``pallas`` compiles each fusion group into
    tile-streaming kernels.  No LLM serving stack applies.
    """
    cfg: Optional[ArchConfig] = dataclasses.field(repr=False)
    plan: CelloPlan = dataclasses.field(repr=False)
    trace: Optional[TracedGraph] = dataclasses.field(
        default=None, repr=False, compare=False)
    codesigned: Optional[CoDesigned] = dataclasses.field(
        default=None, repr=False, compare=False)
    # execution-backend selection (frontend plans): the default backend
    # `.run()` uses, and the kernel shape chosen for every fusion group
    # (`core.lowering.select_group_kernels`)
    backend: str = "reference"
    group_kernels: Tuple[GroupKernel, ...] = dataclasses.field(
        default=(), repr=False, compare=False)
    # execution-level plan (frontend plans): fused dispatch units,
    # cross-pass residency spans, rolled iteration segment
    # (`core.lowering.plan_execution`)
    exec_plan: Optional[ExecPlan] = dataclasses.field(
        default=None, repr=False, compare=False)
    # mesh partitioning (frontend plans lowered with mesh=): row blocks,
    # CSR entry windows, gather/psum/halo exchange sets
    # (`core.lowering.partition_plan`); None for single-device plans
    sharded: Optional[ShardedExecPlan] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def arch(self) -> str:
        return self.cfg.name if self.cfg is not None else self.plan.arch

    # -- execution ------------------------------------------------------
    def serve(self, *, unroll: bool = False):
        """Serving bundle (prefill/decode fns + greedy generate driver)."""
        if self.cfg is None:
            raise ValueError("frontend (HPC) plans have no LLM serving "
                             "stack; execute them with plan.run()")
        from ..launch.serve import make_serving      # lazy: pulls in jax
        return make_serving(self.cfg, self.plan, unroll=unroll)

    def train(self, *, data_iter, n_steps: int, opt_cfg=None, **kwargs
              ) -> Dict[str, Any]:
        """Run the CPU-scale training loop under this plan's remat policy."""
        if self.cfg is None:
            raise ValueError("frontend (HPC) plans have no LLM training "
                             "stack; execute them with plan.run()")
        from ..launch.train import train_loop        # lazy: pulls in jax
        from ..optim import AdamWConfig
        if opt_cfg is None:
            opt_cfg = AdamWConfig(total_steps=n_steps)
        return train_loop(self.cfg, self.plan, opt_cfg,
                          data_iter=data_iter, n_steps=n_steps, **kwargs)

    def run(self, feeds=None, *, seed: int = 0,
            backend: Optional[str] = None,
            config=None) -> Dict[str, Any]:
        """Execute a frontend plan through an execution backend.

        ``backend`` (or ``config=ExecConfig(backend=...)``) overrides the
        plan's default (picked at ``lower()``): ``"reference"`` replays
        the co-designed schedule order through the jax.numpy
        interpreter — ops are pure, so this matches natural-order
        evaluation bit-for-bit; ``"pallas"`` runs each fusion group as
        tile-streaming kernels, matching reference within the tolerances
        documented in ``docs/execution_backends.md``.  Plans lowered with
        ``mesh=`` execute sharded on either backend
        (``docs/distributed.md``).
        """
        if config is not None:
            if backend is not None:
                raise TypeError("run(): pass either config= or backend=, "
                                "not both")
            if config.mesh is not None:
                raise ValueError("the mesh is fixed when the plan is "
                                 "lowered; re-lower with "
                                 "Session.lower(..., mesh=...)")
            backend = config.backend
            config.apply_toggles()
        if self.trace is None or self.trace.program is None:
            raise ValueError("run() needs a frontend-traced plan "
                             "(Session.trace(workload=...) or "
                             "Session.from_graph(program))")
        from ..exec import get_backend                   # lazy: pulls in jax
        return get_backend(backend or self.backend).run(
            self, feeds=feeds, seed=seed)

    def batched(self, config=None, *, backend: Optional[str] = None,
                donate=_UNSET):
        """Wrap this frontend plan for batched serving: one vmapped
        dispatch answers a whole batch of requests (operator leaves
        shared, input leaves batched) — see ``repro.serve.BatchedPlan``.

        ``donate=`` is deprecated since 0.10: pass
        ``config=ExecConfig(donate=...)`` (``docs/api_migration.md``).
        """
        donate_val: Optional[bool] = None
        if donate is not _UNSET:
            if config is not None:
                raise TypeError("batched(): pass either config= or "
                                "donate=, not both")
            import warnings
            warnings.warn(
                "batched(donate=...) is deprecated since 0.10 and will "
                "be removed in 0.11; pass config=ExecConfig(donate=...) "
                "instead (see docs/api_migration.md)",
                DeprecationWarning, stacklevel=2)
            donate_val = donate
        if config is not None:
            if backend is not None:
                raise TypeError("batched(): pass either config= or "
                                "backend=, not both")
            if config.mesh is not None:
                raise ValueError("the mesh is fixed when the plan is "
                                 "lowered; re-lower with "
                                 "Session.lower(..., mesh=...)")
            backend = config.backend
            donate_val = config.donate
        if self.trace is None or self.trace.program is None:
            raise ValueError("batched() needs a frontend-traced plan "
                             "(Session.trace(workload=...) or "
                             "Session.from_graph(program))")
        from ..serve import BatchedPlan                  # lazy: pulls in jax
        return BatchedPlan(self, backend=backend, donate=donate_val)

    # -- introspection --------------------------------------------------
    def device_scopes(self, dtype: str = "float32", *,
                      backend: Optional[str] = None) -> Dict[str, str]:
        """``{HLO instruction name: scope}`` for the device work this
        plan's executable runs outside its kernels: the spmv gather
        (``spmv_gather``) and the CSR per-tile layout (``csr_layout``).
        A device trace names each op by its HLO instruction alone
        (``fusion.6``); this maps those names back.  Compiled for every
        leaf at its traced shape, ``dtype`` values and int32 CSR indices.
        Empty for a backend that names no such scopes."""
        if self.trace is None or self.trace.program is None:
            raise ValueError("device_scopes() needs a frontend-traced plan")
        from ..exec import get_backend                   # lazy: pulls in jax
        fn = get_backend(backend or self.backend).compiled(self)
        scopes = getattr(fn, "device_scopes", None)
        return {} if scopes is None else scopes(dtype)

    def report(self) -> Dict[str, Any]:
        """Headline co-design metrics (empty-ish for default plans)."""
        out: Dict[str, Any] = {
            "arch": self.arch,
            "plan": dataclasses.asdict(self.plan),
        }
        if self.trace is not None:
            out["phase"] = self.trace.phase
            out["shape"] = self.trace.shape_key
        if self.cfg is None:
            out["backend"] = self.backend
            out["group_kernel_kinds"] = [gk.kind
                                         for gk in self.group_kernels]
            if self.exec_plan is not None:
                ep = self.exec_plan
                out["exec_units"] = len(ep.units)
                out["exec_fused_from"] = ep.n_prefuse
                out["rolled_iters"] = (ep.roll.n_iters
                                       if ep.roll is not None else 0)
            if self.sharded is not None:
                out["mesh"] = {"axis": self.sharded.axis,
                               "n_shards": self.sharded.n_shards,
                               "rows_per_shard":
                                   self.sharded.rows_per_shard,
                               "plan": self.sharded.describe()}
        cd = self.codesigned
        if cd is not None:
            m = cd.best.metrics
            out.update({
                "strategy": cd.strategy,
                "capacity_bytes": cd.capacity_bytes,
                "overbook": getattr(cd.result, "overbook", 0.0),
                "explicit_frac": cd.best.schedule.config.explicit_frac,
                "time_s": m.time_s,
                "energy_j": m.energy_j,
                "hbm_bytes": m.hbm_bytes,
                "arithmetic_intensity": m.ai,
                "speedup_vs_implicit": cd.speedup(),
                "energy_ratio_vs_implicit": cd.energy_ratio(),
                "baselines": {
                    name: {"time_s": ev.metrics.time_s,
                           "energy_j": ev.metrics.energy_j,
                           "hbm_bytes": ev.metrics.hbm_bytes}
                    for name, ev in cd.baselines.items()},
                "from_cache": cd.from_cache,
            })
        from .. import obs
        out["obs"] = obs.snapshot()
        return out

    def explain(self) -> str:
        """Human-readable schedule / pin / split / kernel summary."""
        p = self.plan
        lines = [f"CompiledPlan for {self.arch}"]
        if self.trace is not None:
            lines.append(f"  traced phase      : {self.trace.phase} "
                         f"({self.trace.shape_key})")
        cd = self.codesigned
        if cd is not None:
            s = cd.best.schedule
            cap = cd.capacity_bytes
            lines += [
                f"  search strategy   : {cd.strategy}"
                + (" [cache hit]" if cd.from_cache else ""),
                f"  buffer split      : {s.config.explicit_frac:.3f} explicit"
                f" ({s.config.explicit_bytes // 1024 // 1024} MiB of"
                f" {cap // 1024 // 1024} MiB)",
                f"  fusion groups     : "
                + (", ".join("{" + "+".join(g) + "}"
                             for g in s.groups if len(g) > 1) or "(none)"),
                f"  explicit pins     : "
                + (", ".join(f"{t}[g{a}..g{b}]"
                             for t, (a, b) in sorted(s.pins.items()))
                   or "(none)"),
                f"  speedup           : {cd.speedup():.3f}x vs implicit-only,"
                f" energy {cd.energy_ratio():.3f}x better",
                f"  HBM traffic       : "
                f"{cd.best.metrics.hbm_bytes / 1e6:,.1f} MB "
                f"(AI {cd.best.metrics.ai:,.1f} FLOP/B)",
            ]
            ob = getattr(cd.result, "overbook", 0.0)
            if ob:
                lines.append(f"  pin overbook      : {ob:.3f} of the "
                             "explicit region (prefix pins allowed)")
            if self.trace is not None:
                from ..core.schedule import sparse_operand_groups
                partial = dict(getattr(s.pins, "partial", None) or {})
                terms = []
                for grp in sparse_operand_groups(self.trace.graph):
                    base = grp[0].rsplit(".", 1)[0]
                    pp = next((partial[m] for m in grp if m in partial),
                              None)
                    if pp is not None:
                        terms.append(
                            f"{base} pinned=prefix(rows={pp.rows}/"
                            f"{pp.total_rows}, frac={pp.frac:.2f})")
                    elif all(m in s.pins for m in grp):
                        terms.append(f"{base} pinned=full")
                    else:
                        terms.append(f"{base} pinned=streamed")
                if terms:
                    lines.append("  sparse operands   : "
                                 + ", ".join(terms))
        else:
            lines.append("  (default plan — no search was run)")
        if self.cfg is None:
            g = self.trace.graph if self.trace is not None else None
            lines.append(
                f"  execution backend : {self.backend}"
                + (f" over {len(g.ops)} ops" if g is not None else "")
                + " (run(backend=...) to override)")
            if self.group_kernels:
                lines.append("  group kernels     :")
                for i, gk in enumerate(self.group_kernels):
                    lines.append(f"    g{i} {{{'+'.join(gk.ops)}}}: "
                                 f"{gk.describe()}")
            if self.exec_plan is not None:
                lines.append(f"  execution plan    : "
                             f"{self.exec_plan.describe()}")
            if self.sharded is not None:
                lines.append(f"  device mesh       : "
                             f"{self.sharded.describe()}")
        else:
            lines += [
                f"  flash attention   : {p.use_flash_attention} "
                f"(q_block={p.q_block}, kv_block={p.kv_block})",
                f"  fused MLP         : {p.use_fused_mlp} "
                f"(m={p.mlp_block_m}, f={p.mlp_block_f})",
                f"  remat save-set    : {', '.join(p.remat_save_names)}",
            ]
        if p.notes:
            lines.append(f"  notes             : {p.notes}")
        return "\n".join(lines)

    def __repr__(self) -> str:
        tag = (f"phase={self.trace.phase!r}, " if self.trace else "")
        how = "codesigned" if self.codesigned else "default"
        return (f"CompiledPlan({self.arch!r}, {tag}{how}, "
                f"flash={self.plan.use_flash_attention}, "
                f"fused_mlp={self.plan.use_fused_mlp})")
