"""Execution-backend tests: registry, kernel selection, and parity.

Parity policy (see docs/execution_backends.md):

* ``reference`` replays the co-designed schedule order through the same
  pure per-op rules as natural-order evaluation — it must match the
  natural-order oracle **bit-for-bit**.
* ``pallas`` tiles reductions (per-tile partials accumulated across the
  grid) and computes leaf-consuming contractions through XLA rather than
  NumPy's BLAS, so it matches within reduction-reassociation tolerances:
  rtol=2e-4 / atol=1e-5 for float32.  Everything elementwise and every
  per-row matvec lane uses the reference rules verbatim.
"""
import time

import numpy as np
import pytest

from repro.api import Session, get_backend, list_backends, register_backend
from repro.core import build_groups, select_group_kernels
from repro.core.lowering import (_pick_tile_rows, detect_rolled_loop,
                                 flatten_units, fuse_units)
from repro.exec import (EXECUTOR_REGISTRY, Executor, ReferenceExecutor,
                        evaluate, plan_order)
from repro.frontends import Program, build_workload, make_feeds

# float32 reduction-reassociation tolerances (documented policy)
RTOL, ATOL = 2e-4, 1e-5

#: every workload in the HPC registry, sized small enough for interpret-mode
#: CI but large enough that streaming passes run multiple row tiles
PARITY_SET = [
    ("cg", dict(n=96, iters=3)),
    ("bicgstab", dict(n=96, iters=2)),
    ("gmres", dict(n=96, restart=3)),
    ("jacobi2d", dict(n=32, sweeps=3)),
    ("power_iteration", dict(n=96, iters=3)),
    ("mttkrp", dict(i=24, j=24, k=24, rank=8)),
]


def _llm_ffn_program(m=64, d=32, f=48) -> Program:
    """One LLM FFN phase (gated MLP over a token block) on the expression
    frontend: the token dimension streams, the weight matrices are the
    resident operands — the same shape class `core.policy` fuses for the
    arch-registry plans."""
    p = Program("llm_ffn_prefill")
    x = p.input("x", (m, d))
    w_up = p.operator("w_up", (d, f))
    w_gate = p.operator("w_gate", (d, f))
    w_down = p.operator("w_down", (f, d))
    h = p.matmul(x, w_up, name="up")
    g = p.matmul(x, w_gate, name="gate")
    a = p.mul(h, g, name="act")
    p.output(p.matmul(a, w_down, name="ffn_out"))
    return p


def _lowered(tmp_path, workload=None, program=None, **params):
    if workload is not None:
        traced = Session(cache_dir=tmp_path).trace(workload=workload,
                                                   **params)
    else:
        traced = Session.from_graph(program, cache_dir=tmp_path)
    return traced, traced.analyze().codesign().lower()


# ---------------------------------------------------------------------------
# backend parity: HPC registry + one LLM phase under both backends
# ---------------------------------------------------------------------------

class TestBackendParity:
    @pytest.mark.parametrize("workload,params",
                             PARITY_SET, ids=[w for w, _ in PARITY_SET])
    def test_hpc_workload_parity(self, workload, params, tmp_path):
        traced, plan = _lowered(tmp_path, workload=workload, **params)
        feeds = make_feeds(traced.program, seed=7)
        want = evaluate(traced.program, feeds)

        ref = plan.run(feeds, backend="reference")
        assert sorted(ref) == sorted(want)
        for k in want:                    # same pure ops => bitwise
            np.testing.assert_array_equal(np.asarray(ref[k]),
                                          np.asarray(want[k]), err_msg=k)

        pal = plan.run(feeds, backend="pallas")
        assert sorted(pal) == sorted(want)
        for k in want:
            np.testing.assert_allclose(np.asarray(pal[k]),
                                       np.asarray(want[k]),
                                       rtol=RTOL, atol=ATOL, err_msg=k)

    def test_llm_ffn_phase_parity(self, tmp_path):
        prog = _llm_ffn_program()
        traced, plan = _lowered(tmp_path, program=prog)
        feeds = make_feeds(prog, seed=5)
        want = evaluate(prog, feeds)
        ref = plan.run(feeds, backend="reference")
        pal = plan.run(feeds, backend="pallas")
        np.testing.assert_array_equal(np.asarray(ref["ffn_out"]),
                                      np.asarray(want["ffn_out"]))
        np.testing.assert_allclose(np.asarray(pal["ffn_out"]),
                                   np.asarray(want["ffn_out"]),
                                   rtol=RTOL, atol=ATOL)
        # weights are resident operands of the streaming passes
        res = {t for gk in plan.group_kernels for p in gk.passes
               for t in p.resident}
        assert res & {"w_up", "w_gate", "w_down"}

    def test_awkward_row_count_still_streams(self, tmp_path):
        # rows=50: only tile divisors 2 and 1 exist — the streamer must
        # still produce correct results at the finest granularity
        p = Program("odd_rows")
        A = p.operator("A", (50, 50), init="spd")
        x = p.input("x", (50,))
        y = p.matmul(A, x, name="y")
        p.output(p.dot(y, y, name="yy"))
        traced, plan = _lowered(tmp_path, program=p)
        feeds = make_feeds(p, seed=2)
        want = evaluate(p, feeds)
        got = plan.run(feeds, backend="pallas")
        np.testing.assert_allclose(np.asarray(got["yy"]),
                                   np.asarray(want["yy"]),
                                   rtol=RTOL, atol=ATOL)

    def test_pallas_runs_codesigned_group_order(self, tmp_path):
        # the scheduled order a backend must honor differs from build
        # order whenever the search reorders; assert the contract on the
        # plan the backends actually execute
        traced, plan = _lowered(tmp_path, workload="cg", n=96, iters=3)
        order = plan_order(plan)
        natural = [n for n in traced.program._order
                   if not traced.program.nodes[n].is_leaf]
        assert sorted(order) == sorted(natural)
        groups = [list(g) for g in plan.codesigned.best.schedule.groups]
        assert order == [o for g in groups for o in g]


# ---------------------------------------------------------------------------
# fp64 validation path (make_feeds dtype satellite)
# ---------------------------------------------------------------------------

class TestFeedsDtype:
    def test_make_feeds_dtype(self):
        prog = build_workload("cg", n=16, iters=1)
        f32 = make_feeds(prog, seed=0)
        f64 = make_feeds(prog, seed=0, dtype=np.float64)
        assert all(v.dtype == np.float32 for v in f32.values())
        assert all(v.dtype == np.float64 for v in f64.values())
        # same generator stream, cast at the end: identical values
        for k in f32:
            np.testing.assert_allclose(f32[k], f64[k].astype(np.float32),
                                       rtol=0, atol=0)

    def test_index_leaves_stay_int32(self):
        p = Program("g")
        x = p.input("x", (8, 4))
        idx = p.input("idx", (3,), init="indices")
        p.output(p.gather(x, idx, name="out"))
        feeds = make_feeds(p, seed=0, dtype=np.float64)
        assert feeds["idx"].dtype == np.int32
        assert feeds["x"].dtype == np.float64

    def test_non_float_dtype_rejected(self):
        prog = build_workload("cg", n=16, iters=1)
        with pytest.raises(ValueError, match="float dtype"):
            make_feeds(prog, dtype=np.int32)

    def test_fp64_evaluation_under_x64(self, tmp_path):
        import jax
        prog = build_workload("cg", n=32, iters=2)
        feeds = make_feeds(prog, seed=1, dtype=np.float64)
        with jax.enable_x64(True):
            out = evaluate(prog, feeds)
            assert np.asarray(out["x2"]).dtype == np.float64
            # fp64 CG at n=32 is essentially exact: residual identity holds
            # far beyond fp32 precision
            A, b = feeds["A"], feeds["b"]
            r = np.asarray(out["r2"], np.float64)
            x = np.asarray(out["x2"], np.float64)
            np.testing.assert_allclose(r, b - A @ x, rtol=1e-12, atol=1e-12)


# ---------------------------------------------------------------------------
# registry + plan threading
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_builtins_registered(self):
        assert {"reference", "pallas"} <= set(list_backends())
        assert get_backend("reference").name == "reference"
        assert get_backend("pallas").name == "pallas"

    def test_unknown_backend_raises(self, tmp_path):
        with pytest.raises(KeyError, match="unknown execution backend"):
            get_backend("tpu-real")
        _, plan = _lowered(tmp_path, workload="cg", n=16, iters=1)
        with pytest.raises(KeyError, match="unknown execution backend"):
            plan.run(backend="tpu-real")

    def test_lower_backend_sets_default(self, tmp_path):
        traced = Session(cache_dir=tmp_path).trace(workload="power_iteration",
                                                   n=32, iters=2)
        designed = traced.analyze().codesign()
        plan = designed.lower(backend="pallas")
        assert plan.backend == "pallas"
        assert "execution backend : pallas" in plan.explain()
        feeds = make_feeds(traced.program, seed=0)
        got = plan.run(feeds)                 # defaults to pallas
        want = evaluate(traced.program, feeds)
        np.testing.assert_allclose(np.asarray(got["x2"]),
                                   np.asarray(want["x2"]),
                                   rtol=RTOL, atol=ATOL)

    def test_custom_backend_registers_and_runs(self, tmp_path):
        class ShoutingReference(ReferenceExecutor):
            name = "shouting-reference"

        register_backend(ShoutingReference)
        try:
            _, plan = _lowered(tmp_path, workload="cg", n=16, iters=1)
            got = plan.run(seed=4, backend="shouting-reference")
            want = plan.run(seed=4, backend="reference")
            for k in want:
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(want[k]))
        finally:
            EXECUTOR_REGISTRY.pop("shouting-reference", None)

    def test_executor_is_abstract(self, tmp_path):
        _, plan = _lowered(tmp_path, workload="cg", n=16, iters=1)
        with pytest.raises(NotImplementedError):
            Executor().run(plan)

    def test_run_missing_feed_raises(self, tmp_path):
        traced, plan = _lowered(tmp_path, workload="cg", n=16, iters=1)
        feeds = make_feeds(traced.program, seed=0)
        feeds.pop("b")
        for backend in ("reference", "pallas"):
            with pytest.raises(KeyError, match="feeds missing leaf"):
                plan.run(feeds, backend=backend)


# ---------------------------------------------------------------------------
# group -> kernel-shape selection
# ---------------------------------------------------------------------------

class TestKernelSelection:
    def _kernels(self, workload, **params):
        prog = build_workload(workload, **params)
        graph = prog.to_graph()
        groups = build_groups(graph, graph.topo_order(), 64 << 20)
        return select_group_kernels(graph, groups, 64 << 20)

    def test_kernels_partition_the_groups(self, tmp_path):
        _, plan = _lowered(tmp_path, workload="cg", n=96, iters=2)
        groups = [tuple(g) for g in plan.codesigned.best.schedule.groups]
        assert [gk.ops for gk in plan.group_kernels] == groups
        for gk in plan.group_kernels:
            if gk.kind == "stream":
                flat = [o for p in gk.passes for o in p.ops]
                assert flat == list(gk.ops)       # passes partition group
                for p in gk.passes:
                    assert p.rows % p.tile_rows == 0

    def test_cg_in_pass_rhs_splits_into_two_passes(self):
        kernels = self._kernels("cg", n=128, iters=2)
        multi = [gk for gk in kernels
                 if gk.kind == "stream" and len(gk.passes) == 2]
        # p_{k+1} = axpy(...) immediately feeds A @ p_{k+1}: the vector
        # must materialize before it can sit resident for the matvec
        assert multi, [gk.describe() for gk in kernels]
        gk = multi[0]
        assert gk.passes[1].resident    # second pass holds the new vector

    def test_in_pass_scalar_consumer_splits_and_executes(self):
        # schedule.fusable never fuses a tiled op with the in-pass scalar
        # it reads, but select_group_kernels is public API and must stay
        # safe for hand-built groups: the pass splits where the scalar
        # must materialize, and the resulting kernels execute correctly
        import jax.numpy as jnp

        from repro.exec.pallas import _StreamCall
        p = Program("scal")
        x = p.input("x", (16,))
        y = p.input("y", (16,))
        d = p.dot(x, y, name="d")
        p.output(p.axpy(d, x, y, name="z"))
        graph = p.to_graph()
        kernels = select_group_kernels(graph, [["d", "z"]], 1 << 20)
        assert kernels[0].kind == "stream"
        assert [pss.ops for pss in kernels[0].passes] == [("d",), ("z",)]
        feeds = make_feeds(p, seed=0)
        env = {k: jnp.asarray(v) for k, v in feeds.items()}
        for sp in kernels[0].passes:
            env.update(_StreamCall(p, sp, needed={"d", "z"})(env))
        want = evaluate(p, feeds)
        np.testing.assert_allclose(np.asarray(env["z"]),
                                   np.asarray(want["z"]),
                                   rtol=RTOL, atol=ATOL)

    def test_jacobi_is_block_kernel(self):
        kernels = self._kernels("jacobi2d", n=32, sweeps=3)
        assert all(gk.kind == "block" for gk in kernels)

    def test_mttkrp_falls_back_with_reason(self):
        kernels = self._kernels("mttkrp", i=16, j=16, k=16, rank=4)
        assert all(gk.kind == "jnp" for gk in kernels)
        assert any("einsum" in gk.reason for gk in kernels)

    def test_gather_falls_back_irregular(self):
        p = Program("gath")
        x = p.input("x", (32, 8))
        idx = p.input("idx", (8,), init="indices")
        p.output(p.gather(x, idx, name="g"))
        graph = p.to_graph()
        kernels = select_group_kernels(
            graph, build_groups(graph, graph.topo_order(), 1 << 20), 1 << 20)
        assert kernels[0].kind == "jnp"
        assert "irregular" in kernels[0].reason

    def test_irregular_parity_through_fallback(self, tmp_path):
        p = Program("gath2")
        x = p.input("x", (32, 8))
        idx = p.input("idx", (8,), init="indices")
        g = p.gather(x, idx, name="g")
        p.output(p.mul(g, g, name="sq"))
        traced, plan = _lowered(tmp_path, program=p)
        feeds = make_feeds(p, seed=9)
        want = evaluate(p, feeds)
        got = plan.run(feeds, backend="pallas")
        np.testing.assert_array_equal(np.asarray(got["sq"]),
                                      np.asarray(want["sq"]))


# ---------------------------------------------------------------------------
# single-program executable: one dispatch, rolled loops, residency fusion
# ---------------------------------------------------------------------------

class TestSingleProgram:
    def test_exactly_one_dispatch_per_run(self, tmp_path):
        traced, plan = _lowered(tmp_path, workload="cg", n=96, iters=3)
        feeds = make_feeds(traced.program, seed=0)
        ex = get_backend("pallas").compile(plan)
        assert ex.stats == {"traces": 0, "dispatches": 0}
        for runs in (1, 2, 3):
            out = ex(feeds)
            assert ex.stats["dispatches"] == runs
        # one jit trace serves every same-dtype run: had any unit
        # dispatched on its own, re-running would re-enter Python
        assert ex.stats["traces"] == 1
        want = evaluate(traced.program, feeds)
        np.testing.assert_allclose(np.asarray(out["x3"]),
                                   np.asarray(want["x3"]),
                                   rtol=RTOL, atol=ATOL)

    def test_run_driver_uses_single_program(self, tmp_path):
        # CompiledPlan.run memoizes the compiled executable per plan: two
        # run() calls must share one executable and re-dispatch it
        traced, plan = _lowered(tmp_path, workload="power_iteration",
                                n=64, iters=3)
        feeds = make_feeds(traced.program, seed=1)
        plan.run(feeds, backend="pallas")
        plan.run(feeds, backend="pallas")
        backend = get_backend("pallas")
        entry = backend._compiled.get(id(plan))
        assert entry is not None
        ex = entry[1]
        assert ex.stats["dispatches"] == 2 and ex.stats["traces"] == 1

    @pytest.mark.parametrize("workload,params,rolls", [
        ("cg", dict(n=96, iters=4), True),
        ("bicgstab", dict(n=96, iters=4), True),   # phase-shifted x update
        ("jacobi2d", dict(n=32, sweeps=4), True),
        ("power_iteration", dict(n=96, iters=4), True),
        ("gmres", dict(n=96, restart=4), False),   # growing Arnoldi bodies
        ("mttkrp", dict(i=16, j=16, k=16, rank=4), False),  # no loop at all
    ], ids=lambda v: v if isinstance(v, str) else "")
    def test_rolled_loop_detection(self, workload, params, rolls, tmp_path):
        traced, plan = _lowered(tmp_path, workload=workload, **params)
        ep = plan.exec_plan
        assert ep is not None
        if rolls:
            assert ep.roll is not None and ep.roll.n_iters >= 2
        else:
            assert ep.roll is None
        # parity is preserved whichever path the executable takes
        feeds = make_feeds(traced.program, seed=5)
        want = evaluate(traced.program, feeds)
        got = plan.run(feeds, backend="pallas")
        for k in want:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]),
                                       rtol=RTOL, atol=ATOL, err_msg=k)

    def test_rolled_compile_time_is_iteration_free(self, tmp_path):
        # the acceptance bar: tracing cg at iters=64 must cost at most 2x
        # the iters=4 trace — the rolled body is traced once either way.
        # best-of-2 per side keeps a loaded CI runner's one-off stall from
        # flaking a ratio whose real value is ~1x
        sess = Session(cache_dir=tmp_path)

        def compile_time(iters):
            designed = sess.trace(workload="cg", n=64,
                                  iters=iters).codesign()
            plan = designed.lower(backend="pallas")
            feeds = make_feeds(designed.trace.program, seed=0)
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                ex = get_backend("pallas").compile(plan)
                ex(feeds)                          # first run = the trace
                best = min(best, time.perf_counter() - t0)
            return best, plan.exec_plan

        t4, ep4 = compile_time(4)
        t64, ep64 = compile_time(64)
        # structural guarantee first: the trace covers prologue + ONE
        # template body + epilogue, independent of the iteration count
        assert ep64.roll is not None and ep64.roll.n_iters >= 60
        traced_units = (ep64.roll.first + ep64.roll.per_iter
                        + len(ep64.units) - ep64.roll.stop)
        assert traced_units <= len(ep4.units)
        assert t64 <= 2.0 * t4, (t4, t64)

    def test_residency_fusion_shrinks_units(self, tmp_path):
        _, plan = _lowered(tmp_path, workload="cg", n=96, iters=3)
        ep = plan.exec_plan
        assert len(ep.units) < ep.n_prefuse
        # fused units absorb the scalar-glue groups: some unit carries ops
        # from more than one fusion group
        assert any(len(u.groups) > 1 for u in ep.units)
        assert "fused from" in ep.describe()

    def test_fusion_absorbs_eager_scalar_glue(self):
        # [tiled] + [scalar-only jnp] + [tiled-reading-that-scalar] must
        # fuse into ONE pass: the scalar's inputs are tile-invariant, so
        # it is recomputed per tile instead of forcing a pass break
        p = Program("glue")
        a = p.input("a", (16,))
        b = p.input("b", (16,))
        s = p.input("s", ())
        t1 = p.mul(a, b, name="t1")
        ns = p.neg(s, name="ns")
        p.output(p.axpy(ns, a, t1, name="t2"))
        graph = p.to_graph()
        kernels = select_group_kernels(graph, [["t1"], ["ns"], ["t2"]],
                                       1 << 20)
        units = fuse_units(graph, flatten_units(kernels), 1 << 20)
        assert len(units) == 1 and units[0].kind == "stream"
        assert units[0].ops == ("t1", "ns", "t2")
        # ...and a reduction-derived scalar still forces the break
        d = Program("late")
        x = d.input("x", (16,))
        y = d.input("y", (16,))
        dd = d.dot(x, y, name="dd")
        d.output(d.axpy(dd, x, y, name="z"))
        graph2 = d.to_graph()
        k2 = select_group_kernels(graph2, [["dd"], ["z"]], 1 << 20)
        u2 = fuse_units(graph2, flatten_units(k2), 1 << 20)
        assert len(u2) == 2

    def test_detect_rolled_loop_direct(self):
        # hand-built elementwise chain: per-op units, bodies recorded
        p = Program("chain")
        x = p.input("x0", (8,))
        c = p.input("c", (8,))
        for k in range(5):
            with p.iteration():
                x = p.mul(x, c, name=f"x{k + 1}")
        p.output(x)
        graph = p.to_graph()
        groups = [[f"x{k + 1}"] for k in range(5)]
        units = flatten_units(select_group_kernels(graph, groups, 1 << 20))
        roll = detect_rolled_loop(p, units)
        # iteration 0 reads the leaf x0, so it cannot match; 1..4 roll
        assert roll is not None
        assert (roll.first, roll.per_iter, roll.n_iters) == (1, 1, 4)
        [slot] = roll.slots
        assert (slot.read, slot.update, slot.final) == ("x1", "x2", "x5")
        # bodies that carry nothing / unrecorded bodies detect as None
        q = Program("noloop")
        a = q.input("a", (8,))
        q.output(q.mul(a, a, name="sq"))
        g2 = q.to_graph()
        u2 = flatten_units(select_group_kernels(g2, [["sq"]], 1 << 20))
        assert detect_rolled_loop(q, u2) is None

    def test_explain_and_report_surface_exec_plan(self, tmp_path):
        _, plan = _lowered(tmp_path, workload="cg", n=96, iters=4)
        text = plan.explain()
        assert "execution plan" in text and "rolled" in text
        rep = plan.report()
        assert rep["exec_units"] == len(plan.exec_plan.units)
        assert rep["exec_fused_from"] == plan.exec_plan.n_prefuse
        assert rep["rolled_iters"] == plan.exec_plan.roll.n_iters

    def test_donation_covers_all_leaves_and_spares_caller_buffers(
            self, tmp_path, monkeypatch):
        import repro.exec.pallas as pal
        traced, plan = _lowered(tmp_path, workload="cg", n=32, iters=2)
        ex = get_backend("pallas").compile(plan)
        # every leaf dies inside the program (outputs are op-produced)
        assert ex.donate_argnums == tuple(range(len(ex.leaf_names)))
        # donation stays off on CPU (XLA ignores it there and warns)
        monkeypatch.setattr(pal, "_BACKEND_PROBE", "cpu")
        monkeypatch.delenv("CELLO_PALLAS_DONATE", raising=False)
        assert pal.use_donation() is False
        monkeypatch.setattr(pal, "_BACKEND_PROBE", "tpu")
        assert pal.use_donation() is True
        monkeypatch.setenv("CELLO_PALLAS_DONATE", "0")
        assert pal.use_donation() is False

    def test_jnp_call_jits_lazily(self):
        from repro.exec.pallas import _JnpCall
        p = Program("scalars")
        a = p.input("a", ())
        b = p.input("b", ())
        p.output(p.mul(a, b, name="m"))
        call = _JnpCall(p, ["m"], needed={"m"})
        assert call._fn is None           # compile() must not build jits
        import jax.numpy as jnp
        env = {"a": jnp.float32(2.0), "b": jnp.float32(3.0)}
        out = call(env)                   # standalone drive jits on demand
        assert call._fn is not None
        assert float(out["m"]) == 6.0
        # apply() inlines into an outer trace without touching the jit
        call2 = _JnpCall(p, ["m"], needed={"m"})
        assert float(call2.apply(env)["m"]) == 6.0
        assert call2._fn is None

    def test_backend_probe_cached(self, monkeypatch):
        import repro.exec.pallas as pal
        monkeypatch.setattr(pal, "_BACKEND_PROBE", None)
        first = pal._default_backend()
        # once probed, the cached value is reused (no jax import per call)
        monkeypatch.setattr(pal, "_BACKEND_PROBE", "fake-backend")
        assert pal._default_backend() == "fake-backend"
        assert first in ("cpu", "gpu", "tpu")
        monkeypatch.setenv("CELLO_PALLAS_INTERPRET", "1")
        assert pal.use_interpret() is True
        monkeypatch.setenv("CELLO_PALLAS_INTERPRET", "0")
        assert pal.use_interpret() is False

    def test_interpret_mode_follows_the_platform(self, monkeypatch):
        import repro.exec.pallas as pal
        monkeypatch.delenv("CELLO_PALLAS_INTERPRET", raising=False)
        monkeypatch.setattr(pal, "_BACKEND_PROBE", "cpu")
        assert pal.use_interpret() is True
        assert pal.default_solver_backend() == "reference"
        monkeypatch.setattr(pal, "_BACKEND_PROBE", "tpu")
        assert pal.use_interpret() is False
        assert pal.default_solver_backend() == "pallas"
        # on the chip the env var may force Mosaic, never interpretation
        monkeypatch.setenv("CELLO_PALLAS_INTERPRET", "0")
        assert pal.use_interpret() is False
        monkeypatch.setenv("CELLO_PALLAS_INTERPRET", "1")
        with pytest.raises(RuntimeError, match="interpret mode on a TPU"):
            pal.use_interpret()

    def test_perunit_backend_matches_single_program(self, tmp_path):
        traced, plan = _lowered(tmp_path, workload="bicgstab", n=64,
                                iters=2)
        feeds = make_feeds(traced.program, seed=3)
        single = plan.run(feeds, backend="pallas")
        perunit = plan.run(feeds, backend="pallas-perunit")
        for k in single:
            np.testing.assert_allclose(np.asarray(perunit[k]),
                                       np.asarray(single[k]),
                                       rtol=RTOL, atol=ATOL, err_msg=k)


# ---------------------------------------------------------------------------
# VMEM budget edge: tile selection must degrade, never corrupt
# ---------------------------------------------------------------------------

class TestTileBudget:
    def test_resident_over_budget_degrades_to_finest_tile(self):
        # resident operands already exceed the VMEM budget: no tile fits,
        # and the pass degrades to a jnp unit whose reason says so
        assert _pick_tile_rows(1024, per_row_bytes=8192,
                               resident_bytes=2 << 20,
                               budget=1 << 20) is None
        assert _pick_tile_rows(96, per_row_bytes=1 << 30,
                               resident_bytes=0,
                               budget=1 << 20) is None
        p = Program("wide_rhs")
        x = p.input("x", (256, 8192))
        w = p.operator("w", (8192, 8192))            # 256 MiB resident
        p.output(p.matmul(x, w, name="y"))
        graph = p.to_graph()
        (gk,) = select_group_kernels(graph, [["y"]], 64 << 20)
        assert gk.kind == "jnp" and "VMEM" in gk.reason

    def test_budget_boundary_is_inclusive(self):
        # double-buffered working set exactly equal to the budget: taken
        rows, per_row = 1024, 1024
        assert _pick_tile_rows(rows, per_row, 0, 2 * 256 * per_row) == 256
        assert _pick_tile_rows(rows, per_row, 0,
                               2 * 256 * per_row - 1) == 128
        # resident bytes eat the budget down to the boundary
        assert _pick_tile_rows(rows, per_row, 256 * per_row,
                               2 * 512 * per_row) == 256

    def test_prime_row_count_still_positive(self):
        # no lane-aligned divisor: the whole pass is the only legal tile
        assert _pick_tile_rows(97, per_row_bytes=32, resident_bytes=0,
                               budget=1 << 20) == 97
        assert _pick_tile_rows(97, per_row_bytes=1 << 30,
                               resident_bytes=1 << 30, budget=0) is None

    def test_tiles_always_positive_divisors(self):
        for rows in (1, 2, 50, 96, 97, 1024, 1152, 4096):
            for budget in (0, 1 << 10, 1 << 20, 1 << 25):
                t = _pick_tile_rows(rows, 32, 1 << 12, budget)
                if t is not None:
                    assert t >= 1 and rows % t == 0
                    assert t % 128 == 0 or t == rows     # Mosaic-legal

    def test_zero_explicit_budget_plan_still_streams_and_matches(
            self, tmp_path):
        # a plan whose split went all-implicit must still lower to valid
        # stream kernels (floor budget) and run correctly
        prog = build_workload("cg", n=32, iters=2)
        graph = prog.to_graph()
        groups = build_groups(graph, graph.topo_order(), 64 << 20)
        kernels = select_group_kernels(graph, groups, 0)
        for gk in kernels:
            for sp in gk.passes:
                assert sp.tile_rows >= 1
                assert sp.rows % sp.tile_rows == 0


# ---------------------------------------------------------------------------
# the dispatch's instruments: child spans, copied feed bytes, host time,
# and the device scopes a trace cannot name
# ---------------------------------------------------------------------------

@pytest.fixture
def spans():
    """The global tracer, on and empty for the test."""
    from repro import obs
    tr = obs.tracer()
    was = tr.enabled
    tr.clear()
    tr.enable()
    yield tr
    if not was:
        tr.disable()
    tr.clear()


def _dispatch_counts():
    from repro import obs
    cells = obs.snapshot().get("exec.dispatch_s", {}).get("cells", [])
    out = {"True": 0, "False": 0}
    for c in cells:
        if c["labels"].get("backend") == "pallas":
            out[c["labels"]["traced"]] += c["value"]["count"]
    return out


class TestDispatchInstruments:
    def test_dispatch_children_and_feed_copy_bytes(self, tmp_path, spans,
                                                   monkeypatch):
        import warnings

        import jax.numpy as jnp
        from repro import obs
        monkeypatch.setenv("CELLO_PALLAS_DONATE", "1")
        traced, plan = _lowered(tmp_path, workload="cg", n=32, iters=2)
        host = make_feeds(traced.program, seed=0)
        feeds = {k: jnp.asarray(v) for k, v in host.items()}
        feeds["b"] = np.asarray(host["b"])     # transfers: nothing copies it
        before = _dispatch_counts()
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=".*[Dd]onat")
            for _ in range(2):
                plan.run(feeds, backend="pallas")
        ex = get_backend("pallas").compiled(plan)
        assert ex.stats["dispatches"] == 2
        copied = sum(v.nbytes for k, v in feeds.items() if k != "b")
        snap = obs.snapshot(ex._scope)
        (cell,) = snap["exec.feed_copy_bytes"]["cells"]
        assert cell["value"] == 2 * copied
        # exec.dispatch_s: the first run traced the program, the second
        # only enqueued it
        after = _dispatch_counts()
        assert after["True"] - before["True"] == 1
        assert after["False"] - before["False"] == 1
        recs = spans.spans()
        dispatches = [s for s in recs if s["name"] == "exec.dispatch"]
        assert len(dispatches) == 2
        for d in dispatches:
            kids = [s["name"] for s in recs if s["parent"] == d["id"]]
            assert kids == ["exec.feed_check", "exec.feed_copy",
                            "exec.launch"]

    def test_device_scopes_name_the_gather_and_the_layout(self, tmp_path):
        import re

        from repro.exec.pallas import DEVICE_SCOPES, GATHER_SCOPE
        traced = Session(cache_dir=tmp_path).trace(workload="cg_sparse",
                                                   n=256, iters=2)
        plan = traced.analyze().codesign().lower(backend="pallas")
        scopes = plan.device_scopes()
        assert set(scopes.values()) == set(DEVICE_SCOPES)
        # the names are instructions of the executable the plan runs
        ex = get_backend("pallas").compiled(plan)
        text = ex._jit.lower(*ex.leaf_shapes()).compile().as_text()
        defined = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", text,
                                 re.M))
        assert set(scopes) <= defined
        # at least one gather instruction runs under the gather scope
        assert any(re.search(rf"%?{re.escape(n)} = \S+ gather\(", text)
                   for n, sc in scopes.items() if sc == GATHER_SCOPE)
        # neither scope reads as a kernel name (cello_*)
        assert not any(sc.startswith("cello_") for sc in DEVICE_SCOPES)
        assert plan.device_scopes(backend="reference") == {}
        dense = Session(cache_dir=tmp_path).trace(
            workload="cg", n=32, iters=2).analyze().codesign() \
            .lower(backend="pallas")
        assert dense.device_scopes() == {}

    def test_hlo_scopes_takes_the_innermost_scope(self):
        from repro.exec.pallas import hlo_scopes
        text = "\n".join([
            'ENTRY %main {',
            '  %fusion.6 = f32[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(f)/while/body/spmv_gather/mul" '
            'source_line=3}',
            '  ROOT %g.1 = s32[4]{0} gather(%a, %b), metadata={op_name='
            '"jit(f)/csr_layout/vmap(spmv_gather)/gather"}',
            '  %w.2 = s32[4]{0} while(%t), metadata={op_name='
            '"jit(f)/csr_layout/vmap()/gather"}',
            '  %copy.1 = f32[8]{0} copy(%x)',
            '  %n.3 = f32[8]{0} add(%x, %y), metadata={op_name='
            '"jit(f)/spmv_gathered/add"}',
            '}'])
        assert hlo_scopes(text) == {"fusion.6": "spmv_gather",
                                    "g.1": "spmv_gather",
                                    "w.2": "csr_layout"}
