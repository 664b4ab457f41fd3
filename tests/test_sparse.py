"""Sparse-operand tests: CSR frontend, density-aware co-design, kernels.

Covers the contract end-to-end:

* **CSR lowering goldens** — the sub-leaf triple's shapes/dtypes/bytes are
  nnz-based, spmv carries ``2·nnz`` FLOPs, and kernel selection lowers
  spmv groups to stream passes over a padded per-tile entry layout.
* **Generators** — exact nnz counts, valid CSR structure, and the
  promised numerics (laplacian5/banded SPD, random/skewed diagonally
  dominant), all against the scipy-free :func:`csr_to_dense` densifier.
* **Sparse CG** — the residual identity ``r_k = b − A x_k`` against the
  dense reconstruction, plus SPD convergence on the Laplacian.
* **Parity** — reference replays bitwise; pallas matches within the
  documented tolerances, at fp64 under ``jax_enable_x64`` (the modeled
  precision) as well as default fp32.
* **Density-aware pins** — the CSR triple pins all-or-nothing exactly at
  the nnz-footprint capacity boundary, and a paper-shaped sparse solve
  shows the pin in ``plan.explain()``.
"""
import numpy as np
import pytest

from repro.api import Session
from repro.core import select_group_kernels
from repro.core.lowering import flatten_units
from repro.core.reuse import analyze
from repro.core.schedule import choose_pins, sparse_operand_groups
from repro.frontends import (Program, build_workload, csr_to_dense,
                             evaluate, make_feeds, pattern_nnz)
from repro.frontends.sparse import row_counts

# float32 reduction-reassociation tolerances (documented policy)
RTOL32, ATOL32 = 2e-4, 1e-5
# fp64: same reassociation, ~2^-29 smaller ulps
RTOL64, ATOL64 = 1e-9, 1e-12

#: every sparse workload in the registry, one row per pattern family
SPARSE_PARITY_SET = [
    ("cg_sparse", dict(n=64, iters=3)),                       # laplacian5
    ("cg_sparse", dict(n=50, iters=2, pattern="banded", bandwidth=3)),
    ("bicgstab_sparse", dict(n=64, iters=2)),
    ("bicgstab_sparse", dict(n=48, iters=2, pattern="random",
                             density=0.1)),
    ("jacobi_sparse", dict(n=64, sweeps=3)),
    ("jacobi_sparse", dict(n=40, sweeps=2, pattern="skewed",
                           density=0.15)),
]
_IDS = [f"{w}-{p.get('pattern', 'laplacian5')}"
        for w, p in SPARSE_PARITY_SET]


def _dense_A(feeds, n):
    return csr_to_dense(feeds["A.indptr"], feeds["A.indices"],
                        feeds["A.data"], (n, n))


def _lowered(tmp_path, workload, **params):
    traced = Session(cache_dir=tmp_path).trace(workload=workload, **params)
    return traced, traced.analyze().codesign().lower()


# ---------------------------------------------------------------------------
# CSR lowering goldens
# ---------------------------------------------------------------------------

class TestCsrLowering:
    def test_sub_leaf_shapes_and_nnz_annotations(self):
        p = Program("lower")
        A = p.sparse_operator("A", (16, 16))           # laplacian5, g=4
        x = p.input("x", (16,))
        y = p.spmv(A, x, name="y")
        p.output(y)
        nnz = 5 * 16 - 4 * 4
        assert A.nnz == nnz == pattern_nnz("laplacian5", 16)
        assert p.nodes["A.indptr"].shape == (17,)
        assert p.nodes["A.indices"].shape == (nnz,)
        assert p.nodes["A.data"].shape == (nnz,)
        assert y.node.flops == 2 * nnz                 # nnz-based FLOPs
        g = p.to_graph()
        # byte annotations are nnz-based: int32 indices, fp64 data
        assert g.tensors["A.indptr"].bytes == 17 * 4
        assert g.tensors["A.indices"].bytes == nnz * 4
        assert g.tensors["A.data"].bytes == nnz * 8
        assert g.ops["y"].spec == "spmv" and not g.ops["y"].irregular

    def test_spmv_group_selects_spmv_stream_kernel(self):
        p = Program("sel")
        A = p.sparse_operator("A", (64, 64))
        x = p.input("x", (64,))
        y = p.spmv(A, x, name="y")
        p.output(p.dot(y, y, name="yy"))
        g = p.to_graph()
        (gk,) = select_group_kernels(g, [["y", "yy"]], 16 << 20)
        assert gk.kind == "stream"
        (sp,) = gk.passes
        # the spmv runs on the per-tile entry layout: nothing of the
        # operand is held resident, x is gathered by column index
        assert sp.spmv == ("y",) and not sp.resident
        assert sp.reductions == ("yy",)
        assert "pallas-spmv" in gk.describe()

    def test_spmv_reading_in_pass_vector_splits_passes(self):
        p = Program("split")
        A = p.sparse_operator("A", (16, 16))
        x = p.input("x", (16,))
        y1 = p.spmv(A, x, name="y1")
        y2 = p.spmv(A, y1, name="y2")                  # y1 must materialize
        p.output(y2)
        (gk,) = select_group_kernels(p.to_graph(), [["y1", "y2"]], 16 << 20)
        assert gk.kind == "stream" and len(gk.passes) == 2
        assert [u.ops for u in flatten_units([gk])] == [("y1",), ("y2",)]

    def test_spmv_validation(self):
        p = Program("bad")
        A = p.sparse_operator("A", (16, 16))
        with pytest.raises(ValueError, match="square"):
            p.sparse_operator("B", (16, 8))
        with pytest.raises(TypeError, match="SparseOperand"):
            p.spmv(p.input("d", (16, 16)), p.input("x", (16,)))
        with pytest.raises(ValueError, match="shape"):
            p.spmv(A, p.input("x2", (8,)))
        with pytest.raises(ValueError, match="perfect square"):
            p.sparse_operator("C", (12, 12))           # laplacian5 needs g²
        with pytest.raises(ValueError, match="density"):
            p.sparse_operator("D", (16, 16), pattern="random")
        with pytest.raises(ValueError, match="bandwidth"):
            p.sparse_operator("E", (16, 16), pattern="banded")
        with pytest.raises(ValueError, match="unknown sparse pattern"):
            p.sparse_operator("F", (16, 16), pattern="hypercube")


# ---------------------------------------------------------------------------
# deterministic generators
# ---------------------------------------------------------------------------

class TestGenerators:
    @pytest.mark.parametrize("pattern,kw,n", [
        ("laplacian5", {}, 64),
        ("banded", {"bandwidth": 3}, 50),
        ("random", {"density": 0.1}, 48),
        ("skewed", {"density": 0.1}, 48),
    ])
    def test_csr_structure_and_nnz(self, pattern, kw, n):
        p = Program(f"gen_{pattern}")
        A = p.sparse_operator("A", (n, n), pattern=pattern, **kw)
        p.output(p.spmv(A, p.input("x", (n,))))
        feeds = make_feeds(p, seed=4)
        ip, ix, dv = (feeds["A.indptr"], feeds["A.indices"],
                      feeds["A.data"])
        nnz = pattern_nnz(pattern, n, **kw)
        assert nnz == int(row_counts(pattern, n, **kw).sum())
        assert ip.dtype == np.int32 and ix.dtype == np.int32
        assert ip.shape == (n + 1,) and ip[0] == 0 and ip[-1] == nnz
        assert np.all(np.diff(ip) >= 1)                # diagonal present
        assert ix.shape == dv.shape == (nnz,)
        assert ix.min() >= 0 and ix.max() < n
        # columns sorted & unique within every row
        for r in range(n):
            cols = ix[ip[r]:ip[r + 1]]
            assert np.all(np.diff(cols) > 0)
            assert r in cols                           # diagonal entry

    @pytest.mark.parametrize("pattern,kw", [
        ("laplacian5", {}), ("banded", {"bandwidth": 4})])
    def test_symmetric_patterns_are_spd(self, pattern, kw):
        n = 49 if pattern == "laplacian5" else 40
        p = Program(f"spd_{pattern}")
        A = p.sparse_operator("A", (n, n), pattern=pattern, **kw)
        p.output(p.spmv(A, p.input("x", (n,))))
        feeds = make_feeds(p, seed=0, dtype=np.float64)
        D = _dense_A(feeds, n)
        np.testing.assert_allclose(D, D.T)
        assert np.linalg.eigvalsh(D).min() > 0

    @pytest.mark.parametrize("pattern", ["random", "skewed"])
    def test_dominant_diagonal(self, pattern):
        n = 32
        p = Program(f"dom_{pattern}")
        A = p.sparse_operator("A", (n, n), pattern=pattern, density=0.2)
        p.output(p.spmv(A, p.input("x", (n,))))
        D = _dense_A(make_feeds(p, seed=9, dtype=np.float64), n)
        off = np.abs(D - np.diag(np.diag(D))).sum(axis=1)
        assert np.all(np.diag(D) > off - 1e-9)

    def test_laplacian5_vectorized_matches_row_loop(self):
        """The vectorized laplacian5 generator is bit-identical to the
        per-row construction it replaced."""
        from repro.frontends.sparse import _components
        n, g = 4096, 64
        indices, data = [], []
        for r in range(n):                       # the per-row original
            i, j = divmod(r, g)
            cols = [r - g] * (i > 0) + [r - 1] * (j > 0) + [r] \
                + [r + 1] * (j < g - 1) + [r + g] * (i < g - 1)
            indices += cols
            data += [4.0 if c == r else -1.0 for c in cols]
        comp = _components("laplacian5", n, None, None, 0, "A")
        np.testing.assert_array_equal(comp["indices"],
                                      np.asarray(indices, np.int32))
        assert comp["data"].dtype == np.float64
        assert comp["data"].tobytes() == np.asarray(data).tobytes()
        want_ptr = np.concatenate(
            ([0], np.cumsum(row_counts("laplacian5", n)))).astype(np.int32)
        np.testing.assert_array_equal(comp["indptr"], want_ptr)

    @pytest.mark.parametrize("pattern,kw,n", [
        ("laplacian5", {}, 49), ("laplacian5", {}, 1),
        ("banded", {"bandwidth": 3}, 30), ("random", {"density": 0.2}, 30),
        ("skewed", {"density": 0.2}, 30)])
    def test_pattern_offsets_cover_every_entry(self, pattern, kw, n):
        from repro.frontends.sparse import pattern_offsets
        p = Program(f"offs_{pattern}")
        A = p.sparse_operator("A", (n, n), pattern=pattern, **kw)
        p.output(p.spmv(A, p.input("x", (n,))))
        feeds = make_feeds(p, seed=3)
        offs = pattern_offsets(pattern, n, kw.get("bandwidth"))
        if pattern in ("random", "skewed"):
            assert offs is None
            return
        assert list(offs) == sorted(set(offs))
        rows = np.repeat(np.arange(n), np.diff(feeds["A.indptr"]))
        assert set(feeds["A.indices"] - rows) == set(offs)

    def test_dinv_matches_diagonal(self):
        n = 36
        prog = build_workload("jacobi_sparse", n=n, sweeps=1)
        feeds = make_feeds(prog, seed=2, dtype=np.float64)
        D = _dense_A(feeds, n)
        np.testing.assert_allclose(feeds["A.dinv"], 1.0 / np.diag(D))

    def test_deterministic_and_seed_sensitive(self):
        prog = build_workload("cg_sparse", n=36, iters=1,
                              pattern="random", density=0.2)
        a = make_feeds(prog, seed=1)
        b = make_feeds(prog, seed=1)
        c = make_feeds(prog, seed=2)
        np.testing.assert_array_equal(a["A.data"], b["A.data"])
        assert not np.array_equal(a["A.data"], c["A.data"])
        # same pattern+value stream across dtypes (cast at the end)
        d = make_feeds(prog, seed=1, dtype=np.float64)
        np.testing.assert_array_equal(a["A.indices"], d["A.indices"])
        np.testing.assert_allclose(a["A.data"],
                                   d["A.data"].astype(np.float32))


# ---------------------------------------------------------------------------
# sparse CG numerics vs the scipy-free dense reference
# ---------------------------------------------------------------------------

class TestSparseCG:
    def test_residual_identity_and_convergence(self):
        import jax
        prog = build_workload("cg_sparse", n=64, iters=4)
        feeds = make_feeds(prog, seed=1, dtype=np.float64)
        with jax.enable_x64(True):
            vals = evaluate(prog, feeds, return_all=True)
        D = _dense_A(feeds, 64)
        x4, r4 = np.asarray(vals["x4"]), np.asarray(vals["r4"])
        np.testing.assert_allclose(r4, feeds["b"] - D @ x4, atol=1e-8)
        norms = [float(np.linalg.norm(np.asarray(vals[f"r{k}"])))
                 for k in range(5)]
        assert norms[-1] < 0.2 * norms[0]       # SPD Laplacian: converges

    def test_spmv_matches_dense_matvec(self):
        import jax
        for pattern, kw in [("laplacian5", {}),
                            ("banded", {"bandwidth": 5}),
                            ("random", {"density": 0.15})]:
            n = 49
            p = Program(f"mv_{pattern}")
            A = p.sparse_operator("A", (n, n), pattern=pattern, **kw)
            x = p.input("x", (n,))
            p.output(p.spmv(A, x, name="y"))
            feeds = make_feeds(p, seed=5, dtype=np.float64)
            with jax.enable_x64(True):
                out = evaluate(p, feeds)
            np.testing.assert_allclose(
                np.asarray(out["y"]), _dense_A(feeds, n) @ feeds["x"],
                rtol=1e-12, atol=1e-12, err_msg=pattern)


# ---------------------------------------------------------------------------
# reference <-> pallas parity for every sparse workload
# ---------------------------------------------------------------------------

class TestSparseParity:
    @pytest.mark.parametrize("workload,params", SPARSE_PARITY_SET,
                             ids=_IDS)
    def test_parity_fp32(self, workload, params, tmp_path):
        traced, plan = _lowered(tmp_path, workload, **params)
        feeds = make_feeds(traced.program, seed=7)
        want = evaluate(traced.program, feeds)
        ref = plan.run(feeds, backend="reference")
        for k in want:                    # same pure ops => bitwise
            np.testing.assert_array_equal(np.asarray(ref[k]),
                                          np.asarray(want[k]), err_msg=k)
        pal = plan.run(feeds, backend="pallas")
        for k in want:
            np.testing.assert_allclose(np.asarray(pal[k]),
                                       np.asarray(want[k]),
                                       rtol=RTOL32, atol=ATOL32,
                                       err_msg=k)

    @pytest.mark.parametrize("workload,params", SPARSE_PARITY_SET,
                             ids=_IDS)
    def test_parity_fp64(self, workload, params, tmp_path):
        """The modeled precision: fp64 feeds under jax_enable_x64."""
        import jax
        traced, plan = _lowered(tmp_path, workload, **params)
        feeds = make_feeds(traced.program, seed=11, dtype=np.float64)
        with jax.enable_x64(True):
            want = evaluate(traced.program, feeds)
            pal = plan.run(feeds, backend="pallas")
        for k in want:
            assert np.asarray(pal[k]).dtype == np.float64, k
            np.testing.assert_allclose(np.asarray(pal[k]),
                                       np.asarray(want[k]),
                                       rtol=RTOL64, atol=ATOL64,
                                       err_msg=k)

    def test_sparse_cg_rolls_iterations(self, tmp_path):
        traced, plan = _lowered(tmp_path, "cg_sparse", n=64, iters=4)
        assert plan.exec_plan.roll is not None
        assert plan.exec_plan.roll.n_iters >= 2

    def test_feeds_off_the_traced_pattern_are_refused(self, tmp_path):
        """The per-tile layout is sized from the pattern meta: a CSR feed
        with the same nnz but its entries crowded into one row tile
        would lose entries, so the pallas paths refuse it."""
        n = 4096                                   # several row tiles
        traced, plan = _lowered(tmp_path, "cg_sparse", n=n, iters=2)
        feeds = make_feeds(traced.program, seed=0)
        nnz = feeds["A.indices"].shape[0]
        counts = np.ones(n, np.int64)
        counts[0] = nnz - (n - 1)                  # row 0 holds the rest
        feeds["A.indptr"] = np.concatenate(
            ([0], np.cumsum(counts))).astype(np.int32)
        feeds["A.indices"] = (np.arange(nnz) % n).astype(np.int32)
        with pytest.raises(ValueError, match="CSR feed 'A.indptr'"):
            plan.run(feeds, backend="pallas")
        bp = plan.batched(backend="pallas")
        shared = {n: feeds[n] for n in bp.shared_leaves}
        with pytest.raises(ValueError, match="CSR feed 'A.indptr'"):
            bp.run_many([{n: feeds[n] for n in bp.batched_leaves}], shared)
        # the reference backend has no layout and answers as before
        assert np.all(np.isfinite(np.asarray(
            plan.run(feeds, backend="reference")["x2"])))


# ---------------------------------------------------------------------------
# the diagonal layout: laplacian5 / banded spmv without a gather
# ---------------------------------------------------------------------------

def _spmv_program(pattern, n, **kw):
    p = Program(f"dia_{pattern}_{n}")
    A = p.sparse_operator("A", (n, n), pattern=pattern, **kw)
    p.output(p.spmv(A, p.input("x", (n,)), name="y"))
    return p


def _csr_product64(feeds, x):
    """``A @ x`` in float64 straight from the CSR triple."""
    ip = np.asarray(feeds["A.indptr"])
    rows = np.repeat(np.arange(len(ip) - 1), np.diff(ip))
    return np.bincount(rows, weights=np.asarray(feeds["A.data"], np.float64)
                       * np.asarray(x, np.float64)[feeds["A.indices"]],
                       minlength=len(ip) - 1)


#: (pattern, n, pattern params, rows per tile of the spmv pass): one tile
#: for all rows, tiles wider than the grid side g (halo of one tile),
#: as wide as g, narrower than g (a halo of two tiles); banded of
#: bandwidth 1 and 3
DIA_CASES = [
    ("laplacian5", 64, {}, 64),
    ("laplacian5", 4096, {}, 1024),
    ("laplacian5", 16384, {}, 128),
    ("laplacian5", 65536, {}, 128),
    ("banded", 50, {"bandwidth": 1}, 50),
    ("banded", 1024, {"bandwidth": 3}, 128),
]


class TestDiagonalLayout:
    @pytest.mark.parametrize("via", ["pass", "batched"])
    @pytest.mark.parametrize("pattern,n,kw,tile", DIA_CASES,
                             ids=[f"{c[0]}-{c[1]}-t{c[3]}"
                                  for c in DIA_CASES])
    def test_diagonal_spmv_equals_float64_csr_product(
            self, pattern, n, kw, tile, via, tmp_path):
        import dataclasses

        import jax.numpy as jnp

        from repro.exec.pallas import _StreamCall
        p = _spmv_program(pattern, n, **kw)
        feeds = make_feeds(p, seed=4)
        if via == "pass":
            (gk,) = select_group_kernels(p.to_graph(), [["y"]], 16 << 20)
            (sp,) = gk.passes
            assert sp.dia == ("y",)
            call = _StreamCall(p, dataclasses.replace(sp, tile_rows=tile),
                               {"y"})
            env = {k: jnp.asarray(v) for k, v in feeds.items()}
            for name, build in call.derived.items():
                env[name] = build(env, jnp.float32)
            got = [call.apply(env, jnp.float32)["y"]]
            xs = [feeds["x"]]
        else:                  # one shared operand, vmapped right sides
            plan = Session.from_graph(p, cache_dir=tmp_path).analyze() \
                .codesign().lower()
            assert all(u.sp.dia == u.sp.spmv for u in plan.exec_plan.units
                       if u.sp is not None)
            bp = plan.batched(backend="pallas")
            xs = [feeds["x"], feeds["x"][::-1].copy()]
            got = [o["y"] for o in bp.run_many(
                [{"x": x} for x in xs],
                {k: feeds[k] for k in bp.shared_leaves})]
        for x, y in zip(xs, got):
            np.testing.assert_allclose(np.asarray(y, np.float64),
                                       _csr_product64(feeds, x),
                                       rtol=RTOL32, atol=ATOL32)

    def test_cg_sparse_solve_on_the_diagonal_layout(self, tmp_path):
        traced, plan = _lowered(tmp_path, "cg_sparse", n=4096, iters=4)
        spmv = [o for u in plan.exec_plan.units if u.sp for o in u.sp.spmv]
        assert spmv and all(o in u.sp.dia for u in plan.exec_plan.units
                            if u.sp for o in u.sp.spmv)
        feeds = make_feeds(traced.program, seed=2)
        want = evaluate(traced.program, feeds)
        got = plan.run(feeds, backend="pallas")
        for k in want:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]),
                                       rtol=RTOL32, atol=ATOL32, err_msg=k)

    @pytest.mark.parametrize("workload,params,layout", [
        ("cg_sparse", dict(n=64, iters=3), "dia"),
        ("cg_sparse", dict(n=50, iters=2, pattern="banded", bandwidth=3),
         "dia"),
        ("bicgstab_sparse", dict(n=48, iters=2, pattern="random",
                                 density=0.1), "csr"),
        ("jacobi_sparse", dict(n=40, sweeps=2, pattern="skewed",
                               density=0.15), "csr"),
    ], ids=["laplacian5", "banded", "random", "skewed"])
    def test_each_pattern_lowers_to_its_layout(self, workload, params,
                                               layout, tmp_path):
        from repro import obs
        from repro.exec import get_backend
        traced, plan = _lowered(tmp_path, workload, **params)
        passes = [u.sp for u in plan.exec_plan.units
                  if u.sp is not None and u.sp.spmv]
        assert passes
        for sp in passes:
            assert sp.dia == (sp.spmv if layout == "dia" else ())
        text = plan.explain()
        assert f":{layout}" in text
        assert (":csr" if layout == "dia" else ":dia") not in text
        prog = get_backend("pallas").compile(plan)
        assert set(prog.spmv_layouts) == {layout}
        prog(make_feeds(traced.program, seed=1))
        counter = obs.registry().counter("exec.spmv_layout")
        assert counter.value(backend="pallas", layout=layout,
                             scope=prog._scope) \
            == prog.spmv_layouts[layout] > 0

    def test_an_entry_off_the_diagonals_reads_nan_never_a_short_row(
            self, tmp_path):
        """A Laplacian feed with one entry moved off the operand's
        diagonals still fits the per-tile windows, so the feed check
        passes it; the spmv's answer in that row is NaN, and every other
        row is the product."""
        n, row = 4096, 2 * 64 + 5               # an interior grid row
        p = _spmv_program("laplacian5", n)
        plan = Session.from_graph(p, cache_dir=tmp_path).analyze() \
            .codesign().lower()
        feeds = make_feeds(p, seed=6)
        ip = feeds["A.indptr"]
        k = ip[row] + 3                         # the row's (row, row+1)
        assert feeds["A.indices"][k] == row + 1
        feeds["A.indices"][k] = row + 2         # off every diagonal
        y = np.asarray(plan.run(feeds, backend="pallas")["y"])
        assert np.isnan(y[row])
        rest = np.arange(n) != row
        np.testing.assert_allclose(y[rest], _csr_product64(feeds,
                                                           feeds["x"])[rest],
                                   rtol=RTOL32, atol=ATOL32)
        # the reference applies the CSR as given
        assert np.isfinite(np.asarray(
            plan.run(feeds, backend="reference")["y"])).all()


# ---------------------------------------------------------------------------
# density-aware pinning
# ---------------------------------------------------------------------------

def _two_spmv_graph(n=64, bandwidth=2):
    """A is read twice (reuse!); the only pin candidates are its triple."""
    p = Program("pin_boundary")
    A = p.sparse_operator("A", (n, n), pattern="banded",
                          bandwidth=bandwidth)
    x = p.input("x", (n,))
    y1 = p.spmv(A, x, name="y1")
    p.output(p.spmv(A, y1, name="y2"))
    g = p.to_graph()
    csr_bytes = sum(g.tensors[t].bytes
                    for t in ("A.indptr", "A.indices", "A.data"))
    return g, csr_bytes


class TestDensityAwarePins:
    def test_nnz_footprint_boundary(self):
        g, csr_bytes = _two_spmv_graph()
        an = analyze(g)
        groups = [[o] for o in g.topo_order()]
        assert sparse_operand_groups(g) == [("A.indptr", "A.indices",
                                             "A.data")]
        # nnz footprint exactly fits -> the whole triple pins
        pins = choose_pins(g, groups, an, csr_bytes)
        assert {"A.indptr", "A.indices", "A.data"} <= set(pins)
        # one byte short -> nothing of the operand pins (no partial pin)
        pins = choose_pins(g, groups, an, csr_bytes - 1)
        assert not ({"A.indptr", "A.indices", "A.data"} & set(pins))

    def test_pin_is_all_or_nothing_even_when_members_fit(self):
        g, csr_bytes = _two_spmv_graph()
        # indptr+indices alone would fit this budget; the unit must not
        ip_ix = (g.tensors["A.indptr"].bytes
                 + g.tensors["A.indices"].bytes)
        pins = choose_pins(g, [[o] for o in g.topo_order()], analyze(g),
                           ip_ix)
        assert not ({"A.indptr", "A.indices", "A.data"} & set(pins))

    def test_session_plan_shows_density_aware_pin(self, tmp_path):
        """Acceptance: a sparse A whose nnz footprint fits capacity is
        pinned, visibly, where the dense A of the same n might not be."""
        traced, plan = _lowered(tmp_path, "cg_sparse", n=64, iters=3)
        pins = plan.codesigned.best.schedule.pins
        assert {"A.indptr", "A.indices", "A.data"} <= set(pins)
        text = plan.explain()
        assert "A.data[g" in text and "A.indices[g" in text
        assert "pinned-by-nnz-footprint=1" in text
        assert "pallas-spmv" in text

    def test_dense_vs_sparse_footprint_crossover(self, tmp_path):
        """At a capacity far below the dense n² silhouette the sparse
        operand still pins — the density-aware co-design's whole point."""
        n = 256    # dense A = 512 KiB fp64; CSR footprint ~15.6 KiB
        sess = Session(capacity_bytes=256 << 10, cache_dir=tmp_path)
        dense = sess.trace(workload="cg", n=n, iters=2)
        dplan = dense.analyze().codesign().lower()
        assert "A" not in dplan.codesigned.best.schedule.pins
        sparse = sess.trace(workload="cg_sparse", n=n, iters=2)
        splan = sparse.analyze().codesign().lower()
        spins = splan.codesigned.best.schedule.pins
        assert {"A.indptr", "A.indices", "A.data"} <= set(spins)


# ---------------------------------------------------------------------------
# overbooked pins: fractional residency
# ---------------------------------------------------------------------------

class TestOverbookedPins:
    def test_prefix_boundary(self):
        """The fractional boundary: at the overbook window edge an
        indptr-aligned row prefix pins; one byte below it the operand
        streams entirely."""
        g, csr_bytes = _two_spmv_graph()
        an = analyze(g)
        groups = [[o] for o in g.topo_order()]
        edge = -(-csr_bytes * 4 // 5)        # ceil(csr_bytes / 1.25)
        pins = choose_pins(g, groups, an, edge, overbook=0.25)
        assert {"A.indptr", "A.indices", "A.data"} <= set(pins)
        pp = pins.partial["A.data"]
        assert 0 < pp.rows < pp.total_rows
        assert pp.resident_bytes <= edge
        counts = row_counts("banded", 64, bandwidth=2)
        # prefix cut sits on an indptr row boundary, never mid-row
        assert pp.entries == int(counts[: pp.rows].sum())
        pins = choose_pins(g, groups, an, edge - 1, overbook=0.25)
        assert not ({"A.indptr", "A.indices", "A.data"} & set(pins))
        assert not pins.partial

    def test_full_fit_never_prefixes(self):
        g, csr_bytes = _two_spmv_graph()
        pins = choose_pins(g, [[o] for o in g.topo_order()], analyze(g),
                           csr_bytes, overbook=0.25)
        assert {"A.indptr", "A.indices", "A.data"} <= set(pins)
        assert not pins.partial

    def test_overbook_zero_reproduces_all_or_nothing(self):
        """``overbook=0`` must be bit-for-bit the pre-overbook rule."""
        g, csr_bytes = _two_spmv_graph()
        an = analyze(g)
        groups = [[o] for o in g.topo_order()]
        for budget in (csr_bytes, csr_bytes - 1,
                       -(-csr_bytes * 4 // 5)):
            base = choose_pins(g, groups, an, budget)
            zero = choose_pins(g, groups, an, budget, overbook=0.0)
            assert dict(zero) == dict(base)
            assert not zero.partial and not base.partial

    def test_session_prefix_pin_end_to_end(self, tmp_path):
        """A winning prefix pin reaches explain(), the lowered kernels,
        and the pallas backend — which stays parity-correct."""
        sess = Session(cache_dir=tmp_path)
        traced = sess.trace(workload="cg_sparse", n=64, iters=3,
                            pattern="banded", bandwidth=2)
        plan = traced.analyze().codesign(capacity_bytes=4500,
                                         overbook=0.25).lower()
        text = plan.explain()
        assert "pinned=prefix(rows=" in text
        assert "pin overbook" in text
        # the prefix pin is a buffer decision; the spmv passes stream
        # the per-tile entry layout either way
        assert any(p.spmv for gk in plan.group_kernels
                   for p in gk.passes)
        feeds = make_feeds(traced.program, seed=3)
        want = evaluate(traced.program, feeds)
        ref = plan.run(feeds, backend="reference")
        for k in want:                    # residency never touches numerics
            np.testing.assert_array_equal(np.asarray(ref[k]),
                                          np.asarray(want[k]), err_msg=k)
        pal = plan.run(feeds, backend="pallas")
        for k in want:
            np.testing.assert_allclose(np.asarray(pal[k]),
                                       np.asarray(want[k]),
                                       rtol=RTOL32, atol=ATOL32,
                                       err_msg=k)
