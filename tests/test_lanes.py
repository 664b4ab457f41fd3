"""Lane-batched stream passes: a served batch reads the shared operator
once for all lanes.

Under ``BatchedPlan``'s ``jax.vmap`` each stream pass of the pallas pure
core runs as one lane kernel (``cello_lanes_<first op>``) whenever its
matrix and diagonal-layout operands are unbatched; a per-tile CSR spmv
pass falls back to ``jax.vmap`` of the single-RHS kernel.  The single
dispatch (``CompiledPlan.run``) never takes the lane kernel.  All in
Pallas interpret mode, within the backend's float32 tolerances
(docs/execution_backends.md).
"""
import numpy as np
import pytest

from repro import obs
from repro.api import Session
from repro.exec import get_backend
from repro.frontends import make_feeds

PALLAS_RTOL, PALLAS_ATOL = 2e-4, 1e-5

SPARSE_CSR = dict(n=256, iters=4, pattern="random", density=0.05)


def _plans(tmp_path, workload, **params):
    traced = Session(cache_dir=tmp_path).trace(workload=workload, **params)
    return (traced, traced.codesign().lower(backend="pallas"),
            traced.codesign().lower(backend="reference"))


def _feeds(bp, program, n):
    shared = make_feeds(program, seed=0, only=bp.shared_leaves)
    return shared, [make_feeds(program, seed=s, only=bp.batched_leaves)
                    for s in range(1, n + 1)]


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=PALLAS_RTOL, atol=PALLAS_ATOL)


def _passes(bp, lanes):
    counter = obs.registry().counter("exec.batched_pass")
    return int(counter.value(backend="pallas", lanes=lanes,
                             scope=bp._scope))


@pytest.mark.parametrize("batch", [1, 2, 5, 8])
def test_dense_batch_matches_single_solves_and_reference(tmp_path, batch):
    """A rolled dense ``cg`` batch (5 requests pad to 8 lanes) against a
    loop of ``run_one`` and the reference backend."""
    traced, plan, ref = _plans(tmp_path, "cg", n=256, iters=6)
    bp = plan.batched()
    shared, reqs = _feeds(bp, traced.program, batch)
    outs = bp.run_many(reqs, shared)
    assert len(outs) == batch
    for r, out in zip(reqs, outs):
        one = bp.run_one({**shared, **r})
        want = ref.run({**shared, **r})
        for k in want:
            _close(out[k], one[k])
            _close(out[k], want[k])


@pytest.mark.parametrize("workload,params", [
    ("cg", dict(n=256, iters=6)),
    ("cg_sparse", dict(n=4096, iters=4))], ids=["dense", "dia"])
def test_filler_lanes_change_no_real_lane(tmp_path, workload, params):
    """Lanes past the real requests holding NaN right-hand sides leave
    every real lane exactly as repeated requests do: nothing a lane
    kernel computes crosses lanes."""
    traced, plan, _ = _plans(tmp_path, workload, **params)
    bp = plan.batched()
    shared, reqs = _feeds(bp, traced.program, 5)

    def stacked(filler):
        rows = reqs + [filler] * 3
        return {**shared, **{n: np.stack([r[n] for r in rows])
                             for n in bp.batched_leaves}}

    nan = {n: np.full_like(v, np.nan) for n, v in reqs[-1].items()}
    clean = bp.run_batch(stacked(reqs[-1]))
    dirty = bp.run_batch(stacked(nan))
    for k in clean:
        got = np.asarray(dirty[k])
        assert np.isnan(got[5:]).all()
        np.testing.assert_array_equal(got[:5], np.asarray(clean[k])[:5])


@pytest.mark.parametrize("workload,params", [
    ("cg", dict(n=256, iters=6)),
    ("cg_sparse", dict(n=4096, iters=4))], ids=["dense", "dia"])
def test_every_pass_reads_the_operand_once_for_all_lanes(
        tmp_path, workload, params):
    """``exec.batched_pass`` counts each dispatch's stream passes (a rolled
    body once per iteration) under ``shared``, none under ``per_lane``."""
    traced, plan, _ = _plans(tmp_path, workload, **params)
    bp = plan.batched()
    prog = get_backend("pallas").compile(plan)
    shared, reqs = _feeds(bp, traced.program, 3)
    for _ in range(2):
        bp.run_many(reqs, shared)
    core = bp._single.program
    passes = sum(core.batched_passes().values())
    assert core.batched_passes() == {"shared": passes}
    kernels = [c for c in (*prog._pro, *prog._epi) if getattr(
        c, "red_out", None) or getattr(c, "stream_out", None)]
    body = [c for c in prog._tmpl if c.red_out or c.stream_out]
    assert passes == len(kernels) + len(body) * prog.roll.n_iters
    assert _passes(bp, "shared") == 2 * passes
    assert _passes(bp, "per_lane") == 0


def test_per_tile_csr_spmv_keeps_one_read_per_lane(tmp_path):
    """A random-pattern ``cg_sparse`` batch: its gathered spmv values
    differ per lane, so each spmv pass runs ``jax.vmap`` of the
    single-RHS kernel; the batch still matches the reference."""
    traced, plan, ref = _plans(tmp_path, "cg_sparse", **SPARSE_CSR)
    bp = plan.batched()
    shared, reqs = _feeds(bp, traced.program, 4)
    outs = bp.run_many(reqs, shared)
    for r, out in zip(reqs, outs):
        want = ref.run({**shared, **r})
        for k in want:
            _close(out[k], want[k])
    spmv = get_backend("pallas").compile(plan).spmv_layouts
    assert spmv == {"csr": spmv["csr"]}
    assert bp._single.program.batched_passes()["per_lane"] == spmv["csr"]
    assert _passes(bp, "per_lane") == spmv["csr"]


@pytest.mark.parametrize("workload,params", [
    ("cg", dict(n=256, iters=6)),
    ("cg_sparse", dict(n=4096, iters=4)),
    ("cg_sparse", SPARSE_CSR)], ids=["dense", "dia", "csr"])
def test_only_the_batched_program_names_lane_kernels(
        tmp_path, workload, params):
    """The lowered single-dispatch program traces the single-RHS kernels
    alone; its batched twin traces lane kernels."""
    import jax
    traced, plan, _ = _plans(tmp_path, workload, **params)
    prog = get_backend("pallas").compile(plan)
    single = prog._jit.lower(*prog.leaf_shapes()).as_text(debug_info=True)
    assert "cello_stream_" in single and "cello_lanes_" not in single
    bp = plan.batched()
    shapes = dict(zip(prog.leaf_names, prog.leaf_shapes()))
    batched = [jax.ShapeDtypeStruct((4,) + shapes[n].shape, shapes[n].dtype)
               for n in bp.batched_leaves]
    text = bp._build().lower([shapes[n] for n in bp.shared_leaves],
                             batched).as_text(debug_info=True)
    assert "cello_lanes_" in text
