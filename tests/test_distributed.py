"""Distributed-optimisation mechanics: microbatch accumulation equivalence
and the compressed cross-pod all-reduce under shard_map — plus the HPC
side: co-designed DAGs partitioned across a device mesh
(``Session.lower(mesh=...)``, ``core.lowering.partition_plan``)."""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.policy import default_plan
from repro.data import DataConfig, SyntheticLMData
from repro.launch.train import (AdamWConfig, TrainConfig, make_train_step)
from repro.models import init_params
from repro.optim import adamw_init


@pytest.mark.slow
def test_accum_steps_matches_full_batch():
    """accum=2 over a split batch == accum=1 over the full batch (the
    gradient mean must be identical up to f32 reduction order)."""
    cfg = get_config("granite-3-8b").reduced()
    plan = default_plan(cfg, seq=16)
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, weight_decay=0.0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = SyntheticLMData(DataConfig(vocab=cfg.vocab, seq_len=16,
                                    global_batch=8, seed=1))
    x, y = next(ds)
    batch = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}

    s1 = jax.jit(make_train_step(cfg, plan, opt, TrainConfig(donate=False)))
    s2 = jax.jit(make_train_step(cfg, plan, opt,
                                 TrainConfig(accum_steps=2, donate=False)))
    p1, _, m1 = s1(params, adamw_init(params), batch)
    p2, _, m2 = s2(params, adamw_init(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-3)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        # bf16 grads through Adam's normalisation: rare ulp-level flips
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-3, rtol=5e-3)


@pytest.mark.slow
def test_compressed_crosspod_allreduce_subprocess():
    """int8+EF compressed psum over a manual 'pod' axis (shard_map) on 8
    placeholder devices: the compressed mean must track the exact mean and
    the EF residual must carry the difference."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.optim import (CompressionState, compress_int8, decompress_int8,
                         error_feedback_compress)

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))

def sync(grads, err):
    # per-pod grads (already reduced over fast in-pod links) → compress →
    # cross-pod psum of the dequantised tensor + error feedback
    corrected = grads + err
    q, scale = compress_int8(corrected)
    sent = decompress_int8(q, scale)
    new_err = corrected - sent
    total = jax.lax.psum(sent, "pod") / jax.lax.psum(1.0, "pod")
    return total, new_err

f = jax.shard_map(sync, mesh=mesh, in_specs=(P("pod"), P("pod")),
                  out_specs=(P(None), P("pod")), check_vma=False)

rng = np.random.default_rng(0)
g = jnp.asarray(rng.standard_normal((2, 1024)) * 0.01, jnp.float32)
err = jnp.zeros((2, 1024), jnp.float32)
drift = []
for step in range(10):
    gs = g * (1 + 0.1 * step)
    mean_true = np.asarray(gs).mean(axis=0)
    total, err = f(gs, err)
    approx = np.asarray(total)[0]
    drift.append(float(np.abs(approx - mean_true).max()))
# instantaneous error bounded by the quantisation step; EF keeps it flat
print(json.dumps({"max_drift": max(drift), "last_drift": drift[-1]}))
"""
    res = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["max_drift"] < 5e-4, out     # ~int8 step of 0.01-scale grads

# ---------------------------------------------------------------------------
# HPC plan partitioning: Session.lower(mesh=...) over partition_plan
# ---------------------------------------------------------------------------

from repro.api import CodesignConfig, ExecConfig, Session
from repro.core.buffer import MiB
from repro.core.lowering import PlanPartitionError, partition_plan
from repro.frontends.reference import make_feeds


def _jnp_feeds(program, seed=0):
    # bitwise contract holds for jax-array feeds: numpy feeds route the
    # unsharded oracle's matmuls through numpy BLAS, which need not match
    # XLA bit-for-bit (see docs/distributed.md)
    return {k: jnp.asarray(v) for k, v in make_feeds(program, seed).items()}


def _bitwise(a, b):
    assert set(a) == set(b)
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


def test_partition_csr_entry_windows_golden():
    """cg_sparse splits its CSR triple on indptr-aligned entry windows:
    the shard boundaries must equal the cumulative row_counts of the
    deterministic pattern meta, and windows must cover nnz exactly."""
    from repro.frontends.sparse import row_counts
    sess = Session()
    t = sess.trace(workload="cg_sparse", n=256, iters=2)
    plan = sess.lower(sess.codesign(t), mesh=8)
    sp = plan.sharded
    assert sp.n_shards == 8 and sp.rows == 256
    (lay,) = sp.csr
    leaf = t.program.nodes[lay.indptr]
    counts = row_counts(leaf.param("pattern"), 256,
                        density=leaf.param("density"),
                        bandwidth=leaf.param("bandwidth"))
    cum = [0]
    for c in counts:
        cum.append(cum[-1] + int(c))
    assert list(lay.entry_starts) == [cum[k * 32] for k in range(9)]
    assert lay.entry_starts[-1] == lay.nnz
    widest = max(b - a for a, b in zip(lay.entry_starts,
                                       lay.entry_starts[1:]))
    assert lay.pad_entries >= widest and lay.pad_entries % 8 == 0
    for k, sl in enumerate(lay.slices):
        assert sl.rows == 32 and sl.row0 == k * 32
        assert sl.entries == lay.entry_starts[k + 1] - lay.entry_starts[k]


def test_partition_rejections():
    """Everything the contiguous row-block story cannot express fails
    loudly at lower time, never at dispatch."""
    sess = Session()
    t = sess.trace(workload="cg", n=256, iters=2)
    plan = sess.lower(sess.codesign(t))
    # ragged: 256 rows over 3 shards
    with pytest.raises(PlanPartitionError, match="do not split evenly"):
        partition_plan(plan.exec_plan, 3, program=t.program)
    # mttkrp's "abc,cb->ab"-style einsums are not row-block shardable
    tm = sess.trace(workload="mttkrp", rank=16)
    pm = sess.lower(sess.codesign(tm))
    with pytest.raises(PlanPartitionError):
        partition_plan(pm.exec_plan, 4, program=tm.program)
    # overbooked partial pins and sharding both claim the row dimension
    ts = sess.trace(workload="cg_sparse", n=256, iters=2, density=0.3)
    cds = sess.codesign(ts, CodesignConfig(
        overbook=0.25, capacity_bytes=int(0.05 * MiB)))
    partial = dict(getattr(cds.best.schedule.pins, "partial", None) or {})
    if partial:        # overbook only triggers when the searcher takes it
        ps = sess.lower(cds)
        with pytest.raises(PlanPartitionError, match="overbook"):
            partition_plan(ps.exec_plan, 4, program=ts.program)


def test_mesh_k1_degenerates_bitwise():
    """A one-shard mesh is the single-device plan: same outputs, bit for
    bit, and the executors take the plain (unsharded) path."""
    sess = Session()
    t = sess.trace(workload="cg", n=128, iters=3)
    cd = sess.codesign(t)
    feeds = _jnp_feeds(t.program)
    plain = sess.lower(cd).run(feeds)
    k1 = sess.lower(cd, mesh=1)
    assert k1.sharded is not None and k1.sharded.n_shards == 1
    _bitwise(plain, k1.run(feeds))


@pytest.mark.parametrize("wl,params", [
    ("cg", dict(n=256, iters=4)),
    ("cg_sparse", dict(n=256, iters=4)),
    ("jacobi2d", dict(n=64, sweeps=3)),
    ("power_iteration", dict(n=256, iters=3)),
])
def test_sharded_reference_bitwise(wl, params):
    """The sharded reference oracle simulates the mesh on host (eager
    per-op dispatch over K row blocks) — no devices needed, and bitwise
    against the unsharded oracle by construction."""
    sess = Session()
    t = sess.trace(workload=wl, **params)
    cd = sess.codesign(t)
    feeds = _jnp_feeds(t.program)
    ref = sess.lower(cd).run(feeds)
    for k in (4, 8):
        sharded = sess.lower(cd, mesh=k).run(feeds)
        _bitwise(ref, sharded)


def test_mesh_exchange_sets_golden():
    """The partition derives the paper-shaped exchange structure: spmv/
    matmul operands gather, reductions psum, stencils halo-exchange."""
    sess = Session()
    t = sess.trace(workload="cg", n=256, iters=4)
    sp = sess.lower(sess.codesign(t), mesh=8).sharded
    assert set(sp.gathered) == {"x0", "r0", "p1", "p2", "p3"}
    assert "rs0" in sp.reduced and "pAp0" in sp.reduced
    assert not sp.halo
    tj = sess.trace(workload="jacobi2d", n=64, sweeps=3)
    spj = sess.lower(sess.codesign(tj), mesh=4).sharded
    assert set(spj.halo) == {"u1", "u2", "u3"}
    assert not spj.gathered


def test_per_shard_pins_aggregate_capacity():
    """TABLE 11's crossover: an operator too large for one device's
    explicit region pins once the mesh is wide enough — the sharded
    lowering re-codesigns the global graph at aggregate capacity K·C."""
    sess = Session()
    t = sess.trace(workload="cg", n=512, iters=4)      # A = 1 MiB fp32
    cap = int(0.4 * MiB)
    cd = sess.codesign(t, CodesignConfig(capacity_bytes=cap))
    assert "A" not in cd.best.schedule.pins            # does not fit C
    p8 = sess.lower(cd, mesh=8)
    assert p8.codesigned.capacity_bytes == 8 * cap
    assert "A" in p8.codesigned.best.schedule.pins     # fits K·C
    # and the per-shard plan still degenerates bitwise on the oracle
    feeds = _jnp_feeds(t.program)
    _bitwise(sess.lower(cd).run(feeds), p8.run(feeds))


def test_exec_config_and_deprecation_shims():
    """The consolidated typed-config surface: config= and the legacy
    kwargs produce identical plans; mixing them raises; legacy warns."""
    import warnings
    sess = Session()
    t = sess.trace(workload="cg", n=128, iters=2)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = sess.codesign(t, strategy="default", overbook=0.0)
        assert any(issubclass(x.category, DeprecationWarning) for x in w)
    typed = sess.codesign(t, CodesignConfig(strategy="default"))
    assert legacy.best.schedule.pins == typed.best.schedule.pins
    with pytest.raises(TypeError, match="not both"):
        sess.codesign(t, CodesignConfig(), strategy="default")
    with pytest.raises(TypeError, match="not both"):
        sess.lower(typed, ExecConfig(backend="reference"),
                   backend="reference")
    plan = sess.lower(typed, ExecConfig(mesh=(  # named axis round-trips
        "blocks", 4)))
    assert plan.sharded.axis == "blocks"
    assert "mesh=blocks:4" in plan.plan.notes
    # run(config=) picks the backend; a mesh there is rejected (fixed at
    # lower time)
    feeds = _jnp_feeds(t.program)
    out = plan.run(feeds, config=ExecConfig(backend="reference"))
    _bitwise(out, sess.lower(typed).run(feeds))
    with pytest.raises(ValueError, match="re-lower"):
        plan.run(feeds, config=ExecConfig(mesh=2))


@pytest.mark.slow
def test_sharded_pallas_parity_subprocess():
    """The real distributed path: jit(shard_map) around the single-program
    pallas executable on 8 forced host devices — one trace, one dispatch,
    parity with the unsharded oracle within the documented float32
    tolerance (collectives reassociate reductions)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["CELLO_NO_CACHE"] = "1"
import sys; sys.path.insert(0, "src")
import json
import numpy as np
import jax.numpy as jnp
from repro.api import ExecConfig, Session
from repro.frontends.reference import make_feeds

out = {}
for wl, params in [("cg", dict(n=256, iters=4)),
                   ("cg_sparse", dict(n=256, iters=4)),
                   ("jacobi2d", dict(n=64, sweeps=3))]:
    sess = Session()
    t = sess.trace(workload=wl, **params)
    cd = sess.codesign(t)
    feeds = {k: jnp.asarray(v) for k, v in make_feeds(t.program, 0).items()}
    ref = sess.lower(cd).run(feeds)
    plan = sess.lower(cd, config=ExecConfig(backend="pallas", mesh=8))
    from repro.exec.base import get_backend
    prog = get_backend("pallas").compile(plan)   # the stats live per program
    # device feeds go in laid out as the plan runs them
    import jax
    sh = plan.feed_shardings()
    got = prog({k: jax.device_put(v, sh[k]) for k, v in feeds.items()})
    rel = max(float(np.max(np.abs(np.asarray(got[k]) - np.asarray(ref[k]))
                           / (np.abs(np.asarray(ref[k])) + 1e-6)))
              for k in ref)
    out[wl] = {"rel": rel, "stats": prog.stats}
print(json.dumps(out))
"""
    res = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for wl, r in out.items():
        assert r["rel"] < 2e-3, (wl, r)
        assert r["stats"]["dispatches"] == 1, (wl, r)
        assert r["stats"]["traces"] == 1, (wl, r)


# ---------------------------------------------------------------------------
# an operator fed in place: column-blocked passes on a mesh, the leaves'
# shardings, the refusal of a mis-sharded feed, and the per-dispatch
# counters (4 forced host devices, in a subprocess)
# ---------------------------------------------------------------------------

MESH_FEED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import dataclasses, json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.core.lowering as lowering
from repro import obs
from repro.api import Session
from repro.exec import get_backend
from repro.frontends import make_feeds

N, ITERS, K = 1024, 6, 4
lowering.KERNEL_VMEM_BYTES = 512 << 10       # 128 whole rows do not fit
sess = Session(use_cache=False)
t = sess.trace(workload="cg", n=N, iters=ITERS)
cd = sess.codesign(t)
feeds = make_feeds(t.program, seed=5)
ref = sess.lower(cd, backend="reference", mesh=K).run(
    {k: jnp.asarray(v) for k, v in feeds.items()})
out = {"ref": {k: np.asarray(v).tolist() for k, v in ref.items()}}
for variant in ("cols-divisor", "cols-whole-row"):
    plan = sess.lower(cd, backend="pallas", mesh=K)
    if variant == "cols-whole-row":
        local = plan.sharded.local
        units = tuple(dataclasses.replace(
            u, sp=dataclasses.replace(u.sp, tile_cols=N))
            if u.sp is not None and u.sp.tile_cols else u
            for u in local.units)
        plan = dataclasses.replace(plan, sharded=dataclasses.replace(
            plan.sharded, local=dataclasses.replace(local, units=units)))
    sh = plan.feed_shardings()
    prog = get_backend("pallas").compiled(plan)
    placed = {k: jax.device_put(v, sh[k]) for k, v in feeds.items()}
    got = [prog(placed) for _ in range(2)]
    host = prog(feeds)                       # host arrays: placed by jit
    rec = {"tile_cols": sorted({u.sp.tile_cols for u in
                                plan.sharded.local.units
                                if u.sp is not None and u.sp.tile_cols}),
           "got": {k: np.asarray(v).tolist() for k, v in got[1].items()},
           "host_same": all(np.array_equal(np.asarray(host[k]),
                                           np.asarray(got[1][k]))
                            for k in host),
           "shardings": {k: [str(a) for a in v.spec] for k, v in sh.items()},
           "one_mesh": all(v.mesh is prog.mesh for v in sh.values()),
           "leaves": sorted(nd.name for nd in t.program.leaves()),
           "gathered": len(plan.sharded.gathered),
           "reduced": len(plan.sharded.reduced),
           "scope": prog._scope}
    refused = {}
    for name, bad in (
            ("one device", jnp.asarray(feeds["A"])),
            ("columns", jax.device_put(feeds["A"], NamedSharding(
                prog.mesh, P(None, "shards"))))):
        try:
            prog(dict(placed, A=bad))
            refused[name] = None
        except Exception as e:
            refused[name] = [type(e).__name__, str(e)]
    rec["refused"] = refused
    snap = obs.registry().snapshot()
    rec["counters"] = {
        name: [[c["labels"], c["value"]] for c in snap[name]["cells"]
               if c["labels"].get("scope") == prog._scope]
        for name in ("exec.matvec_tiling", "exec.collective_bytes",
                     "exec.dispatches")}
    out[variant] = rec
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_feed_run():
    res = subprocess.run([sys.executable, "-c", MESH_FEED_SCRIPT],
                         cwd=str(pathlib.Path(__file__).resolve().parents[1]),
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("variant", ["cols-divisor", "cols-whole-row"])
def test_column_blocked_mesh_matches_sharded_reference(mesh_feed_run,
                                                       variant):
    """Column-blocked passes on each shard's row block, fed in place,
    match the bitwise sharded oracle at the float32 tolerances
    (docs/execution_backends.md); a host feed gives the same answer."""
    rec = mesh_feed_run[variant]
    assert rec["tile_cols"] == ([1024] if variant == "cols-whole-row"
                                else [256])
    for k, want in mesh_feed_run["ref"].items():
        np.testing.assert_allclose(np.asarray(rec["got"][k]),
                                   np.asarray(want), rtol=2e-4, atol=1e-5,
                                   err_msg=k)
    assert rec["host_same"]


def test_feed_shardings_name_every_leaf_on_one_mesh(mesh_feed_run):
    rec = mesh_feed_run["cols-divisor"]
    assert sorted(rec["shardings"]) == rec["leaves"] == ["A", "b", "x0"]
    assert all(spec == ["shards"] for spec in rec["shardings"].values())
    assert rec["one_mesh"]


def test_a_mis_sharded_feed_is_refused(mesh_feed_run):
    for variant in ("cols-divisor", "cols-whole-row"):
        refused = mesh_feed_run[variant]["refused"]
        for how, err in refused.items():
            assert err is not None, how
            assert err[0] == "FeedShardingError"
            assert "feed_shardings()['A']" in err[1]


def test_mesh_counters_count_per_dispatch(mesh_feed_run):
    """Three dispatches: ``exec.matvec_tiling`` counts each one's 7
    column-blocked matvecs, ``exec.collective_bytes`` the bytes each
    shard receives (the other 3 shards' rows of every gathered vector,
    their partials of every reduction); refused feeds dispatch nothing."""
    rec = mesh_feed_run["cols-divisor"]
    counters = {name: {tuple(sorted(lab.items())): v for lab, v in cells}
                for name, cells in rec["counters"].items()}
    scope = ("scope", rec["scope"])
    base = (("backend", "pallas"), scope)

    def value(name, **labels):
        key = tuple(sorted(base + tuple(labels.items())))
        return counters[name].get(key, 0)
    assert value("exec.dispatches") == 3
    assert value("exec.matvec_tiling", tiling="blocked") == 3 * 7
    assert value("exec.matvec_tiling", tiling="rows") == 0
    assert value("exec.collective_bytes", op="all_gather") == \
        3 * rec["gathered"] * (1024 - 256) * 4
    assert value("exec.collective_bytes", op="psum") == \
        3 * rec["reduced"] * 3 * 4
    assert rec["gathered"] == 7


def test_feed_shardings_need_a_device_mesh():
    sess = Session()
    t = sess.trace(workload="cg", n=128, iters=2)
    cd = sess.codesign(t)
    for plan in (sess.lower(cd, backend="pallas"),
                 sess.lower(cd, backend="pallas", mesh=1),
                 sess.lower(cd, mesh=4)):           # reference: simulated
        with pytest.raises(ValueError, match="mesh"):
            plan.feed_shardings()
