"""The four-chip cell ``gp-dense-64k.mesh4`` at a size a CPU holds: its
operator built in place per shard on four forced host devices, its whole
run through the harness, its precision controls, and its readers."""
import json
import os
import subprocess
import sys

import pytest

import cells
import conftest
import work
from conftest import BENCH, REPO

#: the four-chip cell has no one-chip stand-in among the small cells; its
#: metrics name this one, which the checkouts of the tests do not hold
conftest.TWIN.setdefault("gp-dense-64k.mesh4", "mesh-small.solve")

SCRIPT = r"""
import json, os, pathlib, sys, tempfile
bench, repo = pathlib.Path(sys.argv[1]), pathlib.Path(sys.argv[2])
sys.path[:0] = [str(bench), str(bench / "tests"), str(repo / "src")]
import numpy as np
import jax, jax.numpy as jnp
import cells, conftest, inputs, precision_control, run
from repro.api import Session

conftest.TWIN.setdefault("gp-dense-64k.mesh4", "mesh-small.solve")
config = json.loads((bench / "configs" / "gp-dense-64k.json").read_text())
config.update(name="mesh-small", params={"n": 512, "iters": 50})
kind = cells._module(bench, "operators", "rbf_kernel_sharded")
out = {"devices": len(jax.devices())}

a = kind.build(config, 2**40 + 5)["A"]
host = np.asarray(a)
u = np.asarray(jax.random.uniform(inputs.device_key(2**40 + 5, "operator"),
                                  (512, 8), jnp.float32, -3 ** 0.5,
                                  3 ** 0.5), np.float64)
d2 = ((u[:, None, :] - u[None, :, :]) ** 2).sum(-1)
want = np.exp(-d2 / 2.0) + 0.1 * np.eye(512)
out["formula_err"] = float(np.max(np.abs(host - want)))
out["symmetric"] = bool(np.array_equal(host, host.T))
out["shard_rows"] = sorted((s.index[0].start, s.data.shape[0], s.device.id)
                           for s in a.addressable_shards)

plan = Session(use_cache=False).trace(workload="cg", n=512, iters=50) \
    .analyze().codesign().lower(backend="pallas", mesh=4)
sh = plan.feed_shardings()
out["plan_sharding_is_kinds"] = sh["A"] == kind.row_sharding(config)
again = kind.build(config, 2**40 + 5, sh["A"])["A"]
out["rebuild_bitwise"] = bool(np.array_equal(np.asarray(again), host))
mv = kind.reference_matvec(config, 2**40 + 5)
v = np.random.default_rng(0).standard_normal((512, 3))
out["reference_err"] = float(np.max(np.abs(mv(v) - host.astype(np.float64) @ v)))

root = conftest.make_checkout(
    pathlib.Path(tempfile.mkdtemp()) / "co",
    extra_cells={"mesh-small.solve": ("mesh-small", "solve-closed1-mesh")},
    extra_configs={"mesh-small": config})
bench_json = json.loads((root / "BENCHMARK.json").read_text())
for w in bench_json["workloads"]:
    if w["name"] == "mesh-small.solve":
        w["chips"] = 4
(root / "BENCHMARK.json").write_text(json.dumps(bench_json))
cell = cells.load(root, "mesh-small.solve")
out["per_layer"] = sorted(m["name"] for m in cell.per_layer)
out["end_to_end"] = sorted(m["name"] for m in cell.end_to_end)
rc = run.main(["--workload", "mesh-small.solve", "--seed", str(2**33 + 7),
               "--seconds", "1", "--trace", "0"], root=root, allow_cpu=True)
out["rc"] = rc
out["controls"] = precision_control.main(
    ["--workload", "mesh-small.solve", "--seeds", "3"], root=root)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("jc")))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(BENCH),
                           str(REPO)], env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    result = json.loads(next(x for x in lines if x.startswith("RESULT "))[7:])
    result["line"] = json.loads(next(x for x in lines
                                     if x.startswith('{"correct"')))
    return result


def test_each_shard_builds_its_rows_of_the_formula(mesh_run):
    assert mesh_run["devices"] == 4
    assert [r[:2] for r in mesh_run["shard_rows"]] == [
        [0, 128], [128, 128], [256, 128], [384, 128]]
    assert len({r[2] for r in mesh_run["shard_rows"]}) == 4
    assert mesh_run["formula_err"] < 1e-6
    assert mesh_run["symmetric"]


def test_the_reference_rebuilds_the_very_matrix(mesh_run):
    assert mesh_run["plan_sharding_is_kinds"]
    assert mesh_run["rebuild_bitwise"]
    assert mesh_run["reference_err"] < 1e-9


def test_the_cell_runs_through_the_harness(mesh_run):
    assert mesh_run["rc"] == 0
    line = mesh_run["line"]
    assert line["correct"] is True, line["compared"]
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"setup_s", "solve_ms.dense"}
    assert set(mesh_run["per_layer"]) >= {
        "codesign_s", "warmup_s", "wide_matvec_roofline.mesh",
        "solve_roofline.mesh", "collective_ms_per_solve.mesh",
        "collective_mib_per_solve.mesh", "device_idle_pct.mesh"}


def test_the_bfloat16_control_reads_over_a_limit(mesh_run):
    bf16, default = mesh_run["controls"]
    assert bf16["side"] == "bf16" and default["side"] == "default"
    assert any(c["value"] > c["limit"] for c in bf16["compared"].values())
    # on a CPU the default precision is float32's own: within the limits
    assert all(c["value"] <= c["limit"] for c in default["compared"].values())


def _ctx(**kw):
    config = json.loads((BENCH / "configs" / "gp-dense-64k.json")
                        .read_text())
    kind = cells._module(BENCH, "operators", "rbf_kernel_sharded")
    ctx = {"config": config, "peaks": work.peaks_for("TPU v5 lite"),
           "work": work.solve_work(config, kind), "solves": 100,
           "trace": {"busy_s": 31.0, "window_s": 32.0, "device_ops": [
               ["cello_wide_Ap1.3", 29.0], ["cello_wide_Ap0.1", 0.6],
               ["cello_wide_Ax0.1", 0.6], ["all-gather-start.2", 0.3],
               ["all-reduce.5", 0.2], ["psum.42", 0.1],
               ["cello_stream_x2.1", 0.1]]}}
    ctx.update(kw)
    return ctx


def _read(name, ctx):
    return cells.metric_reader(name, BENCH)(ctx)


def test_mesh_rooflines_read_per_chip():
    """About 310 ms a solve on each chip for its 4 GiB row block, read 51
    times: under 100% of one chip's roofline, and not the four times
    higher the one-chip reader would give."""
    ctx = _ctx()
    wide = _read("wide_matvec_roofline.mesh", ctx)
    whole = _read("solve_roofline.mesh", ctx)
    assert 80.0 < wide < 100.0 and 80.0 < whole < 100.0
    assert _read("solve_roofline.dense", ctx) > 300.0
    for name in ("wide_matvec_roofline.mesh", "solve_roofline.mesh",
                 "collective_ms_per_solve.mesh"):
        assert _read(name, _ctx(trace=None)) is None


def test_collective_time_is_read_from_the_ten_largest_ops():
    """By opcode, or by the primitive's name a TPU trace gives
    ``lax.psum``'s all-reduce (``psum.<k>``)."""
    assert _read("collective_ms_per_solve.mesh", _ctx()) == \
        pytest.approx(6.0)
    quiet = _ctx()
    quiet["trace"] = dict(quiet["trace"], device_ops=[
        ["cello_wide_Ap1.3", 29.0]])
    assert _read("collective_ms_per_solve.mesh", quiet) is None
    assert _read("wide_matvec_roofline.mesh", dict(
        quiet, trace=dict(quiet["trace"], device_ops=[]))) is None


def test_collective_mib_per_solve(monkeypatch):
    from repro import obs
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "registry", lambda: reg)
    assert _read("collective_mib_per_solve.mesh", {}) is None
    reg.counter("exec.dispatches").inc(4, backend="pallas", scope="p-1")
    moved = reg.counter("exec.collective_bytes")
    moved.inc(4 * 3 * 2**20, backend="pallas", op="all_gather", scope="p-1")
    moved.inc(4 * 2**19, backend="pallas", op="psum", scope="p-1")
    assert _read("collective_mib_per_solve.mesh", {}) == pytest.approx(3.5)
