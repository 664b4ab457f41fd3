"""Smoke run of the co-designed solver path on one TPU chip (or four).

    python chip_smoke.py            # one chip: dense CG, sparse CG, serving
    python chip_smoke.py --mesh 4   # four chips: dense CG on a 4-device mesh

Every phase goes through the entry points a user calls
(``Session().trace(...).analyze().codesign().lower(backend="pallas")`` and
``repro.serve.Server``), runs in this one process on the accelerator, and
is checked against an independent float64 NumPy solve built from the same
``make_feeds`` seed.  Nothing may finish on the CPU, in Pallas interpret
mode, or through the ``reference`` backend: the script refuses to start
without a TPU and asserts which backend answered.

Timings printed here are smoke timings of one run each — set-up included,
no warm-up discipline — not benchmark numbers.  The last line of standard
output is one JSON object naming the device; any failed phase exits
non-zero without printing it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Tolerances of the float64 comparisons, as (x, residual): the device
#: solution's relative distance to the float64 solution, and how far its
#: true residual ||b - A x|| / ||b|| may sit above the float64 one.  CG
#: runs in fp32 (unit roundoff 6e-8) and every iteration re-rounds n-term
#: dot products and update vectors.  The dense matvec runs on the MXU at
#: Precision.HIGHEST (fp32-accurate; XLA's default would round operands
#: to bf16, 4e-3 relative); the sparse one (the Laplacian, on the diagonal
#: layout) sums each row's fp32 products with shifted x on the VPU.
#: * dense cg (kappa ~ 5, converged after 32 iterations): fp32 sits at
#:   its floor — an XLA:CPU fp32 solve is 5.7e-7 from float64 with a true
#:   residual of 5.9e-7;
#: * sparse cg (1024^2 Laplacian, kappa ~ 4e5, 32 iterations: far from
#:   converged) amplifies rounding — the XLA:CPU fp32 solve is 6.1e-3 from
#:   float64 and its residual moves by 1.7e-3; the bounds allow 5x and 3x.
TOL = {"cg": (1e-4, 1e-5), "cg_sparse": (3e-2, 5e-3), "serve": (1e-4, 1e-5)}


def _log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------------
# float64 host references (independent of the code under test)
# --------------------------------------------------------------------------

def host_cg(matvec, b: np.ndarray, iters: int) -> np.ndarray:
    """Plain CG from x0 = 0 in float64, the same recurrence as the
    ``cg`` / ``cg_sparse`` workloads."""
    x = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rs = r @ r
    for _ in range(iters):
        ap = matvec(p)
        alpha = rs / (p @ ap)
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = r @ r
        p = r + (rs_new / rs) * p
        rs = rs_new
    return x


def csr_matvec(indptr, indices, data):
    rows = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))

    def mv(v):
        return np.bincount(rows, weights=data * v[indices],
                           minlength=len(indptr) - 1)
    return mv


def check_solve(name: str, x_dev, matvec, b: np.ndarray, iters: int,
                tol) -> dict:
    """Compare a device solve with the float64 host solve."""
    tol_x, tol_res = tol
    x_dev = np.asarray(x_dev, np.float64)
    assert np.all(np.isfinite(x_dev)), f"{name}: non-finite solution"
    x_ref = host_cg(matvec, b, iters)
    err = float(np.linalg.norm(x_dev - x_ref) / np.linalg.norm(x_ref))
    bn = np.linalg.norm(b)
    res_dev = float(np.linalg.norm(b - matvec(x_dev)) / bn)
    res_ref = float(np.linalg.norm(b - matvec(x_ref)) / bn)
    ok = err <= tol_x and res_dev <= res_ref + tol_res
    _log(f"  [{name}] rel_err_vs_fp64={err:.3e} (tol {tol_x:g}) "
         f"true_residual={res_dev:.6e} (fp64 host {res_ref:.6e}, "
         f"tol +{tol_res:g}) -> {'ok' if ok else 'FAIL'}")
    assert ok, f"{name}: outside tolerance {tol}"
    return {"rel_err": err, "residual": res_dev, "residual_fp64": res_ref}


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def _units(plan) -> str:
    """Pallas against jnp execution units, with each jnp unit's reason
    (selected at lowering, as ``explain()`` shows it)."""
    units = plan.exec_plan.units
    reasons = {}
    for u in units:
        if u.kind == "jnp":
            why = plan.group_kernels[u.groups[0]].reason.split(": ", 1)[-1]
            reasons[why] = reasons.get(why, 0) + 1
    pallas = sum(u.kind in ("stream", "block") for u in units)
    return (f"pallas_units={pallas} jnp_units={len(units) - pallas} "
            f"jnp_reasons={reasons}")


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def _timed_runs(plan, feeds):
    """First run (compile + execute) and a second, warm run."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(plan.run(feeds))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(plan.run(feeds))
    warm = time.perf_counter() - t0
    return out, first, warm


def solve_phase(workload: str, iters: int, mesh=None, **params) -> dict:
    import jax
    from repro.api import Session
    from repro.frontends import make_feeds

    t0 = time.perf_counter()
    sess = Session(use_cache=False)
    traced = sess.trace(workload=workload, iters=iters, **params)
    plan = (traced.analyze().codesign()
            .lower(backend="pallas", mesh=mesh))
    setup = time.perf_counter() - t0
    feeds = make_feeds(traced.program, seed=0)
    _log(f"[{workload}] {_units(plan)} codesign+lower_s={setup:.2f}")
    out, first, warm = _timed_runs(plan, feeds)
    x = out[f"x{iters}"]
    _log(f"  smoke timings (not benchmarks): first_run_s={first:.3f} "
         f"(compile + run) warm_run_s={warm:.3f} "
         f"compile_s~={first - warm:.3f}")
    # the peak is the process's high-water mark, earlier phases included
    for d in sorted(x.sharding.device_set, key=lambda d: d.id):
        _log(f"  device {d.id} peak_bytes_in_use={_peak_bytes(d)}")
    if "A" in feeds:
        A = feeds["A"].astype(np.float64)
        matvec = A.__matmul__
    else:
        matvec = csr_matvec(feeds["A.indptr"], feeds["A.indices"],
                            feeds["A.data"].astype(np.float64))
    rec = check_solve(workload, x, matvec, feeds["b"].astype(np.float64),
                      iters, TOL[workload])
    if mesh:
        devs = {s.device for s in x.addressable_shards}
        assert len(devs) == mesh, f"solution spans {len(devs)} devices"
        _log(f"  solution shards on {len(devs)} distinct devices")
    return {**rec, "first_run_s": first, "warm_run_s": warm}


def serve_phase(n: int = 4096, iters: int = 16, requests: int = 16) -> dict:
    from repro.api import Session
    from repro.frontends import build_workload, make_feeds
    from repro.serve import ServeConfig, Server, request

    srv = Server(config=ServeConfig(max_batch_size=8, fallback=None,
                                    autostart=False),
                 session=Session(use_cache=False))
    futs = [srv.submit(request("cg", n=n, iters=iters, backend="pallas",
                               seed=s)) for s in range(requests)]
    t0 = time.perf_counter()
    srv.start()
    results = [f.result(timeout=900) for f in futs]
    wall = time.perf_counter() - t0
    st = srv.stats()
    srv.close()
    for res in results:
        assert res.backend == "pallas" and not res.degraded, \
            (res.backend, res.degraded)
    (label, b), = st["buckets"].items()
    assert b["dispatches"] == b["batches"], (b["dispatches"], b["batches"])
    _log(f"[serve] {label} requests={b['requests']} batches={b['batches']} "
         f"dispatches={b['dispatches']} sizes={b['batch_sizes']}")
    _log(f"  smoke timings (not benchmarks): wall_s={wall:.3f} for "
         f"{requests} requests (plan build + compile included)")

    program = build_workload("cg", n=n, iters=iters)
    A = make_feeds(program, seed=0, only=["A"])["A"].astype(np.float64)
    worst = 0.0
    for s, res in enumerate(results):
        b_s = make_feeds(program, seed=s, only=["b"])["b"]
        rec = check_solve(f"serve seed={s}", res.outputs[f"x{iters}"],
                          A.__matmul__, b_s.astype(np.float64), iters,
                          TOL["serve"])
        worst = max(worst, rec["rel_err"])
    return {"wall_s": wall, "worst_rel_err": worst}


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", type=int, default=None, metavar="K",
                    help="run only dense CG lowered onto a K-device mesh")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _log(f"no TPU: jax reports platform {dev.platform!r}")
        return 2
    if os.environ.get("CELLO_PALLAS_INTERPRET", "").strip():
        _log("CELLO_PALLAS_INTERPRET is set; the smoke run compiles "
             "every kernel through Mosaic")
        return 2
    from repro.exec.pallas import use_interpret
    from repro.runtime import enable_compile_cache
    assert not use_interpret()
    cache = enable_compile_cache()
    n_dev = len(jax.devices())
    _log(f"device_kind={dev.device_kind} devices={n_dev} "
         f"compile_cache={cache}")

    if args.mesh:
        assert n_dev >= args.mesh, f"need {args.mesh} devices, have {n_dev}"
        solve_phase("cg", 32, mesh=args.mesh, n=8192)
        count = args.mesh
    else:
        solve_phase("cg", 32, n=8192)
        solve_phase("cg_sparse", 32, n=1 << 20, pattern="laplacian5")
        serve_phase()
        count = 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
