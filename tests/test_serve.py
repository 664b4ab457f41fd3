"""Serving-layer tests: batched parity, routing, coalescing, concurrency.

Batched parity policy (docs/serving.md):

* ``reference`` + gather/segment programs (``cg_sparse``): a vmapped batch
  matches a loop of jitted single-request solves (``BatchedPlan.run_one``)
  **bit-for-bit** at fp32 and fp64 — every vmap lane lowers to the same
  per-lane gather/segment arithmetic.
* ``reference`` + dense programs (``cg``): the batched matvec lowers to a
  batched contraction whose summation order may differ from the unbatched
  one in the last ulps — parity within SERVE_RTOL/SERVE_ATOL (fp32) and
  SERVE_RTOL64/SERVE_ATOL64 (fp64), orders of magnitude tighter than any
  algorithmic difference.
* ``pallas``: within the backend's documented reduction-reassociation
  tolerances (rtol=2e-4 / atol=1e-5 float32) against the reference oracle.
"""
import json
import pathlib
import threading
import time

import numpy as np
import pytest

from repro.api import Session
from repro.api.cache import CodesignCache
from repro.exec import Executor
from repro.frontends import make_feeds
from repro.serve import (BatchedPlan, Overloaded, PlanRouter, ServeConfig,
                         Server, ServerClosed, SolveRequest, density_bucket,
                         request)
from repro.testing import faults

# batched-vs-single reference tolerances (see module docstring)
SERVE_RTOL, SERVE_ATOL = 1e-4, 1e-5
SERVE_RTOL64, SERVE_ATOL64 = 1e-9, 1e-12
# pallas documented float32 policy (docs/execution_backends.md)
PALLAS_RTOL, PALLAS_ATOL = 2e-4, 1e-5


def _plan(tmp_path, workload, **params):
    traced = Session(cache_dir=tmp_path).trace(workload=workload, **params)
    return traced, traced.codesign().lower()


def _batch_feeds(bp, program, n, dtype=None):
    shared = make_feeds(program, seed=0, dtype=dtype,
                        only=bp.shared_leaves)
    per_req = [make_feeds(program, seed=s, dtype=dtype,
                          only=bp.batched_leaves) for s in range(n)]
    return shared, per_req


# ---------------------------------------------------------------------------
# BatchedPlan parity (satellite: dense CG + cg_sparse at fp32 and fp64)
# ---------------------------------------------------------------------------

class TestBatchedParity:
    def test_sparse_bitwise_fp32(self, tmp_path):
        traced, plan = _plan(tmp_path, "cg_sparse", n=64, iters=2)
        bp = plan.batched()
        shared, per_req = _batch_feeds(bp, traced.program, 4)
        outs = bp.run_many(per_req, shared)
        for r, out in zip(per_req, outs):
            one = bp.run_one({**shared, **r})
            for k in one:
                np.testing.assert_array_equal(np.asarray(one[k]),
                                              np.asarray(out[k]))

    def test_sparse_bitwise_fp64(self, tmp_path):
        import jax
        with jax.enable_x64(True):
            traced, plan = _plan(tmp_path, "cg_sparse", n=64, iters=2)
            bp = plan.batched()
            shared, per_req = _batch_feeds(bp, traced.program, 4,
                                           dtype=np.float64)
            outs = bp.run_many(per_req, shared)
            assert np.asarray(outs[0]["x2"]).dtype == np.float64
            for r, out in zip(per_req, outs):
                one = bp.run_one({**shared, **r})
                for k in one:
                    np.testing.assert_array_equal(np.asarray(one[k]),
                                                  np.asarray(out[k]))

    @pytest.mark.parametrize("fp64", [False, True], ids=["fp32", "fp64"])
    def test_dense_cg_close(self, tmp_path, fp64):
        import jax
        import contextlib
        ctx = (jax.enable_x64(True) if fp64
               else contextlib.nullcontext())
        dtype = np.float64 if fp64 else None
        rtol, atol = ((SERVE_RTOL64, SERVE_ATOL64) if fp64
                      else (SERVE_RTOL, SERVE_ATOL))
        with ctx:
            traced, plan = _plan(tmp_path, "cg", n=96, iters=2)
            bp = plan.batched()
            shared, per_req = _batch_feeds(bp, traced.program, 4,
                                           dtype=dtype)
            outs = bp.run_many(per_req, shared)
            for r, out in zip(per_req, outs):
                # vs the jitted single-request twin of one vmap lane...
                one = bp.run_one({**shared, **r})
                # ...and vs the eager per-request plan.run() loop
                eager = plan.run({**shared, **r})
                for k in one:
                    np.testing.assert_allclose(
                        np.asarray(out[k]), np.asarray(one[k]),
                        rtol=rtol, atol=atol)
                    np.testing.assert_allclose(
                        np.asarray(out[k]), np.asarray(eager[k]),
                        rtol=rtol, atol=atol)

    @pytest.mark.parametrize("workload,params",
                             [("cg", dict(n=96, iters=2)),
                              ("cg_sparse", dict(n=64, iters=2))],
                             ids=["cg", "cg_sparse"])
    def test_pallas_batched_within_tolerance(self, tmp_path, workload,
                                             params):
        traced = Session(cache_dir=tmp_path).trace(workload=workload,
                                                   **params)
        plan = traced.codesign().lower(backend="pallas")
        bp = plan.batched()
        assert bp.backend == "pallas"
        shared, per_req = _batch_feeds(bp, traced.program, 4)
        outs = bp.run_many(per_req, shared)
        ref = traced.codesign().lower(backend="reference")
        for r, out in zip(per_req, outs):
            want = ref.run({**shared, **r})
            for k in want:
                np.testing.assert_allclose(
                    np.asarray(out[k]), np.asarray(want[k]),
                    rtol=PALLAS_RTOL, atol=PALLAS_ATOL)


class TestBatchedPlanMechanics:
    def test_one_dispatch_per_batch_and_trace_reuse(self, tmp_path):
        traced, plan = _plan(tmp_path, "cg", n=64, iters=2)
        bp = plan.batched()
        shared, per_req = _batch_feeds(bp, traced.program, 8)
        bp.run_many(per_req, shared)
        assert bp.stats == {"traces": 1, "dispatches": 1}
        bp.run_many(per_req, shared)       # same batch size: no retrace
        assert bp.stats == {"traces": 1, "dispatches": 2}
        bp.run_many(per_req[:4], shared)   # new padded size: one retrace
        assert bp.stats == {"traces": 2, "dispatches": 3}

    def test_padding_matches_unpadded(self, tmp_path):
        traced, plan = _plan(tmp_path, "cg_sparse", n=64, iters=2)
        bp = plan.batched()
        shared, per_req = _batch_feeds(bp, traced.program, 5)
        padded = bp.run_many(per_req, shared)            # 5 -> 8 lanes
        assert len(padded) == 5
        unpadded = bp.run_many(per_req, shared, pad=False)
        for p, u in zip(padded, unpadded):
            for k in p:
                np.testing.assert_array_equal(p[k], u[k])

    def test_shape_validation(self, tmp_path):
        traced, plan = _plan(tmp_path, "cg", n=64, iters=2)
        bp = plan.batched()
        shared, per_req = _batch_feeds(bp, traced.program, 2)
        feeds = dict(shared)
        for n in bp.batched_leaves:
            feeds[n] = np.stack([r[n] for r in per_req])
        with pytest.raises(ValueError, match="unbatched"):
            bad = dict(feeds)
            bad["A"] = np.stack([shared["A"]] * 2)     # batched operator
            bp.run_batch(bad)
        with pytest.raises(ValueError, match="must be batched"):
            bad = dict(feeds)
            bad["b"] = per_req[0]["b"]                 # unbatched input
            bp.run_batch(bad)
        with pytest.raises(ValueError, match="inconsistent batch"):
            bad = dict(feeds)
            bad["x0"] = np.stack([per_req[0]["x0"]] * 3)
            bp.run_batch(bad)
        with pytest.raises(KeyError, match="missing leaf"):
            bad = dict(feeds)
            del bad["b"]
            bp.run_batch(bad)

    def test_batched_convenience_and_leaf_split(self, tmp_path):
        traced, plan = _plan(tmp_path, "cg_sparse", n=64, iters=2)
        bp = plan.batched()
        assert isinstance(bp, BatchedPlan)
        # CSR sub-leaves are operator (shared); b/x0 are inputs (batched)
        assert set(bp.batched_leaves) == {"b", "x0"}
        assert all(n.startswith("A.") for n in bp.shared_leaves)


# ---------------------------------------------------------------------------
# router: bucket keys, density decades, LRU
# ---------------------------------------------------------------------------

class TestRouter:
    def test_default_params_canonicalize(self, tmp_path):
        r = PlanRouter(session=Session(cache_dir=tmp_path))
        k1 = r.bucket(request("cg_sparse", n=64))
        k2 = r.bucket(request("cg_sparse", n=64, pattern="laplacian5",
                              iters=4))
        assert k1 == k2
        assert k1.density == "laplacian5"
        assert "laplacian5" in k1.label

    def test_density_decade_bucketing(self, tmp_path):
        r = PlanRouter(session=Session(cache_dir=tmp_path))
        ks = [r.bucket(request("cg_sparse", n=64, pattern="random",
                               density=d))
              for d in (0.0008, 0.001, 0.0012)]
        assert len(set(ks)) == 1
        assert dict(ks[0].params)["density"] == 0.001
        far = r.bucket(request("cg_sparse", n=64, pattern="random",
                               density=0.01))
        assert far != ks[0]

    def test_density_bucket_values(self):
        assert density_bucket(0.001) == 0.001
        assert density_bucket(0.0008) == 0.001
        assert density_bucket(0.5) == 1.0
        for bad in (0.0, -1.0, 1.5):
            with pytest.raises(ValueError):
                density_bucket(bad)

    def test_invalid_requests_raise(self, tmp_path):
        r = PlanRouter(session=Session(cache_dir=tmp_path))
        with pytest.raises(KeyError, match="unknown HPC workload"):
            r.bucket(request("nope"))
        with pytest.raises(TypeError):
            r.bucket(request("cg", n=64, bogus=1))
        with pytest.raises(ValueError, match="float dtype"):
            request("cg", n=64, dtype="int32")

    def test_lru_bounded_and_counted(self, tmp_path):
        r = PlanRouter(session=Session(cache_dir=tmp_path), max_plans=2)
        keys = [r.bucket(request("cg", n=n, iters=2)) for n in (32, 48, 64)]
        r.plan_for(keys[0])
        r.plan_for(keys[0])                      # hit
        r.plan_for(keys[1])
        r.plan_for(keys[2])                      # evicts keys[0]
        st = r.stats()
        assert st["plans_cached"] == 2
        assert st["evictions"] == 1
        assert st["buckets"][keys[0].label]["cache_hits"] == 1
        assert st["buckets"][keys[0].label]["cache_misses"] == 1
        r.plan_for(keys[0])                      # cold again: miss
        assert r.stats()["buckets"][keys[0].label]["cache_misses"] == 2

    def test_request_feeds_overlay(self, tmp_path):
        r = PlanRouter(session=Session(cache_dir=tmp_path))
        entry = r.plan_for(r.bucket(request("cg", n=64, iters=2)))
        b = np.ones(64, np.float64)
        feeds = r.request_feeds(entry, request("cg", n=64, iters=2,
                                               feeds={"b": b}))
        assert feeds["b"].dtype == np.float32        # cast to bucket dtype
        np.testing.assert_array_equal(feeds["b"], np.ones(64, np.float32))
        with pytest.raises(KeyError, match="shared operator"):
            r.request_feeds(entry, request(
                "cg", n=64, iters=2,
                feeds={"A": np.eye(64, dtype=np.float32)}))
        with pytest.raises(ValueError, match="expected shape"):
            r.request_feeds(entry, request("cg", n=64, iters=2,
                                           feeds={"b": np.ones(5)}))


# ---------------------------------------------------------------------------
# server: coalescing, one dispatch per batch, stats, errors
# ---------------------------------------------------------------------------

class TestServer:
    def test_smoke_32_mixed_buckets_one_dispatch_per_batch(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path),
                     max_batch_size=16, autostart=False)
        futs = []
        for s in range(16):
            futs.append(srv.submit(request("cg", n=64, iters=2, seed=s)))
            futs.append(srv.submit(request("cg_sparse", n=64, iters=2,
                                           seed=s)))
        srv.start()
        results = [f.result(timeout=300) for f in futs]
        srv.close()
        assert all(r.batch_size == 16 for r in results)
        assert all(r.residual is not None and np.isfinite(r.residual)
                   for r in results)
        st = srv.stats()
        assert st["requests"] == 32
        assert st["batches"] == 2
        assert st["queue_depth"] == 0
        assert st["plans_cached"] == 2
        assert len(st["buckets"]) == 2
        for b in st["buckets"].values():
            assert b["requests"] == 16
            assert b["batches"] == 1
            # the one-dispatch-per-coalesced-batch guarantee, via the
            # PR-4-style executable counters
            assert b["dispatches"] == b["batches"] == 1
            assert b["traces"] == 1
            assert b["batch_sizes"] == {16: 1}
            assert b["cache_misses"] == 1

    def test_max_batch_size_splits_bursts(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path),
                     max_batch_size=8, autostart=False)
        futs = [srv.submit(request("cg", n=64, iters=2, seed=s))
                for s in range(20)]
        srv.start()
        sizes = sorted(f.result(timeout=300).batch_size for f in futs)
        srv.close()
        assert sizes == [4] * 4 + [8] * 16
        (bucket,) = srv.stats()["buckets"].values()
        assert bucket["batches"] == 3
        assert bucket["dispatches"] == 3
        assert bucket["batch_sizes"] == {8: 2, 4: 1}

    def test_max_wait_coalesces_trickle(self, tmp_path):
        # 4 requests submitted while the worker is already waiting: far
        # below max_batch_size, so only the (generous) max-wait deadline
        # can close the batch — all 4 must ride in one dispatch
        srv = Server(session=Session(cache_dir=tmp_path),
                     max_batch_size=16, max_wait_us=500_000)
        futs = [srv.submit(request("cg", n=64, iters=2, seed=s))
                for s in range(4)]
        results = [f.result(timeout=300) for f in futs]
        srv.close()
        assert [r.batch_size for r in results] == [4, 4, 4, 4]
        (bucket,) = srv.stats()["buckets"].values()
        assert bucket["batches"] == 1

    def test_round_robin_no_starvation(self, tmp_path):
        """Two hot buckets + one cold bucket all make progress: under
        ``policy="round_robin"`` the cold bucket is served within the
        first scheduling cycle instead of waiting out both hot backlogs
        (which is what ``oldest`` does when the hot requests were queued
        first)."""
        srv = Server(session=Session(cache_dir=tmp_path),
                     max_batch_size=2, max_wait_us=0, autostart=False,
                     policy="round_robin")
        order, lock = [], threading.Lock()

        def tag(label):
            def cb(_f, label=label):
                with lock:
                    order.append(label)
            return cb

        futs = []
        for s in range(6):                             # hot bucket 1
            f = srv.submit(request("cg", n=64, iters=2, seed=s))
            f.add_done_callback(tag("h1"))
            futs.append(f)
        for s in range(6):                             # hot bucket 2
            f = srv.submit(request("cg", n=128, iters=2, seed=s))
            f.add_done_callback(tag("h2"))
            futs.append(f)
        cold = srv.submit(request("cg_sparse", n=64, iters=2, seed=0))
        cold.add_done_callback(tag("cold"))
        futs.append(cold)
        srv.start()
        results = [f.result(timeout=300) for f in futs]
        srv.close()
        assert all(np.isfinite(r.residual) for r in results)
        # one full cycle = one batch (2 requests) per hot bucket, then the
        # cold one; under "oldest" the cold request would complete last
        assert order.index("cold") <= 4, order
        assert {"h1", "h2", "cold"} <= set(order[:5]), order

    def test_policy_validation(self, tmp_path):
        with pytest.raises(ValueError, match="unknown policy"):
            Server(session=Session(cache_dir=tmp_path),
                   autostart=False, policy="fifo")

    def test_execution_error_propagates_to_futures(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), autostart=False)
        fut = srv.submit(request("cg", n=64, iters=2,
                                 feeds={"b": np.ones(3)}))   # bad shape
        ok = srv.submit(request("cg", n=64, iters=2, seed=1))
        srv.start()
        # the bad feed poisons only its own batch
        with pytest.raises(ValueError, match="expected shape"):
            fut.result(timeout=300)
        with pytest.raises(ValueError):
            ok.result(timeout=300)     # same batch: shares the failure
        srv.close()
        after = Server(session=Session(cache_dir=tmp_path))
        res = after.solve(request("cg", n=64, iters=2, seed=1))
        after.close()
        assert np.isfinite(res.residual)

    def test_submit_side_validation_and_close(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), autostart=False)
        with pytest.raises(KeyError):
            srv.submit(request("nope"))          # raises in the caller
        pending = srv.submit(request("cg", n=64, iters=2))
        srv.close(flush=False)                   # never started: dropped
        with pytest.raises(RuntimeError, match="closed"):
            pending.result(timeout=10)
        with pytest.raises(RuntimeError, match="closed"):
            srv.submit(request("cg", n=64, iters=2))

    def test_context_manager_solves(self, tmp_path):
        with Server(session=Session(cache_dir=tmp_path)) as srv:
            res = srv.solve(request("cg_sparse", n=64, iters=2, seed=3))
        assert res.batch_size == 1
        assert "cg_sparse" in res.bucket
        assert set(res.outputs) == {"x2", "r2"}
        assert res.residual == pytest.approx(
            float(np.linalg.norm(res.outputs["r2"])))


# ---------------------------------------------------------------------------
# concurrency: disk-cache writers, compile cache, trace memo
# ---------------------------------------------------------------------------

class TestConcurrency:
    def test_codesign_cache_racing_writers(self, tmp_path):
        sess = Session(cache_dir=tmp_path)
        res = sess.trace(workload="cg", n=32, iters=2).codesign().result
        cache = CodesignCache(tmp_path / "race")
        key = cache.key(probe="race")
        seen, errors = [], []

        def racer():
            try:
                for _ in range(20):
                    cache.put(key, res)
                    got = cache.get(key)
                    # readers see a complete entry or a miss — never torn
                    if got is not None:
                        seen.append(got.best.metrics.time_s)
            except Exception as e:              # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=racer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert seen and set(seen) == {res.best.metrics.time_s}
        final = cache.get(key)
        assert final is not None
        assert final.best.schedule.order == res.best.schedule.order

    def test_executor_compiles_once_under_race(self, tmp_path):
        traced, plan = _plan(tmp_path, "cg", n=32, iters=2)
        feeds = make_feeds(traced.program, seed=0)
        compiles = []

        class Counting(Executor):
            name = "counting-test"

            def compile(self, p):
                compiles.append(threading.get_ident())
                time.sleep(0.05)        # widen the race window
                from repro.exec.reference import execute_plan
                return lambda f: execute_plan(p.trace.program, feeds=f)

        ex = Counting()
        barrier = threading.Barrier(6)

        def run():
            barrier.wait()
            ex.run(plan, feeds)

        threads = [threading.Thread(target=run) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(compiles) == 1

    def test_session_trace_memo_race(self, tmp_path):
        sess = Session(cache_dir=tmp_path)
        barrier = threading.Barrier(8)
        got = []

        def tracer():
            barrier.wait()
            got.append(sess.trace(workload="cg", n=48, iters=2))

        threads = [threading.Thread(target=tracer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(got) == 8
        assert all(g is got[0] for g in got)     # one memoized build


# ---------------------------------------------------------------------------
# observability: stats() snapshot consistency under concurrent submitters
# ---------------------------------------------------------------------------

class TestServerObservability:
    def test_concurrent_submit_totals_reconcile(self, tmp_path):
        # many client threads race the worker; the documented invariant —
        # requests == queued + in_flight + errors + Σ size·count — must
        # hold for every stats() snapshot, including ones taken mid-flight
        srv = Server(session=Session(cache_dir=tmp_path),
                     max_batch_size=8, max_wait_us=2000.0)
        n_threads, per = 4, 10
        futs, flock = [], threading.Lock()

        def client(t):
            for i in range(per):
                f = srv.submit(request("cg", n=64, iters=2,
                                       seed=t * per + i))
                with flock:
                    futs.append(f)

        def reconciles(st):
            served = sum(size * cnt
                         for b in st["buckets"].values()
                         for size, cnt in b["batch_sizes"].items())
            return (st["requests"] == st["queue_depth"] + st["in_flight"]
                    + st["errors"] + served), served

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for _ in range(10):
            ok, _ = reconciles(srv.stats())
            assert ok
            time.sleep(0.002)
        for th in threads:
            th.join()
        results = [f.result(timeout=300) for f in futs]
        srv.close()
        st = srv.stats()
        total = n_threads * per
        assert st["requests"] == total
        assert st["errors"] == 0
        assert st["queue_depth"] == 0 and st["in_flight"] == 0
        ok, served = reconciles(st)
        assert ok and served == total
        (bucket,) = st["buckets"].values()
        assert st["batches"] == sum(bucket["batch_sizes"].values())
        assert len(results) == total
        assert all(np.isfinite(r.residual) for r in results)

    def test_errors_counted_in_reconciliation(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), autostart=False)
        bad = srv.submit(request("cg", n=64, iters=2,
                                 feeds={"b": np.ones(3)}))   # bad shape
        srv.start()
        with pytest.raises(ValueError):
            bad.result(timeout=300)
        srv.close()
        st = srv.stats()
        assert st["requests"] == 1 and st["errors"] == 1
        served = sum(size * cnt for b in st["buckets"].values()
                     for size, cnt in b["batch_sizes"].items())
        assert served == 0
        assert st["requests"] == st["queue_depth"] + st["in_flight"] \
            + st["errors"] + served

    def test_latency_quantiles_match_streaming_histogram(self, tmp_path):
        # acceptance: stats() p50/p99 come from the streaming histogram
        # and must sit within the documented ±5% (HIST_REL_ERROR) of the
        # nearest-rank sample quantile of the latencies the clients saw
        from repro.obs import HIST_REL_ERROR
        srv = Server(session=Session(cache_dir=tmp_path),
                     max_batch_size=4)
        lat = [srv.solve(request("cg", n=64, iters=2, seed=s)).latency_s
               for s in range(12)]
        srv.close()
        (bucket,) = srv.stats()["buckets"].values()
        summ = bucket["latency"]
        assert summ["count"] == 12
        assert summ["sum"] == pytest.approx(sum(lat))
        assert summ["min"] == pytest.approx(min(lat))
        assert summ["max"] == pytest.approx(max(lat))
        for q, p in (("p50", 50), ("p99", 99)):
            exact = float(np.percentile(lat, p, method="inverted_cdf"))
            assert abs(summ[q] - exact) / exact <= HIST_REL_ERROR + 1e-9
        wait = bucket["queue_wait"]
        assert wait["count"] == 12 and wait["max"] <= summ["max"]


# ---------------------------------------------------------------------------
# bench_compare: per-metric direction in one invocation
# ---------------------------------------------------------------------------

def _bench_compare():
    import importlib.util
    path = pathlib.Path(__file__).resolve().parent.parent / "scripts" \
        / "bench_compare.py"
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _dump(rps, p99):
    return {"TABLE 9": [{"name": "hpc/cg/batch16", "us_per_call": 1.0,
                         "backend": "reference",
                         "derived": {"requests_per_s": rps,
                                     "p99_ms": p99}}]}


class TestBenchCompareMultiMetric:
    def test_parse_metrics(self):
        bc = _bench_compare()
        assert bc.parse_metrics("us_per_call") == [("us_per_call", False)]
        assert bc.parse_metrics("x", True) == [("x", True)]
        assert bc.parse_metrics("requests_per_s:higher,p99_ms:lower") == \
            [("requests_per_s", True), ("p99_ms", False)]
        assert bc.parse_metrics("a:lower, b", True) == \
            [("a", False), ("b", True)]
        with pytest.raises(ValueError):
            bc.parse_metrics("a:sideways")
        with pytest.raises(ValueError):
            bc.parse_metrics(" , ")

    def test_two_directions_gate_in_one_pass(self):
        bc = _bench_compare()
        base = _dump(rps=1000.0, p99=5.0)
        spec = dict(backend="", max_regress=0.25,
                    metric="requests_per_s:higher,p99_ms:lower")

        _, failures, gated = bc.compare(_dump(900.0, 5.5), base, **spec)
        assert gated == 2 and not failures          # both within bound

        _, failures, _ = bc.compare(_dump(500.0, 5.0), base, **spec)
        assert len(failures) == 1                   # throughput collapsed
        assert "requests_per_s" in failures[0]

        _, failures, _ = bc.compare(_dump(1000.0, 9.0), base, **spec)
        assert len(failures) == 1                   # latency blew up
        assert "p99_ms" in failures[0]

        _, failures, _ = bc.compare(_dump(500.0, 9.0), base, **spec)
        assert len(failures) == 2                   # both gates fire

    def test_failure_detail_carries_values_and_params(self):
        """A tripped gate names the operating point: raw baseline vs
        current values plus the row's capacity/density-class params."""
        bc = _bench_compare()
        base = _dump(1000.0, 5.0)
        new = _dump(500.0, 5.0)
        for d in (base, new):
            d["TABLE 9"][0]["derived"].update(
                {"density": 0.01, "capacity_kib": 1792, "overbook": 0.25})
        base["TABLE 9"][0]["derived"]["overbook"] = 0.0
        _, failures, _ = bc.compare(
            new, base, backend="", max_regress=0.25,
            metric="requests_per_s", higher_is_better=True)
        assert len(failures) == 1
        assert "baseline=1000" in failures[0]
        assert "current=500" in failures[0]
        assert "density=0.01" in failures[0]
        assert "capacity_kib=1792" in failures[0]
        assert "overbook=0.25 (baseline 0.0)" in failures[0]

    def test_single_metric_unchanged(self):
        bc = _bench_compare()
        base = _dump(1000.0, 5.0)
        lines, failures, gated = bc.compare(
            _dump(1000.0, 20.0), base, backend="", max_regress=0.25,
            metric="requests_per_s", higher_is_better=True)
        assert gated == 1 and not failures
        # single-metric labels keep the bare row name (no suffix)
        assert any("hpc/cg/batch16 " in ln and "[" not in ln.split()[1]
                   for ln in lines if "ok" in ln)

    def test_cli_round_trip(self, tmp_path, capsys):
        bc = _bench_compare()
        new = tmp_path / "new.json"
        baseline = tmp_path / "base.json"
        new.write_text(json.dumps(_dump(500.0, 9.0)))
        baseline.write_text(json.dumps(_dump(1000.0, 5.0)))
        rc = bc.main([str(new), "--baseline", str(baseline),
                      "--backend", "",
                      "--metric", "requests_per_s:higher,p99_ms:lower"])
        assert rc == 1
        out = capsys.readouterr().out
        assert "REGRESSION" in out
        rc = bc.main([str(new), "--baseline", str(baseline),
                      "--backend", "", "--metric", "p99_ms:sideways"])
        assert rc == 2


# ---------------------------------------------------------------------------
# shutdown races (satellite: close() vs in-flight / queued / poisoned work)
# ---------------------------------------------------------------------------

class TestShutdownRaces:
    @pytest.fixture(autouse=True)
    def _clean_rules(self):
        faults.clear()
        yield
        faults.clear()

    def test_close_flush_waits_for_in_flight_batch(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=2,
                     max_wait_us=200)
        srv.solve(request("cg", n=32, iters=2))       # warm the plan
        with faults.inject("serve.dispatch", kind="slow", delay_s=0.3,
                           times=1):
            fut = srv.submit(request("cg", n=32, iters=2, seed=1))
            time.sleep(0.05)                          # batch is in flight
            srv.close(flush=True)                     # racing the dispatch
        assert fut.result(timeout=1).batch_size == 1  # served, not dropped
        with pytest.raises(ServerClosed):
            srv.submit(request("cg", n=32, iters=2, seed=2))

    def test_close_noflush_fails_queued_futures_typed(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=4,
                     max_wait_us=200, autostart=False)
        futs = [srv.submit(request("cg", n=32, iters=2, seed=s))
                for s in range(3)]
        srv.close(flush=False)
        for f in futs:
            with pytest.raises(ServerClosed, match="closed"):
                f.result(timeout=1)
        st = srv.stats()
        assert st["errors"] == 3 and st["queue_depth"] == 0

    def test_poisoned_batch_does_not_poison_the_bucket(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=2,
                     max_wait_us=200, autostart=False)
        futs = [srv.submit(request("cg", n=32, iters=2, seed=s))
                for s in range(4)]                    # two batches of 2
        with faults.inject("serve.dispatch", kind="fail", times=1):
            srv.start()
            for f in futs[:2]:                        # poisoned batch only
                with pytest.raises(faults.InjectedFault):
                    f.result(timeout=60)
            for f in futs[2:]:                        # same bucket, served
                assert f.result(timeout=60).batch_size == 2
        st = srv.stats()
        assert st["errors"] == 2
        assert st["requests"] == 4
        srv.close()


# ---------------------------------------------------------------------------
# client cancel() races (PR 9 review): no settle site may raise
# InvalidStateError into the worker or an unrelated submitter
# ---------------------------------------------------------------------------

class TestClientCancelRaces:
    def test_cancelled_future_does_not_crash_the_batch(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=4,
                     max_wait_us=200, autostart=False)
        futs = [srv.submit(request("cg", n=32, iters=2, seed=s))
                for s in range(3)]
        assert futs[1].cancel()              # still queued: the cancel wins
        srv.start()
        # the other members of the batch are served normally — un-fixed,
        # set_result on the cancelled future raised InvalidStateError,
        # crashed the worker, and failed the whole batch WorkerCrashed
        assert futs[0].result(timeout=60).batch_size == 2
        assert futs[2].result(timeout=60).batch_size == 2
        assert futs[1].cancelled()
        h = srv.health()
        assert h["status"] == "ok" and h["worker_restarts"] == 0
        st = srv.stats()
        assert st["requests"] == 3
        assert st["errors"] == 1             # the cancelled request
        srv.close()

    def test_cancel_racing_shed_does_not_raise_in_submitter(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=8,
                     max_wait_us=50_000, autostart=False,
                     max_queue=1, overload="shed_oldest")
        f1 = srv.submit(request("cg", n=32, iters=2, seed=1))
        assert f1.cancel()
        # the queue is full, so this submit sheds the (already-cancelled)
        # head — un-fixed, set_exception raised InvalidStateError here,
        # in an unrelated submitter's thread
        f2 = srv.submit(request("cg", n=32, iters=2, seed=2))
        assert f1.cancelled()
        srv.start()
        assert f2.result(timeout=60).batch_size == 1
        srv.close()

    def test_shed_head_does_not_restart_the_wait_window(self, tmp_path):
        # the coalescing window is anchored at batch open: losing the head
        # mid-wait (shed here; an expiring deadline is the same path) must
        # not re-open the max_wait window from the new head's t_submit
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=8,
                     max_wait_us=500_000, max_queue=1,
                     overload="shed_oldest")
        srv.solve(request("cg", n=32, iters=2))           # warm the plan
        t0 = time.monotonic()
        f1 = srv.submit(request("cg", n=32, iters=2, seed=1))
        time.sleep(0.25)               # worker is mid-wait on f1's batch
        f2 = srv.submit(request("cg", n=32, iters=2, seed=2))  # sheds f1
        with pytest.raises(Overloaded):
            f1.result(timeout=1)
        assert f2.result(timeout=60).batch_size == 1
        closed_after = time.monotonic() - t0
        # fixed: batch closes ~0.5s after open; un-fixed the window
        # restarts from f2.t_submit and closes at ~0.75s
        assert closed_after < 0.68, closed_after
        srv.close()


class TestTypedRequestsAndConfig:
    """0.10 surface: ServeConfig, SolveRequest.bucket/deadline_s, fp64."""

    def test_request_bucket_method_is_the_canonicalization(self, tmp_path):
        req = request("cg_sparse", n=64, iters=2, density=0.0011)
        router = PlanRouter(session=Session(cache_dir=tmp_path))
        assert req.bucket() == router.bucket(req)
        assert req.bucket().density == "d0.001"     # bucketed, not raw

    def test_deadline_rides_on_the_request(self, tmp_path):
        srv = Server(None, ServeConfig(max_batch_size=4, autostart=False),
                     session=Session(cache_dir=tmp_path))
        # an already-expired per-request deadline fails fast at submit
        with pytest.raises(ValueError, match="deadline_s"):
            srv.submit(request("cg", n=32, iters=2, deadline_s=-1.0))
        fut = srv.submit(request("cg", n=32, iters=2, deadline_s=60.0))
        srv.start()
        assert fut.result(timeout=120).batch_size == 1
        srv.close()

    def test_submit_dict_deprecated_but_works(self, tmp_path):
        import warnings
        srv = Server(config=ServeConfig(max_batch_size=4),
                     session=Session(cache_dir=tmp_path))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            fut = srv.submit({"workload": "cg", "n": 32, "iters": 2})
            assert any(issubclass(x.category, DeprecationWarning)
                       for x in w)
        assert fut.result(timeout=120).batch_size == 1
        srv.close()

    def test_legacy_server_kwargs_warn_and_conflict_raises(self, tmp_path):
        import warnings
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            srv = Server(session=Session(cache_dir=tmp_path),
                         max_batch_size=4, autostart=False)
            assert any(issubclass(x.category, DeprecationWarning)
                       for x in w)
        assert srv.max_batch_size == 4
        srv.close()
        with pytest.raises(TypeError, match="not both"):
            Server(config=ServeConfig(), max_batch_size=4)

    def test_mixed_fp32_fp64_buckets_one_server(self, tmp_path):
        """float64 requests build and dispatch under thread-local x64:
        the outputs really are float64, fp32 buckets are untouched, and
        the two dtypes land in separate buckets of one server."""
        srv = Server(config=ServeConfig(max_batch_size=8),
                     session=Session(cache_dir=tmp_path))
        try:
            f32 = srv.submit(request("cg", n=64, iters=3, seed=1))
            f64 = srv.submit(request("cg", n=64, iters=3, seed=1,
                                     dtype="float64"))
            r32 = f32.result(timeout=300)
            r64 = f64.result(timeout=300)
            x32 = next(v for k, v in sorted(r32.outputs.items())
                       if k.startswith("x"))
            x64 = next(v for k, v in sorted(r64.outputs.items())
                       if k.startswith("x"))
            assert np.asarray(x32).dtype == np.float32
            assert np.asarray(x64).dtype == np.float64
            # same seed, same solver: fp64 refines fp32, not replaces it
            np.testing.assert_allclose(np.asarray(x32),
                                       np.asarray(x64, np.float32),
                                       rtol=1e-3, atol=1e-5)
            labels = set(srv.stats()["buckets"])
            assert any("float64" in lb for lb in labels)
            assert any("float32" in lb for lb in labels)
        finally:
            srv.close()
