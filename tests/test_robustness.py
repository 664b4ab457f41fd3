"""Chaos suite: the serving stack under injected faults.

Drives every failure-handling layer end-to-end with the deterministic
fault-injection harness (``repro.testing.faults``, docs/robustness.md)
and proves the ISSUE's acceptance criteria without process restarts:

(a) a bucket whose pallas compile always fails serves *correct* results
    via the reference fallback (the reference interpreter is the bitwise
    oracle, so fallback output is exact), with its breaker open and the
    transition visible in ``stats()``;
(b) at sustained overload with ``reject`` the server stays responsive
    (bounded queue depth, overloaded p99 within 10x the unloaded p99)
    and every rejected/expired request fails fast with a typed error —
    no future ever hangs;
(c) a worker crash mid-batch fails exactly the in-flight futures and
    subsequent submits succeed after a supervised restart.
"""
import time

import numpy as np
import pytest

from repro.api import Session
from repro.serve import (CircuitBreaker, DeadlineExceeded, Overloaded,
                         RetryPolicy, Server, ServerClosed, WorkerCrashed,
                         request)
from repro.testing import faults


@pytest.fixture(autouse=True)
def _clean_rules():
    faults.clear()
    yield
    faults.clear()


def _reconciles(st):
    served = sum(size * cnt for b in st["buckets"].values()
                 for size, cnt in b["batch_sizes"].items())
    return st["requests"] == (st["queue_depth"] + st["in_flight"]
                              + st["errors"] + served)


# ---------------------------------------------------------------------------
# resilience primitives
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestCircuitBreaker:
    def test_opens_after_threshold_consecutive_failures(self):
        br = CircuitBreaker(failure_threshold=3, reset_timeout_s=10.0)
        br.record_failure()
        br.record_failure()
        br.record_success()               # consecutive count resets
        br.record_failure()
        br.record_failure()
        assert br.state == "closed" and br.allow()
        br.record_failure()
        assert br.state == "open" and not br.allow()

    def test_half_open_probe_success_closes(self):
        clk = _Clock()
        br = CircuitBreaker(2, reset_timeout_s=5.0, clock=clk)
        br.record_failure()
        br.record_failure()
        assert not br.allow()
        clk.t = 5.0                       # cooldown elapsed
        assert br.allow()                 # the single probe
        assert br.state == "half_open"
        assert not br.allow()             # no second probe while pending
        br.record_success()
        assert br.state == "closed" and br.allow()

    def test_half_open_probe_failure_reopens(self):
        clk = _Clock()
        br = CircuitBreaker(1, reset_timeout_s=1.0, clock=clk)
        br.record_failure()
        clk.t = 1.0
        assert br.allow()
        br.record_failure()
        assert br.state == "open" and not br.allow()
        clk.t = 1.5                       # cooldown restarts from reopen
        assert not br.allow()
        clk.t = 2.0
        assert br.allow()
        assert br.stats()["opens"] == 2

    def test_transition_counter(self):
        from repro import obs
        c = obs.registry().counter("serve.breaker.transitions")
        before = c.value(**{"name": "t.bucket", "from": "closed",
                            "to": "open", "scope": "t"})
        br = CircuitBreaker(1, name="t.bucket", scope="t")
        br.record_failure()
        assert c.value(**{"name": "t.bucket", "from": "closed",
                          "to": "open", "scope": "t"}) == before + 1


class TestRetryPolicy:
    def test_backoff_schedule(self):
        p = RetryPolicy(max_retries=4, backoff_s=0.1, multiplier=2.0,
                        max_backoff_s=0.3)
        assert [p.delay_s(k) for k in (1, 2, 3, 4)] == \
            [0.1, 0.2, 0.3, 0.3]          # capped

    def test_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(max_retries=-1)
        with pytest.raises(ValueError):
            RetryPolicy(multiplier=0.5)


# ---------------------------------------------------------------------------
# (a) fallback chain: pallas compile always fails -> reference serves
# ---------------------------------------------------------------------------

class TestFallbackChain:
    def test_broken_pallas_bucket_serves_exact_reference_answers(
            self, tmp_path):
        seeds = list(range(4))

        def serve(backend, ctx):
            srv = Server(session=Session(cache_dir=tmp_path),
                         max_batch_size=4, max_wait_us=500,
                         autostart=False, breaker_failures=2,
                         retry=RetryPolicy(max_retries=1, backoff_s=0.001),
                         fallback="reference")
            with ctx:
                futs = [srv.submit(request("cg", n=32, iters=2, seed=s,
                                           backend=backend))
                        for s in seeds]
                srv.start()
                res = [f.result(timeout=120) for f in futs]
            st = srv.stats()
            srv.close()
            return res, st

        import contextlib
        # oracle: the same seeds served natively on the reference backend,
        # same batch composition (autostart=False -> one batch of 4)
        oracle, _ = serve("reference", contextlib.nullcontext())
        broken, st = serve(
            "pallas", faults.inject("exec.compile@pallas", kind="fail"))

        for o, b in zip(oracle, broken):
            assert b.degraded and b.backend == "reference"
            assert set(b.outputs) == set(o.outputs)
            for k in o.outputs:
                # the fallback runs the identical reference BatchedPlan:
                # bitwise equality, not a tolerance
                np.testing.assert_array_equal(np.asarray(b.outputs[k]),
                                              np.asarray(o.outputs[k]))

        lb = [k for k in st["buckets"] if "/pallas" in k][0]
        b = st["buckets"][lb]
        assert b["fallbacks"] == len(seeds)
        assert b["errors"] == 0           # every future got an answer
        assert b["retries"] >= 1          # the retry policy ran first
        assert _reconciles(st)

    def test_breaker_opens_and_is_visible_in_stats(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=2,
                     max_wait_us=200, breaker_failures=2,
                     breaker_reset_s=60.0, fallback="reference")
        with faults.inject("exec.compile@pallas", kind="fail") as rule:
            # each solve is its own failed batch: 2 failures open the
            # breaker; later batches skip pallas entirely
            for s in range(4):
                res = srv.solve(request("cg", n=32, iters=2, seed=s,
                                        backend="pallas"))
                assert res.degraded
        st = srv.stats()
        lb = [k for k in st["buckets"] if "/pallas" in k][0]
        assert st["buckets"][lb]["breaker"] == "open"
        assert st["buckets"][lb]["breaker_opens"] == 1
        assert srv.health()["status"] == "degraded"
        assert srv.health()["breakers"][lb] == "open"
        srv.close()
        # with the breaker open the primary is not attempted: the compile
        # fault fired only for the pre-open batches (one try each, no
        # retry policy configured)
        assert rule.fired == 2

    def test_breaker_open_no_fallback_fails_typed(self, tmp_path):
        from repro.serve import CircuitOpen
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=1,
                     max_wait_us=100, breaker_failures=1,
                     breaker_reset_s=60.0, fallback=None)
        with faults.inject("exec.compile@pallas", kind="fail"):
            with pytest.raises(faults.InjectedFault):
                srv.solve(request("cg", n=32, iters=2, backend="pallas"))
            with pytest.raises(CircuitOpen):
                srv.solve(request("cg", n=32, iters=2, seed=1,
                                  backend="pallas"))
        srv.close()

    def test_float64_on_mosaic_fails_typed_without_fallback(
            self, tmp_path, monkeypatch):
        # kernels compiled by Mosaic run fp32: a float64 bucket is refused
        # before it reaches the compiler, and no other backend answers
        # unless the caller configured a fallback
        from repro.exec.pallas import KernelDtypeError
        from repro.serve import ServeConfig
        monkeypatch.setenv("CELLO_PALLAS_INTERPRET", "0")
        srv = Server(session=Session(cache_dir=tmp_path),
                     config=ServeConfig(max_batch_size=1, fallback=None))
        with pytest.raises(KernelDtypeError):
            srv.solve(request("cg", n=32, iters=2, dtype="float64",
                              backend="pallas"))
        st = srv.stats()
        assert st["fallbacks"] == 0 and st["errors"] == 1
        srv.close()

    def test_transient_failure_recovered_by_retry_not_fallback(
            self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=2,
                     max_wait_us=200,
                     retry=RetryPolicy(max_retries=2, backoff_s=0.001))
        with faults.inject("serve.dispatch", kind="fail", times=1):
            res = srv.solve(request("cg", n=32, iters=2))
        assert not res.degraded and res.backend == "reference"
        st = srv.stats()
        assert st["retries"] == 1 and st["fallbacks"] == 0
        assert st["errors"] == 0
        srv.close()


# ---------------------------------------------------------------------------
# (b) overload: bounded queue, fast typed failures, responsive p99
# ---------------------------------------------------------------------------

class TestOverload:
    def test_sustained_overload_with_reject_stays_responsive(self,
                                                             tmp_path):
        dispatch_s = 0.05
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=4,
                     max_wait_us=500, max_queue=8, overload="reject")
        # warm the plan so compile time doesn't pollute latencies
        srv.solve(request("cg", n=32, iters=2))
        with faults.inject("serve.dispatch", kind="slow",
                           delay_s=dispatch_s):
            # unloaded: sequential closed-loop requests
            unloaded = []
            for s in range(6):
                t0 = time.monotonic()
                srv.solve(request("cg", n=32, iters=2, seed=s))
                unloaded.append(time.monotonic() - t0)
            unloaded_p99 = float(np.percentile(unloaded, 99))

            # overloaded: open-loop arrivals at ~4x capacity
            # (capacity ~ max_batch/dispatch_s = 80 rps -> 320 rps)
            period = dispatch_s / (4 * srv.max_batch_size)
            futs, rejected, depths = [], 0, []
            t_end = time.monotonic() + 0.6
            while time.monotonic() < t_end:
                try:
                    futs.append(srv.submit(
                        request("cg", n=32, iters=2,
                                seed=len(futs) % 17),
                        deadline_s=5.0))
                except Overloaded:
                    rejected += 1
                if len(futs) % 8 == 0:
                    depths.append(srv.stats()["queue_depth"])
                time.sleep(period)

            served, expired = [], 0
            for f in futs:
                try:
                    # generous wall timeout: the assertion is that no
                    # future hangs, not that service is fast here
                    f.result(timeout=30)
                    served.append(f)
                except DeadlineExceeded:
                    expired += 1
                # nothing else may come out of an overloaded server

        assert rejected > 0               # overload actually happened
        assert len(served) > 0            # and the server kept serving
        assert max(depths) <= srv.max_queue
        loaded_p99 = float(np.percentile(
            [f.result().latency_s for f in served], 99))
        assert loaded_p99 <= 10 * unloaded_p99, \
            f"p99 {loaded_p99:.3f}s vs unloaded {unloaded_p99:.3f}s"
        st = srv.stats()
        assert st["rejected"] == rejected
        assert st["deadline_missed"] == expired
        assert _reconciles(st)
        srv.close()

    def test_shed_oldest_fails_head_serves_tail(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=4,
                     max_wait_us=500, max_queue=2, overload="shed_oldest",
                     autostart=False)
        f1 = srv.submit(request("cg", n=32, iters=2, seed=1))
        f2 = srv.submit(request("cg", n=32, iters=2, seed=2))
        f3 = srv.submit(request("cg", n=32, iters=2, seed=3))
        with pytest.raises(Overloaded, match="shed"):
            f1.result(timeout=5)          # failed at submit time of f3
        srv.start()
        assert f2.result(timeout=60).batch_size == 2
        assert f3.result(timeout=60).batch_size == 2
        st = srv.stats()
        assert st["shed"] == 1 and _reconciles(st)
        srv.close()

    def test_block_policy_waits_for_space(self, tmp_path):
        import threading
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=1,
                     max_wait_us=100, max_queue=1, overload="block",
                     autostart=False)
        f1 = srv.submit(request("cg", n=32, iters=2, seed=1))
        blocked = {}

        def submitter():
            blocked["fut"] = srv.submit(request("cg", n=32, iters=2,
                                                seed=2))

        t = threading.Thread(target=submitter)
        t.start()
        t.join(timeout=0.2)
        assert t.is_alive()               # genuinely blocked on admission
        srv.start()                       # worker drains -> space frees
        t.join(timeout=60)
        assert not t.is_alive()
        assert f1.result(timeout=60).batch_size == 1
        assert blocked["fut"].result(timeout=60).batch_size == 1
        srv.close()

    def test_block_policy_honours_deadline(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=1,
                     max_wait_us=100, max_queue=1, overload="block",
                     autostart=False)
        srv.submit(request("cg", n=32, iters=2, seed=1))
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded, match="admission"):
            srv.submit(request("cg", n=32, iters=2, seed=2),
                       deadline_s=0.1)
        assert time.monotonic() - t0 < 5.0
        srv.close(flush=False)


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------

class TestDeadlines:
    def test_deadline_caps_coalescing_wait(self, tmp_path):
        # max_wait is 10s, but the lone request's 1s deadline closes the
        # batch early — it is dispatched, not expired
        srv = Server(session=Session(cache_dir=tmp_path),
                     max_batch_size=16, max_wait_us=10_000_000)
        t0 = time.monotonic()
        res = srv.submit(request("cg", n=32, iters=2),
                         deadline_s=1.0).result(timeout=30)
        assert res.batch_size == 1
        assert time.monotonic() - t0 < 5.0
        assert srv.stats()["deadline_missed"] == 0
        srv.close()

    def test_expiry_fails_only_the_affected_future(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=1,
                     max_wait_us=100)
        # warm both buckets so the slow phase is dispatch-dominated
        srv.solve(request("cg", n=32, iters=2))
        srv.solve(request("cg", n=48, iters=2))
        with faults.inject("serve.dispatch", kind="slow", delay_s=0.5,
                           times=1):
            f_busy = srv.submit(request("cg", n=32, iters=2, seed=1))
            time.sleep(0.05)              # worker is now mid-dispatch
            f_live = srv.submit(request("cg", n=48, iters=2, seed=2))
            f_dead = srv.submit(request("cg", n=48, iters=2, seed=3),
                                deadline_s=0.1)
            with pytest.raises(DeadlineExceeded):
                f_dead.result(timeout=30)
            assert f_busy.result(timeout=30).batch_size == 1
            assert f_live.result(timeout=30).batch_size == 1
        st = srv.stats()
        assert st["deadline_missed"] == 1
        assert st["errors"] == 1 and _reconciles(st)
        srv.close()

    def test_submit_validates_deadline(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), autostart=False)
        with pytest.raises(ValueError, match="deadline_s"):
            srv.submit(request("cg", n=32, iters=2), deadline_s=0.0)
        srv.close()


# ---------------------------------------------------------------------------
# (c) worker supervision
# ---------------------------------------------------------------------------

class TestWorkerSupervision:
    def test_crash_fails_exactly_in_flight_then_recovers(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=4,
                     max_wait_us=500, autostart=False,
                     max_worker_restarts=2)
        # bucket A's batch will be in flight when the crash fires; bucket
        # B's requests are queued-but-not-in-flight and must survive
        doomed = [srv.submit(request("cg", n=32, iters=2, seed=s))
                  for s in range(4)]
        queued = [srv.submit(request("cg", n=48, iters=2, seed=s))
                  for s in range(2)]
        with faults.inject("serve.worker", kind="fail", times=1):
            srv.start()
            for f in doomed:
                with pytest.raises(WorkerCrashed):
                    f.result(timeout=60)
            for f in queued:
                assert f.result(timeout=60).batch_size == 2
        # the restarted worker keeps serving new submits
        res = srv.submit(request("cg", n=32, iters=2, seed=9)) \
                 .result(timeout=60)
        assert res.batch_size == 1
        h = srv.health()
        assert h["status"] == "degraded" and h["worker_restarts"] == 1
        st = srv.stats()
        assert st["errors"] == len(doomed)
        assert st["worker_restarts"] == 1
        assert _reconciles(st)
        srv.close()

    def test_restart_exhaustion_goes_down_and_fails_fast(self, tmp_path):
        srv = Server(session=Session(cache_dir=tmp_path), max_batch_size=1,
                     max_wait_us=100, max_worker_restarts=0,
                     autostart=False)
        f1 = srv.submit(request("cg", n=32, iters=2, seed=1))
        f2 = srv.submit(request("cg", n=32, iters=2, seed=2))
        with faults.inject("serve.worker", kind="fail"):
            srv.start()
            with pytest.raises(WorkerCrashed):
                f1.result(timeout=60)
            with pytest.raises(WorkerCrashed):   # queued: dropped un-served
                f2.result(timeout=60)
        assert srv.health()["status"] == "down"
        with pytest.raises(ServerClosed, match="down"):
            srv.submit(request("cg", n=32, iters=2, seed=3))
        st = srv.stats()
        assert st["errors"] == 2 and _reconciles(st)
        srv.close()


# ---------------------------------------------------------------------------
# codesign cache corruption (satellite bugfix)
# ---------------------------------------------------------------------------

class TestCacheCorruption:
    def _corrupt_count(self):
        from repro import obs
        return obs.registry().counter("codesign.cache.corrupt").value()

    def test_truncated_entry_is_deleted_and_re_derived(self, tmp_path):
        sess = Session(cache_dir=tmp_path)
        first = sess.trace(workload="cg", n=32, iters=2).codesign()
        assert not first.from_cache
        (entry,) = tmp_path.glob("*.json")
        entry.write_text(entry.read_text()[:40])      # truncate on disk

        before = self._corrupt_count()
        again = Session(cache_dir=tmp_path).trace(
            workload="cg", n=32, iters=2).codesign()
        assert not again.from_cache                   # re-derived, no raise
        assert self._corrupt_count() == before + 1
        assert again.best.schedule.groups == first.best.schedule.groups
        # the re-derived result was re-published over the deleted entry
        third = Session(cache_dir=tmp_path).trace(
            workload="cg", n=32, iters=2).codesign()
        assert third.from_cache

    def test_garbage_json_counts_corrupt_not_plain_miss(self, tmp_path):
        from repro.api.cache import CodesignCache
        cache = CodesignCache(tmp_path)
        (tmp_path / "deadbeef.json").write_text("{not json at all")
        before = self._corrupt_count()
        assert cache.get("deadbeef") is None
        assert self._corrupt_count() == before + 1
        assert not (tmp_path / "deadbeef.json").exists()
        # a genuinely absent key is a plain miss: no corrupt bump
        assert cache.get("0000") is None
        assert self._corrupt_count() == before + 1

    def test_injected_corruption_site(self, tmp_path):
        sess = Session(cache_dir=tmp_path)
        sess.trace(workload="cg", n=32, iters=2).codesign()
        before = self._corrupt_count()
        with faults.inject("codesign.cache", kind="corrupt", times=1):
            res = Session(cache_dir=tmp_path).trace(
                workload="cg", n=32, iters=2).codesign()
        assert not res.from_cache
        assert self._corrupt_count() == before + 1


# ---------------------------------------------------------------------------
# supervision internals (PR 9 review): crash accounting, the restart
# window in health(), and close() boundedness
# ---------------------------------------------------------------------------

class TestSupervisionInternals:
    def test_crash_after_accounting_does_not_double_count(self, tmp_path):
        from concurrent.futures import Future

        from repro.serve.server import _InFlightBatch, _Item
        srv = Server(session=Session(cache_dir=tmp_path), autostart=False)
        req = request("cg", n=32, iters=2)
        key = srv.router.bucket(req)
        fut = Future()
        # simulate a crash landing AFTER _serve_batch settled the
        # counters (accounted=True): the future still gets the typed
        # error, but serve.errors must NOT be bumped a second time
        srv._current = _InFlightBatch(key, [_Item(req, fut,
                                                  time.monotonic())],
                                      accounted=True)
        srv._on_worker_crash(RuntimeError("boom"))
        with pytest.raises(WorkerCrashed):
            fut.result(timeout=1)
        st = srv.stats()
        assert st["errors"] == 0            # already accounted; no double
        assert st["worker_restarts"] == 1
        srv.close()

    def test_health_degraded_not_down_during_restart_window(self, tmp_path):
        import threading
        srv = Server(session=Session(cache_dir=tmp_path))
        assert srv.health()["status"] == "ok"
        # the supervisor's window: the replacement thread is registered
        # under the lock, start() has not run yet (ident is None) — a
        # restarting server must read degraded, not down
        with srv._cv:
            real = srv._worker
            srv._worker = threading.Thread(target=lambda: None, daemon=True)
            srv._worker_restarts = 1
        h = srv.health()
        assert h["status"] == "degraded" and not h["worker_alive"]
        with srv._cv:
            srv._worker = real
            srv._worker_restarts = 0
        assert srv.health()["status"] == "ok"
        srv.close()

    def test_close_bounded_when_replacement_never_starts(self, tmp_path):
        import threading
        srv = Server(session=Session(cache_dir=tmp_path))
        # a replacement that was registered but whose start() never ran:
        # close() must give up on its ident instead of spinning forever
        with srv._cv:
            srv._worker = threading.Thread(target=lambda: None, daemon=True)
        t0 = time.monotonic()
        srv.close()
        assert time.monotonic() - t0 < 5.0
