"""Chaos demo: the serving stack absorbing injected failures, end to end.

Drives one :class:`repro.serve.Server` through three incidents using the
deterministic fault-injection harness (``repro.testing.faults``,
``docs/robustness.md``) and prints what the failure-handling layer did
about each:

1. **Broken backend** — every pallas compile fails: the per-bucket
   retry policy runs, the circuit breaker opens, and every request is
   still answered *exactly* via the reference fallback (the reference
   interpreter is the bitwise oracle, so degraded mode loses speed, not
   precision).
2. **Overload** — open-loop arrivals at several times capacity against a
   bounded queue with ``overload="reject"``: excess load fails fast and
   typed, served latency stays bounded.
3. **Worker crash** — the worker thread dies mid-batch: in-flight
   futures fail with :class:`~repro.serve.WorkerCrashed`, the supervisor
   restarts the worker, and the very next submit succeeds.

Faults can also be armed without touching code via the environment::

    CELLO_FAULTS='exec.compile@pallas=fail:x2' python examples/serve_chaos.py

    python examples/serve_chaos.py --n 64 --iters 2
"""
from __future__ import annotations

import argparse
import time

from repro.serve import (Overloaded, RetryPolicy, ServeConfig, Server,
                         WorkerCrashed,
                         request)
from repro.runtime import enable_compile_cache
from repro.testing import faults


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=64, help="operator size "
                    "(perfect square: the cg_sparse grid needs one)")
    ap.add_argument("--iters", type=int, default=2,
                    help="unrolled CG iterations")
    ap.add_argument("--requests", type=int, default=8,
                    help="requests per incident")
    args = ap.parse_args()
    enable_compile_cache()

    srv = Server(config=ServeConfig(
        max_batch_size=4, max_wait_us=500.0,
        max_queue=8, overload="reject",
        retry=RetryPolicy(max_retries=1, backoff_s=0.001),
        fallback="reference", breaker_failures=2))

    # -- incident 1: the pallas backend cannot compile ------------------
    print("# incident 1: pallas compile fails -> reference fallback")
    with faults.inject("exec.compile@pallas", kind="fail"):
        for seed in range(args.requests):
            res = srv.solve(request("cg", n=args.n, iters=args.iters,
                                    seed=seed, backend="pallas"))
            assert res.degraded and res.backend == "reference"
    st = srv.stats()
    lb = next(k for k in st["buckets"] if "/pallas" in k)
    print(f"  served={args.requests} degraded, fallbacks="
          f"{st['fallbacks']}, retries={st['retries']}, "
          f"breaker[{lb}]={st['buckets'][lb]['breaker']}")
    print(f"  health: {srv.health()['status']}")

    # -- incident 2: sustained overload against a bounded queue --------
    print("# incident 2: overload with a bounded queue (reject)")
    srv.solve(request("cg", n=args.n, iters=args.iters))    # warm plan
    futs, rejected = [], 0
    with faults.inject("serve.dispatch", kind="slow", delay_s=0.02):
        for seed in range(6 * args.requests):
            try:
                futs.append(srv.submit(
                    request("cg", n=args.n, iters=args.iters,
                            seed=seed % 7),
                    deadline_s=5.0))
            except Overloaded:
                rejected += 1
            time.sleep(0.001)
        served = [f.result(timeout=60) for f in futs]
    assert rejected > 0 and served
    print(f"  offered={6 * args.requests} served={len(served)} "
          f"rejected fast+typed={rejected} "
          f"queue_depth={srv.stats()['queue_depth']}")

    # -- incident 3: the worker thread crashes mid-batch ----------------
    print("# incident 3: worker crash -> supervised restart")
    with faults.inject("serve.worker", kind="fail", times=1):
        fut = srv.submit(request("cg", n=args.n, iters=args.iters,
                                 seed=99))
        try:
            fut.result(timeout=60)
            raise AssertionError("expected WorkerCrashed")
        except WorkerCrashed as e:
            print(f"  in-flight future failed typed: {type(e).__name__}")
    res = srv.solve(request("cg", n=args.n, iters=args.iters, seed=100))
    h = srv.health()
    print(f"  next solve served (batch={res.batch_size}), health="
          f"{h['status']}, worker_restarts={h['worker_restarts']}")

    srv.close()
    print("chaos absorbed: fallback exact, overload typed, crash "
          "supervised")


if __name__ == "__main__":
    main()
