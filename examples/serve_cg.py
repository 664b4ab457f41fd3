"""Serve a batch of CG solves through ``repro.serve``, end to end.

Spins up a :class:`repro.serve.Server`, submits a burst of mixed-bucket
requests (dense ``cg`` + CSR-sparse ``cg_sparse``, each with its own
right-hand side), and shows the serving pipeline at work: the router
canonicalizes requests into bucket keys, a bounded LRU keeps one vmapped
:class:`~repro.serve.BatchedPlan` resident per bucket, and the worker
coalesces same-bucket requests so each batch is answered in **one device
dispatch** — which ``stats()`` then proves.

    python examples/serve_cg.py --n 256 --requests 32 --max-batch 16
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.serve import ServeConfig, Server, request
from repro.runtime import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=256, help="operator size")
    ap.add_argument("--iters", type=int, default=4,
                    help="unrolled CG iterations")
    ap.add_argument("--requests", type=int, default=32,
                    help="requests per workload")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="coalesce up to this many same-bucket requests")
    ap.add_argument("--max-wait-us", type=float, default=2000.0,
                    help="close a batch after its head waited this long")
    ap.add_argument("--backend", default="reference",
                    help="execution backend (reference | pallas)")
    args = ap.parse_args()
    enable_compile_cache()

    # autostart=False + submit-all + start(): every request is queued
    # before the first batch closes, so coalescing is deterministic —
    # ceil(requests / max_batch) batches per bucket
    srv = Server(config=ServeConfig(max_batch_size=args.max_batch,
                                    max_wait_us=args.max_wait_us,
                                    autostart=False))
    futs = []
    for seed in range(args.requests):
        futs.append(srv.submit(request(
            "cg", n=args.n, iters=args.iters, seed=seed,
            backend=args.backend)))
        futs.append(srv.submit(request(
            "cg_sparse", n=args.n, iters=args.iters, seed=seed,
            backend=args.backend)))
    # an explicit right-hand side rides along as a feeds overlay (input
    # leaves only — the operator is the bucket's shared one)
    futs.append(srv.submit(request(
        "cg", n=args.n, iters=args.iters, backend=args.backend,
        feeds={"b": np.ones(args.n, np.float32)})))

    srv.start()
    results = [f.result() for f in futs]
    srv.close()

    for res in results[:3] + results[-1:]:
        print(f"{res.bucket:60s} batch={res.batch_size:2d} "
              f"latency={res.latency_s * 1e3:7.2f}ms "
              f"residual={res.residual:.3g}")
    print(f"... {len(results)} results total\n")

    st = srv.stats()
    print(f"requests={st['requests']} batches={st['batches']} "
          f"plans_cached={st['plans_cached']}")
    for label, b in st["buckets"].items():
        print(f"  {label}")
        print(f"    requests={b['requests']} batches={b['batches']} "
              f"sizes={b['batch_sizes']} cache={b['cache_hits']}h/"
              f"{b['cache_misses']}m")
        # the serving guarantee: every coalesced batch was ONE dispatch
        assert b["dispatches"] == b["batches"], (b["dispatches"],
                                                 b["batches"])
    print("one dispatch per coalesced batch: verified")


if __name__ == "__main__":
    main()
