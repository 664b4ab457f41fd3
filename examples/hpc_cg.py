"""Conjugate Gradient through the CELLO co-designer, end to end.

Builds the paper's headline HPC workload (skewed ``(n×n)·(n,)`` matvec
chains with cross-iteration reuse of the operator ``A``), runs the
schedule × buffer co-design, prints the decision (including the kernel
selected per fusion group), then executes the co-designed schedule through
both execution backends — the ``reference`` jax.numpy oracle and the
``pallas`` tile-streaming kernels — and validates them against
natural-order evaluation.

    python examples/hpc_cg.py --n 4096 --iters 4
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.api import Session
from repro.runtime import enable_compile_cache
from repro.frontends import evaluate, make_feeds


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=4096,
                    help="operator size (n x n); at 4096 the fp64 operator "
                         "is exactly the 128 MiB on-chip capacity")
    ap.add_argument("--iters", type=int, default=4,
                    help="unrolled CG iterations")
    ap.add_argument("--workload", default="cg",
                    help="any registered workload that takes n/iters "
                         "(cg, bicgstab, power_iteration)")
    args = ap.parse_args()
    enable_compile_cache()

    sess = Session()                    # arch-less: frontend traces only
    traced = sess.trace(workload=args.workload, n=args.n, iters=args.iters)
    print(f"traced   : {traced}")
    analyzed = traced.analyze()
    print(f"analyzed : {analyzed}")
    designed = analyzed.codesign()
    print(f"codesign : {designed}")
    plan = designed.lower()
    print()
    print(plan.explain())

    # numerical validation: scheduled execution vs natural-order reference,
    # on both execution backends
    feeds = make_feeds(traced.program, seed=0)
    want = evaluate(traced.program, feeds)
    print()
    got = None
    for backend in ("reference", "pallas"):
        got = plan.run(feeds, backend=backend)
        worst = max(float(np.max(np.abs(np.asarray(got[k])
                                        - np.asarray(want[k]))))
                    for k in want)
        print(f"numerical check [{backend:9s}] vs natural-order oracle: "
              f"max abs diff = {worst:.3g} over {sorted(want)}")
    if args.workload == "cg":
        r = np.asarray(got[f"r{args.iters}"])
        print(f"final CG residual norm: {np.linalg.norm(r):.4g}")


if __name__ == "__main__":
    main()
