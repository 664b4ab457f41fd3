"""Quickstart: run the CELLO schedule × hybrid-buffer co-design through the
staged Session API and lower the result to an execution plan.

    python examples/quickstart.py [--arch granite-3-8b] [--phase train]

(Install with `pip install -e .` first — or prefix with PYTHONPATH=src.)
"""
import argparse

from repro.api import CodesignConfig, Session
from repro.runtime import enable_compile_cache
from repro.configs import list_archs
from repro.core.buffer import MiB


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=list_archs())
    ap.add_argument("--phase", default="train",
                    choices=("train", "prefill", "decode"))
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=8192,
                    help="sequence length (train/prefill) or KV length "
                         "(decode)")
    ap.add_argument("--capacity-mib", type=int, default=128)
    ap.add_argument("--strategy", default="default",
                    choices=("default", "exhaustive", "greedy", "alap"))
    ap.add_argument("--no-cache", action="store_true",
                    help="force a fresh search (skip the disk cache)")
    args = ap.parse_args()
    enable_compile_cache()

    sess = Session(args.arch, capacity_bytes=args.capacity_mib * MiB,
                   use_cache=not args.no_cache)
    shape = (dict(batch=args.batch, kv_len=args.seq)
             if args.phase == "decode"
             else dict(batch=args.batch, seq=args.seq))

    # stage 1+2: trace the op DAG, analyse its reuse structure
    traced = sess.trace(phase=args.phase, **shape)
    analyzed = traced.analyze()
    print(traced)
    print(analyzed)
    top = analyzed.pin_candidates()[:3]
    if top:
        print("top pin candidates   :",
              ", ".join(f"{t.name} (saves {t.pin_value():.1f} B/B)"
                        for t in top))

    # stage 3: the joint schedule × buffer-split search
    designed = analyzed.codesign(CodesignConfig(strategy=args.strategy))
    print(f"\n{designed}")
    best = designed.best.metrics
    for name, ev in designed.baselines.items():
        print(f"  vs {name:13s}: speedup "
              f"{ev.metrics.time_s / best.time_s:5.2f}x   energy "
              f"{ev.metrics.energy_j / best.energy_j:5.2f}x   HBM "
              f"{ev.metrics.hbm_bytes / max(1, best.hbm_bytes):6.1f}x")

    # stage 4: lower onto kernels + remat policy
    plan = designed.lower()
    print("\n" + plan.explain())


if __name__ == "__main__":
    main()
