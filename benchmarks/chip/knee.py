"""Sweep the offered rate of an open-loop serving cell to find its knee.

    python3 benchmarks/chip/knee.py --workload gp-dense-8k.serve \\
        --seed <n> --seconds 15 --rates 20 30 40 50 60

One process, one set-up, then one window per rate, in the order given,
through the cell's own driver (``drivers/<kind>.py``) with only its rate
changed.  Prints one line per rate: offered and completed rates, the
95th percentile from when each request was due, and the backlog when the
window closed.  The knee is the highest rate completed with no growing
backlog; the cell's traffic file then offers 0.8 of it.  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import cells                                                 # noqa: E402


def main(argv: Optional[List[str]] = None, *,
         root: pathlib.Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = cells.load(root, args.workload)
    if "rate_per_s" not in cell.traffic:
        ap.error(f"{args.workload} offers no fixed rate to sweep")
    sys.path.insert(0, str(root / "src"))
    from repro.runtime import enable_compile_cache
    enable_compile_cache()

    traffic = dict(cell.traffic, rate_per_s=max(args.rates))
    drv = cells.driver_class(cell)(
        cell.config, traffic, args.seed, args.seconds,
        lambda msg: print(msg, file=sys.stderr, flush=True),
        cells.operator_kind(cell))
    drv.setup()
    try:
        for rate in args.rates:
            drv.traffic["rate_per_s"] = rate
            drv.count = int(round(rate * args.seconds))
            res = drv.window(args.seconds)
            print(json.dumps({
                "offered_per_s": rate,
                "completed_per_s": res["metrics"]["serve_solves_per_s"],
                "p95_ms": res["metrics"]["serve_p95_ms"],
                "backlog_at_close": res["backlog"],
                "failed": res["failed"]}), flush=True)
    finally:
        drv.finish()
    return 0


if __name__ == "__main__":
    sys.exit(main())
