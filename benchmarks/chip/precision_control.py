"""Controls of a cell's correctness check below the precision its
configuration states, against one float64 reference.

    python3 benchmarks/chip/precision_control.py --workload <cell> \\
        --seeds 1 [2 ...]

For each seed, on the cell's own operator and its first ``check_sample``
right-hand sides (the traffic kind's driver's ``sample_inputs``), reads
the numbers the benchmark compares (``reference.py``) for two CG
recurrences put in the program's place and run on the device:

* ``bf16``: ``control.py``'s control, everything in bfloat16;
* ``default``: float32 vectors with the operator applied at XLA's default
  precision, which on a TPU multiplies float32 operands in one bfloat16
  pass: what a contraction that names no precision computes there.

Both are compared with one float64 CG on the operator kind's own product,
so a cell whose reference is slow pays for it once per seed.  Each side
has to read above a limit.  One JSON line per seed and side on standard
output, as ``control.py`` prints them.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Any, Dict, List, Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import cells                                                 # noqa: E402
import control                                               # noqa: E402
import reference                                             # noqa: E402


def default_cg(op, b: np.ndarray, iters: int):
    """The reference recurrence on the rows of ``b`` in float32 on the
    device, the operator applied at the default precision; returns the
    solutions and residuals as float64 rows."""
    import jax
    import jax.numpy as jnp

    def step(_, state):
        x, r, p, rs, a = state
        ap = p @ a.T
        alpha = rs / jnp.sum(p * ap, axis=1, keepdims=True)
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = jnp.sum(r * r, axis=1, keepdims=True)
        return x, r, r + (rs_new / rs) * p, rs_new, a

    @jax.jit
    def solve(a, b):
        rs = jnp.sum(b * b, axis=1, keepdims=True)
        x, r, _, _, _ = jax.lax.fori_loop(0, iters, step,
                                          (jnp.zeros_like(b), b, b, rs, a))
        return x, r
    x, r = solve(op, jnp.asarray(b, jnp.float32))
    return np.asarray(x, np.float64), np.asarray(r, np.float64)


def readings(cell: cells.Cell, seed: int) -> List[Dict[str, Any]]:
    """The compared numbers of both sides on ``seed``, each the worst over
    the sample, with the limits."""
    cfg = cell.config
    kind = cells.operator_kind(cell)
    drv = cells.driver_class(cell)(cfg, cell.traffic, seed, 1.0,
                                   lambda msg: None, kind)
    b = drv.sample_inputs(int(cfg["check_sample"]))
    iters = int(cfg["params"]["iters"])
    sides = {"bf16": control.bf16_cg(kind, kind.bf16_operator(cfg, seed),
                                     b, iters)}
    op = kind.build(cfg, seed)["A"]
    sides["default"] = default_cg(op, b, iters)
    del op
    mv = kind.reference_matvec(cfg, seed)
    x_ref, _ = reference.host_cg(mv, b.T.copy(), iters)
    return [{"cell": cell.name, "seed": seed, "side": side,
             "compared": reference.judge(
                 reference.compare_all(b.T, x.T, r.T, x_ref, mv),
                 cfg["limits"])}
            for side, (x, r) in sides.items()]


def main(argv: Optional[List[str]] = None, *,
         root: pathlib.Path = ROOT) -> List[Dict[str, Any]]:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = cells.load(root, args.workload)
    sys.path.insert(0, str(root / "src"))
    out = []
    for seed in args.seeds:
        for rec in readings(cell, seed):
            print(json.dumps(rec), flush=True)
            out.append(rec)
    return out


if __name__ == "__main__":
    main()
