"""The control of a cell's correctness check, and the program beside it.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 \\
        [--program]

For each seed, on the cell's own operator and right-hand sides at the
cell's own size (the first ``check_sample`` of them), reads the numbers
the benchmark compares (``x_err``, ``x_maxerr``, ``r_gap``;
:mod:`reference`) for:

* the control: the reference's CG recurrence put in the program's place
  and computed on the device in bfloat16, the precision below the float32
  the configuration states, on the operator kind's ``bf16_operator`` and
  ``bf16_rows_matvec``.  It has to read above a limit;
* with ``--program``, the program on the same inputs through the cell's
  own path (the traffic kind's driver's ``solve_rows``: ``CompiledPlan
  .run`` for a cell of solves, the solver service for a served cell).  It
  has to read below them.

One JSON line per seed and side on standard output.  Beside the numbers
compared it gives ``r_err``, the 2-norm of ``r - r_ref`` over that of
``b`` against the reference's own recurrence residual: a reading kept to
show why ``r`` is compared by ``r_gap`` instead (``PERF.md``).  The benchmark's own
runs never run this; it is how the limits in ``configs/*.json`` were set
(``PERF.md``), and ``tests/test_control.py`` runs it at a small size.
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
import reference                                             # noqa: E402


def bf16_cg(kind, op, b: np.ndarray, iters: int):
    """The reference recurrence on the rows of ``b`` against the operator
    ``op`` (applied by ``kind.bf16_rows_matvec``), in bfloat16 on the
    device; returns the solutions and residuals as float64 rows."""
    import jax
    import jax.numpy as jnp

    def step(_, state):
        x, r, p, rs, a = state
        ap = kind.bf16_rows_matvec(a, p)
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
    x, r = solve(op, jnp.asarray(b, jnp.bfloat16))
    return (np.asarray(x.astype(jnp.float32), np.float64),
            np.asarray(r.astype(jnp.float32), np.float64))


def readings(cell: cells.Cell, seed: int, side: str) -> Dict[str, Any]:
    """The compared numbers of ``side`` (``control`` or ``program``) on
    ``seed``, each the worst over the sample, with the limits, and the
    worst ``r_err``."""
    cfg = cell.config
    kind = cells.operator_kind(cell)
    drv = cells.driver_class(cell)(cfg, cell.traffic, seed, 1.0,
                                   lambda msg: None, kind)
    b = drv.sample_inputs(int(cfg["check_sample"]))
    iters = int(cfg["params"]["iters"])
    if side == "control":
        x, r = bf16_cg(kind, kind.bf16_operator(cfg, seed), b, iters)
    else:
        x, r = drv.solve_rows(b)
    mv = kind.reference_matvec(cfg, seed)
    x_ref, r_ref = reference.host_cg(mv, b.T.copy(), iters)
    got = reference.compare_all(b.T, x.T, r.T, x_ref, mv)
    r_err = np.linalg.norm(r.T - r_ref, axis=0) / np.linalg.norm(b.T, axis=0)
    return {"cell": cell.name, "seed": seed, "side": side,
            "compared": reference.judge(got, cfg["limits"]),
            "r_err": float(np.max(r_err))}


def main(argv: Optional[List[str]] = None, *,
         root: pathlib.Path = ROOT) -> List[Dict[str, Any]]:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="also read the program on the same inputs")
    args = ap.parse_args(argv)
    cell = cells.load(root, args.workload)
    sys.path.insert(0, str(root / "src"))
    sides = ["control"] + (["program"] if args.program else [])
    out = []
    for seed in args.seeds:
        for side in sides:
            rec = readings(cell, seed, side)
            print(json.dumps(rec), flush=True)
            out.append(rec)
    return out


if __name__ == "__main__":
    main()
