"""Benchmark harness — one table per paper-style experiment.

Prints ``name,us_per_call,derived...`` CSV blocks; on a table failure the
full traceback is printed (CI logs must be debuggable) before the
``ERROR,...`` row.

``--json PATH`` additionally writes a machine-readable dump
``{table_title: [{name, us_per_call, backend, derived}, ...]}`` so the
per-PR perf trajectory (``BENCH_*.json``) can be recorded and diffed.
Two non-table keys ride along (``scripts/bench_compare.py`` skips them
when diffing): ``meta`` — jax/jaxlib/python versions, platform, device
backend, x64 flag, UTC timestamp — and ``obs`` — the run's
``repro.obs`` metrics snapshot.
``--tables`` filters tables by case-insensitive substring (comma-separated),
which is what the CI smoke job uses to run one cheap table.  ``--backend``
threads an execution backend into the tables that run plans for real (the
HPC tables 7/8): TABLE 8 restricts to that backend, TABLE 7 gains measured
``run_us`` wall-clock next to its model columns.  ``--repeats N`` threads a
repeat count into the measuring tables: each timing is the **median of N
runs after one excluded warmup** (the warmup pays tracing/compilation), so
recorded trajectories (and the `scripts/bench_compare.py` regression gate)
compare medians, not first-run noise.
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import traceback
from typing import Any, Dict, List


def _tables():
    from . import (bench_speedup, bench_energy, bench_capacity, bench_split,
                   bench_kernels, bench_roofline, bench_hpc, bench_exec,
                   bench_serve, bench_overload, bench_dist)
    return [
        ("TABLE 1 — CELLO speedup vs baselines", bench_speedup),
        ("TABLE 2 — energy vs baselines", bench_energy),
        ("TABLE 3 — HBM traffic vs buffer capacity", bench_capacity),
        ("TABLE 4 — explicit/implicit split co-design sweep", bench_split),
        ("TABLE 5 — kernel microbench (interpret) + correctness",
         bench_kernels),
        ("TABLE 6 — roofline terms from the multi-pod dry-run",
         bench_roofline),
        ("TABLE 7 — HPC DAG speedup vs implicit/explicit/fused baselines",
         bench_hpc),
        ("TABLE 8 — measured wall-clock per execution backend",
         bench_exec),
        ("TABLE 9 — batched serving throughput vs sequential solves",
         bench_serve),
        # shares the BENCH_serve.json dump with TABLE 9: its rows use
        # disjoint metric names (served_frac/shed_rate/... vs
        # requests_per_s/p50_ms/p99_ms) so each gate skips the other's
        ("TABLE 10 — serving under overload per admission policy",
         bench_overload),
        ("TABLE 11 — distributed co-design: per-shard pin crossover",
         bench_dist),
    ]


def _meta(backend: str = None) -> Dict[str, Any]:
    """Provenance block for ``--json`` dumps: enough to tell whether two
    recorded trajectories are comparable (same jax/jaxlib, same device
    class, same x64 mode).  Lives under the top-level ``meta`` key, which
    ``scripts/bench_compare.py`` skips when diffing rows."""
    import datetime
    import platform
    meta: Dict[str, Any] = {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "backend_flag": backend,
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
    }
    try:
        import jax
        import jaxlib
        meta["jax"] = jax.__version__
        meta["jaxlib"] = getattr(jaxlib, "__version__", None)
        meta["jax_backend"] = jax.default_backend()
        meta["x64"] = bool(jax.config.jax_enable_x64)
    except Exception:                                 # pragma: no cover
        meta["jax"] = None
    return meta


def _maybe_number(cell: str) -> Any:
    for cast in (int, float):
        try:
            return cast(cell)
        except ValueError:
            continue
    return cell


def _records(rows: List[str],
             backend: str = None) -> List[Dict[str, Any]]:
    """CSV block -> [{name, us_per_call, backend, derived}] (header row
    first).  ``backend`` records which execution backend produced the
    wall-clock; a per-row ``backend`` column wins over the global flag,
    and model-only tables record ``None``."""
    if not rows:
        return []
    header = rows[0].split(",")
    out = []
    for line in rows[1:]:
        cells = line.split(",")
        rec: Dict[str, Any] = {"name": cells[0], "us_per_call": None,
                               "backend": backend, "derived": {}}
        for col, cell in zip(header[1:], cells[1:]):
            if col == "us_per_call":
                try:
                    rec["us_per_call"] = float(cell)
                except ValueError:
                    pass
            elif col == "backend":
                rec["backend"] = cell
            else:
                rec["derived"][col] = _maybe_number(cell)
        out.append(rec)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.run",
        description="Run the paper-style benchmark tables.")
    ap.add_argument("--json", metavar="PATH",
                    help="write a machine-readable row dump to PATH")
    ap.add_argument("--tables", metavar="FILTERS",
                    help="comma-separated case-insensitive substrings; only "
                         "matching table titles run (e.g. --tables hpc)")
    ap.add_argument("--backend", metavar="NAME",
                    help="execution backend for the tables that run plans "
                         "for real (reference | pallas | any registered "
                         "name); threaded into the HPC tables")
    ap.add_argument("--repeats", metavar="N", type=int,
                    help="timed repetitions per measurement (median "
                         "reported, one warmup excluded); threaded into "
                         "the tables that accept it")
    args = ap.parse_args(argv)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    wanted = ([f.strip().lower() for f in args.tables.split(",") if f.strip()]
              if args.tables else None)

    failures = 0
    dump: Dict[str, List[Dict[str, Any]]] = {}
    ran = 0
    for title, mod in _tables():
        if wanted and not any(w in title.lower() for w in wanted):
            continue
        ran += 1
        print(f"\n# {title}")
        kwargs = {}
        params = inspect.signature(mod.run).parameters
        if args.backend and "backend" in params:
            kwargs["backend"] = args.backend
        if args.repeats and "repeats" in params:
            kwargs["repeats"] = args.repeats
        try:
            rows = list(mod.run(**kwargs))
        except Exception as e:                       # pragma: no cover
            failures += 1
            traceback.print_exc(file=sys.stdout)
            print(f"ERROR,{type(e).__name__}: {e}")
            dump[title] = []
        else:
            for row in rows:
                print(row)
            # only tables that actually received the backend kwarg ran a
            # backend; model-only tables keep backend=None in the dump
            dump[title] = _records(rows, backend=kwargs.get("backend"))
    if wanted and not ran:
        print(f"no table title matches {args.tables!r}", file=sys.stderr)
        sys.exit(2)
    if args.json:
        out: Dict[str, Any] = dict(dump)
        out["meta"] = _meta(args.backend)
        try:
            from repro import obs
            out["obs"] = obs.snapshot()
        except Exception:                             # pragma: no cover
            pass
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
        print(f"\nwrote {args.json}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
