"""Chip benchmark of the co-designed solver: one cell per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs from the root of a checkout, on the machine that holds the chips the
cell asks for, in this one process:

1. finds the cell in ``BENCHMARK.json`` and its configuration and traffic
   files (:mod:`cells`); refuses to start without a TPU, with fewer chips
   than the cell asks for, or with Pallas kernels interpreted;
2. builds the operator (``operators/<kind>.py``) and inputs from
   ``--seed``, stages and lowers the plan through the user's entry
   points, and warms up every shape the window uses (the traffic kind's
   driver, ``drivers/<kind>.py``); all of that, from process start, is
   ``setup_s``;
3. measures for ``--seconds`` (under the profiler with ``--trace 1``);
4. compares answers due in the window with a float64 reference
   (:mod:`reference`, on the operator kind's own product) once the window
   has closed, its peak memory has been read and the program's state is
   freed;
5. prints one JSON line last on standard output: the cell's end-to-end
   metrics (``--trace 0``) or its per-layer metrics (``--trace 1``, read by
   ``metrics/<name>.py`` from the trace reduction, :mod:`trace_reduce`),
   and last, under ``compared``, each number compared with its limit,
   which also close standard error.

JAX's persistent compilation cache lives in ``.jax_cache/`` at the root of
the checkout, so only the first run of a cell there compiles.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse                                              # noqa: E402
import gc                                                    # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import pathlib                                               # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
import tempfile                                              # noqa: E402
from typing import Any, Dict, List, Optional                 # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import cells                                                 # noqa: E402
import reference                                             # noqa: E402
import trace_reduce                                          # noqa: E402
import work                                                  # noqa: E402

EXIT_CELL = 2          # the cell, its files or the program cannot be found
EXIT_DEVICE = 3        # no TPU, too few chips, or interpreted kernels


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _import_program(root: pathlib.Path):
    """Import ``repro`` from this checkout's ``src/`` and nowhere else."""
    src = root / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise cells.CellError(f"the program is not in this checkout: no "
                              f"{src / 'repro'}")
    sys.path.insert(0, str(src))
    import repro
    where = pathlib.Path(repro.__file__).resolve()
    if src.resolve() not in where.parents:
        raise cells.CellError(f"imported repro from {where}, not from {src}")
    return repro


def _device_refusal(devices, chips: int, allow_cpu: bool) -> Optional[str]:
    """Why the cell cannot run on ``devices``, or ``None`` where it can."""
    if devices[0].platform != "tpu" and not allow_cpu:
        return f"no TPU: JAX reports platform {devices[0].platform!r}"
    if len(devices) < chips:
        return f"the cell asks for {chips} chips; JAX found {len(devices)}"
    from repro.exec.pallas import use_interpret
    if use_interpret() and not allow_cpu:
        return "Pallas kernels would run interpreted; refusing"
    return None


def _peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _start_trace(log_dir: str) -> None:
    import jax
    from repro import obs
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    # the program's own spans (serve.*, exec.*) land in the trace too
    obs.tracer().enable(jax_profiler=True)
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def _stop_trace() -> None:
    import jax
    from repro import obs
    jax.profiler.stop_trace()
    obs.tracer().disable()
    obs.tracer().clear()


def main(argv: Optional[List[str]] = None, *, root: pathlib.Path = ROOT,
         allow_cpu: bool = False) -> int:
    """Run one cell; returns the exit code.  ``allow_cpu`` lets a test
    drive the whole run on the CPU with interpreted kernels."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")

    try:
        cell = cells.load(root, args.workload)
        readers = {m["name"]: cells.metric_reader(m["name"], cell.bench_dir)
                   for m in cell.per_layer}
        kind = cells.operator_kind(cell)
        driver = cells.driver_class(cell)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
        _import_program(root)
    except cells.CellError as e:
        _log(str(e))
        return EXIT_CELL
    import jax
    devices = jax.devices()
    refusal = _device_refusal(devices, cell.chips, allow_cpu)
    if refusal:
        _log(refusal)
        return EXIT_DEVICE
    from repro.runtime import enable_compile_cache
    cache_dir = enable_compile_cache()
    device_kind = devices[0].device_kind
    peaks = None
    if devices[0].platform == "tpu":
        peaks = work.peaks_for(device_kind)
    _log(f"cell {cell.name}: device_kind={device_kind} "
         f"devices={len(devices)} compile_cache={cache_dir}")

    drv = driver(cell.config, cell.traffic, args.seed, args.seconds, _log,
                 kind)
    drv.setup()
    setup_s = time.perf_counter() - _T_START
    split = drv.setup_split
    _log("set-up: " + " ".join(f"{k}={v:.3f}" for k, v in split.items())
         + f" total setup_s={setup_s:.3f}")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    try:
        if trace_dir:
            _start_trace(trace_dir)
            drv.prime()
        try:
            result = drv.window(args.seconds)
        finally:
            if trace_dir:
                _stop_trace()
        drv.finish()
        _log(f"compiles inside the window: {drv.compiles.count}")
        peak = _peak_bytes(devices[:cell.chips])
        _log(f"peak_bytes_in_use={peak}")

        answers = drv.answers()
        ctx: Dict[str, Any] = {
            "config": cell.config, "setup": split, "peaks": peaks,
            "work": work.solve_work(cell.config, kind),
            "solves": drv.solves_in_window, "window_s": drv.window_s,
            "serve": drv.serve_layer, "trace": None}
        drv.free()
        gc.collect()

        reduction = None
        if trace_dir:
            events = trace_reduce.load_xplane(
                trace_reduce.find_xplane(trace_dir))
            reduction = trace_reduce.reduce_events(events)
            ctx["trace"] = reduction
            _log(f"trace: busy {reduction['busy_s']:.6f} s of "
                 f"{reduction['window_s']:.6f} s; pallas "
                 f"{reduction['pallas_s']:.6f} s, other "
                 f"{reduction['other_s']:.6f} s")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    limits = cell.config["limits"]
    if answers:
        compared = reference.judge(drv.check(answers), limits)
    else:                                   # nothing answered: no reading
        compared = {k: {"value": None, "limit": float(v)}
                    for k, v in limits.items()}
    _log(f"reference: {len(answers)} answers compared in "
         f"{time.perf_counter() - t_ref:.3f} s")
    correct = result["failed"] == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())

    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            value = readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        if peaks and drv.solves_in_window:
            least, bound = work.least_time_s(ctx["work"], peaks)
            _log(f"least time per solve {least * 1e3:.6f} ms, bound by "
                 f"{bound}")
    else:
        # a name's ".<suffix>" only picks the cells a bound holds for:
        # "solve_ms.sparse" is the driver's "solve_ms"
        values = dict(result["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"].split(".")[0]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    device: Dict[str, Any] = {"platform": devices[0].platform,
                              "kind": device_kind,
                              "count": len(devices),
                              "memory_peak_bytes": peak}
    line: Dict[str, Any] = {"correct": bool(correct),
                            "attempted": int(result["attempted"]),
                            "failed": int(result["failed"]),
                            "metrics": metrics, "device": device}
    if reduction is not None:
        device.update(busy_s=reduction["busy_s"],
                      window_s=reduction["window_s"])
        line["breakdown"] = {"device_ops": reduction["device_ops"],
                             "idle_gaps": reduction["idle_gaps"]}
    line["compared"] = compared
    for name, c in compared.items():
        _log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
