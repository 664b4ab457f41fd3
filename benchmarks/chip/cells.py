"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell is one entry of ``workloads``: a configuration and a traffic mix.
Everything that belongs to one configuration, mix, kind or metric is a
file of its own, found by name, so each is added with new files and
entries alone:

* ``configs/<config>.json``: the deployment (workload builder and its
  parameters, dtype, operator, the correctness limits);
* ``operators/<kind>.py``: one file per ``operator.kind`` of a
  configuration: the operator made on the device, its float64 reference
  product, its bfloat16 control product and the bytes it occupies;
* ``traffic/<traffic>.json``: the mix, whose ``kind`` names its driver
  and whose other keys are the driver's parameters;
* ``drivers/<kind>.py``: one driver per traffic ``kind``;
* ``metrics/<metric>.py``: one reader per per-layer metric.  A metric
  named ``<quantity>.<part>`` (one quantity split by the end-to-end metric
  it moves) is read by ``metrics/<quantity>.py`` unless it has a file of
  its own.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
#: where the benchmark lives, relative to the root of a checkout
BENCH_DIR = pathlib.Path("benchmarks/chip")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    #: the checkout's benchmark directory, where the cell's files lie
    bench_dir: pathlib.Path = HERE


class CellError(ValueError):
    """The cell named on the command line cannot be found or read."""


def _load_json(path: pathlib.Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise CellError(f"cannot read {path}: {e}") from None


def _applies(metric: Dict[str, Any], cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def load(root: pathlib.Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its
    configuration, traffic and the metrics it reports."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"have {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if entry["config"] not in configs:
        raise CellError(f"workload {workload!r} names config "
                        f"{entry['config']!r}, which BENCHMARK.json lacks")
    config = _load_json(root / configs[entry["config"]]["file"])
    traffic = _load_json(root / BENCH_DIR / "traffic"
                         / f"{entry['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, workload, reported)]
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer,
                bench_dir=root / BENCH_DIR)


@functools.lru_cache(maxsize=None)
def _module(bench_dir: pathlib.Path, folder: str, name: str) -> ModuleType:
    """``<bench_dir>/<folder>/<name>.py``, loaded once."""
    path = bench_dir / folder / f"{name}.py"
    if not path.is_file():
        raise CellError(f"no file {path} for {folder} entry {name!r}")
    tag = "".join(c if c.isalnum() else "_" for c in f"{folder}_{name}")
    spec = importlib.util.spec_from_file_location(f"chip_{tag}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def operator_kind(cell: Cell) -> ModuleType:
    """The file of the cell's ``operator.kind``."""
    return _module(cell.bench_dir, "operators",
                   cell.config["operator"]["kind"])


def driver_class(cell: Cell):
    """The ``DRIVER`` class of the cell's traffic ``kind``."""
    return _module(cell.bench_dir, "drivers", cell.traffic["kind"]).DRIVER


def metric_reader(name: str, bench_dir: pathlib.Path = HERE
                  ) -> Callable[[Dict[str, Any]], Optional[float]]:
    """The ``read(ctx)`` function of the metric's reader."""
    own = bench_dir / "metrics" / f"{name}.py"
    return _module(bench_dir, "metrics", name if own.is_file()
                   else name.split(".")[0]).read
