"""Fixtures of the benchmark's CPU tests: a checkout with small cells.

Run by path, from the root of the repository:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

#: small stand-ins for the chip's configurations: the same workloads,
#: operators, limits and drivers at sizes a CPU test run holds
SMALL_CONFIGS = {
    "dense-small": ("gp-kin8nm", {"n": 256, "iters": 50}, 16),
    "served-small": ("gp-dense-8k", {"n": 256, "iters": 32}, 16),
    "sparse-small": ("poisson2d-1m",
                     {"n": 1024, "iters": 50, "pattern": "laplacian5"}, 2),
}
SMALL_CELLS = {
    "dense-small.solve": ("dense-small", "solve-closed1"),
    "sparse-small.solve": ("sparse-small", "solve-closed1"),
    "served-small.serve": ("served-small", "serve-open-small"),
    "served-small.serve-closed": ("served-small", "serve-closed-small"),
}
#: the small cell that stands in for each cell of the benchmark
TWIN = {"poisson2d-1m.solve": "sparse-small.solve",
        "gp-kin8nm.solve": "dense-small.solve",
        "gp-dense-8k.serve": "served-small.serve",
        "gp-dense-8k.serve-closed16": "served-small.serve-closed"}
SMALL_TRAFFIC = {
    "serve-open-small": {"kind": "open_serve", "rate_per_s": 20,
                         "max_batch_size": 4},
    "serve-closed-small": {"kind": "closed_serve", "clients": 4,
                           "max_batch_size": 4, "rhs_pool": 8},
}


def make_checkout(root: pathlib.Path, extra_cells=None,
                  extra_configs=None) -> pathlib.Path:
    """A checkout at ``root``: the benchmark's files, the program (linked),
    and a ``BENCHMARK.json`` with the small cells (and ``extra_cells``:
    ``{name: (config, traffic)}``, and ``extra_configs``: ``{name: file
    content}``, written under ``configs/``)."""
    bench_dst = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dst,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = []
    for name, (base, params, sample) in SMALL_CONFIGS.items():
        cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
        cfg.update(name=name, params=params, check_sample=sample)
        path = bench_dst / "configs" / f"{name}.json"
        path.write_text(json.dumps(cfg))
        configs.append({"name": name, "source": cfg["source"],
                        "file": str(path.relative_to(root)), "reduced": [],
                        "why": "small stand-in for a CPU test"})
    for name, cfg in (extra_configs or {}).items():
        path = bench_dst / "configs" / f"{name}.json"
        path.write_text(json.dumps(cfg))
        configs.append({"name": name, "source": cfg["source"],
                        "file": str(path.relative_to(root)), "reduced": [],
                        "why": "a configuration added by files alone"})
    for name, mix in SMALL_TRAFFIC.items():
        (bench_dst / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    cells = dict(SMALL_CELLS, **(extra_cells or {}))
    bench["configs"] += configs
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                           "why": "small stand-in for a CPU test"}
                          for n, (c, t) in cells.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TWIN[w] for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    return make_checkout(tmp_path / "checkout")
