"""The harness: cells found by name, refusals without a TPU or without
the program, and the arithmetic of its end-to-end metrics."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import cells
import run
import stats
from conftest import BENCH, REPO, make_checkout


def test_a_new_cell_is_found_by_name(tmp_path):
    root = make_checkout(tmp_path / "co")
    traffic = root / "benchmarks" / "chip" / "traffic" / "solve-closed2.json"
    traffic.write_text(json.dumps({"kind": "closed_solve", "rhs_pool": 2}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "sparse-small.solve2",
                               "config": "sparse-small",
                               "traffic": "solve-closed2", "chips": 1,
                               "why": "a cell added by files alone"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "sparse-small.solve" in m.get("workloads", ()):
            m["workloads"].append("sparse-small.solve2")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load(root, "sparse-small.solve2")
    assert cell.traffic == {"kind": "closed_solve", "rhs_pool": 2}
    assert cell.config["params"]["n"] == 1024
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "solve_ms.sparse"]
    assert {m["name"] for m in cell.per_layer} == {
        "codesign_s", "warmup_s", "pallas_ms_per_solve.sparse",
        "xla_ms_per_solve.sparse", "device_idle_pct.sparse",
        "solve_roofline.sparse"}
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"], cell.bench_dir))


def test_every_benchmark_metric_has_a_reader_and_every_cell_reports():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = cells.load(REPO, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        # each end-to-end metric is one the cell's driver reports
        for name in names - {"setup_s"}:
            assert name.split(".")[0] in ("solve_ms", "serve_p95_ms",
                                          "serve_solves_per_s"), name
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in names
            cells.metric_reader(m["name"])
        assert cells.operator_kind(cell).reference_matvec
        assert cells.driver_class(cell).window


def test_split_names_share_one_reader():
    """``<quantity>.<part>`` is read by ``metrics/<quantity>.py``."""
    for part in ("sparse", "dense", "serve"):
        assert cells.metric_reader(f"device_idle_pct.{part}") is \
            cells.metric_reader("device_idle_pct")
    with pytest.raises(cells.CellError, match="no file"):
        cells.metric_reader("no_such_metric.dense")


def test_roofline_reads_device_time_per_solve():
    """The whole solve's share of the roofline is over the device's busy
    time per solve, not the host's window."""
    config = json.loads((BENCH / "configs" / "gp-kin8nm.json").read_text())
    import work
    kind = cells._module(BENCH, "operators", "rbf_kernel")
    w = work.solve_work(config, kind)
    peaks = work.peaks_for("TPU v5 lite")
    least, _ = work.least_time_s(w, peaks)
    ctx = {"trace": {"busy_s": 2.0, "window_s": 30.0}, "solves": 100,
           "peaks": peaks, "work": w, "window_s": 30.0}
    value = cells.metric_reader("solve_roofline.dense")(ctx)
    assert value == pytest.approx(least / 0.02 * 100)
    assert cells.metric_reader("solve_roofline.dense")(
        dict(ctx, trace=None)) is None


def test_unknown_cell_is_refused(tmp_path):
    root = make_checkout(tmp_path / "co")
    with pytest.raises(cells.CellError, match="no workload"):
        cells.load(root, "no-such.cell")


def test_no_tpu_exits_nonzero_without_a_result(capsys, monkeypatch,
                                               tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = run.main(["--workload", "gp-kin8nm.solve", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == run.EXIT_DEVICE
    assert capsys.readouterr().out == ""


def test_only_the_benchmark_files_exit_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "gp-kin8nm.solve", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "not in this checkout" in proc.stderr


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 95) == 95
    assert stats.percentile(values, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([5, 1, 4, 2, 3], 50) == 3
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate():
    assert stats.rate(90, 30.0) == 3.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
