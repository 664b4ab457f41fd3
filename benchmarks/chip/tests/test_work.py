"""work.py and peaks.json: exact work of both configurations, no default
peak."""
import json

import pytest

import cells
import work
from conftest import BENCH


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _work(name):
    config = _config(name)
    return work.solve_work(config, cells._module(
        BENCH, "operators", config["operator"]["kind"]))


def test_poisson2d_1m_work():
    # n = 1024^2 rows, nnz = 5n - 4*1024 = 5,238,784; 51 spmvs each read
    # values and column indices (8 B an entry), n + 1 row pointers, x in
    # and y out; 50 state passes of 7 vectors; set-up b, x0 in, r0 out
    n, nnz = 1048576, 5238784
    kind = cells._module(BENCH, "operators", "laplacian5")
    assert kind.nnz(_config("poisson2d-1m")) == nnz
    spmv_bytes = nnz * 8 + (n + 1) * 4 + 2 * n * 4
    assert spmv_bytes == 54493188
    assert _work("poisson2d-1m") == {
        "bytes": 51 * spmv_bytes + 50 * 7 * n * 4 + 3 * n * 4,
        "flops": 51 * 2 * nnz + 50 * 10 * n + 3 * n,
    }
    assert _work("poisson2d-1m") == {
        "bytes": 4259741900, "flops": 1061789696}


def test_gp_kin8nm_work():
    # 51 matvecs each stream the 256 MiB kernel matrix; 50 state passes
    n = 8192
    assert _work("gp-kin8nm") == {
        "bytes": 51 * (n * n * 4 + 2 * n * 4) + 50 * 7 * n * 4 + 3 * n * 4,
        "flops": 51 * 2 * n * n + 50 * 10 * n + 3 * n,
    }
    assert _work("gp-kin8nm") == {
        "bytes": 13705117696, "flops": 6849224704}


def test_gp_dense_8k_work():
    # the service's operator: 33 matvecs, 32 state passes
    n = 8192
    assert _work("gp-dense-8k") == {
        "bytes": 33 * (n * n * 4 + 2 * n * 4) + 32 * 7 * n * 4 + 3 * n * 4,
        "flops": 33 * 2 * n * n + 32 * 10 * n + 3 * n,
    }
    assert _work("gp-dense-8k") == {
        "bytes": 8867971072, "flops": 4431831040}


def test_least_time_is_bandwidth_bound_on_v5e():
    peaks = work.peaks_for("TPU v5 lite")
    assert peaks == {"flops_bf16_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                     "hbm_bytes": 16e9}
    secs, bound = work.least_time_s(_work("gp-kin8nm"), peaks)
    assert bound == "hbm"
    assert secs == pytest.approx(13705117696 / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks_for("TPU v9 imaginary")
