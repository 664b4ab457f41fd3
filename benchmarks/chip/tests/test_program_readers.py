"""The readers of the program's own instruments: counters and histograms
read from its ``repro.obs`` registry, and device time mapped to the
program's named scopes."""
import pytest

import cells
import device_scopes
from conftest import BENCH


@pytest.fixture
def registry(monkeypatch):
    """A fresh registry in place of the program's global one."""
    from repro import obs
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "registry", lambda: reg)
    return reg


def _read(name, ctx=None):
    return cells.metric_reader(name, BENCH)(ctx or {})


def test_feed_copy_per_solve(registry):
    assert _read("feed_copy_mib_per_solve.dense") is None     # no program
    copied = registry.counter("exec.feed_copy_bytes")
    runs = registry.counter("exec.dispatches")
    assert _read("feed_copy_mib_per_solve.dense") is None     # no runs
    for scope in ("pallas-1", "pallas-2"):
        runs.inc(3, backend="pallas", scope=scope)
        copied.inc(3 * 2**20, backend="pallas", scope=scope)
    runs.inc(5, backend="pallas-perunit", scope="perunit-3")
    assert _read("feed_copy_mib_per_solve.dense") == pytest.approx(1.0)
    assert _read("feed_copy_mib_per_solve.sparse") == pytest.approx(1.0)


def test_exec_host_ms_leaves_out_traced_runs(registry):
    assert _read("exec_host_ms.sparse") is None
    h = registry.histogram("exec.dispatch_s")
    h.observe(40.0, backend="pallas", traced=True)             # a compile
    assert _read("exec_host_ms.sparse") is None
    for v in (0.002, 0.004):
        h.observe(v, backend="pallas", traced=False)
    h.observe(1.0, backend="reference", traced=False)
    assert _read("exec_host_ms.sparse") == pytest.approx(3.0)


def test_serve_readers(registry):
    for name in ("serve_h2d_mib_per_batch", "serve_launch_ms",
                 "serve_readback_ms"):
        assert _read(name) is None
    sent = registry.counter("serve.h2d_bytes")
    batches = registry.counter("serve.batch_dispatches")
    for scope in ("batched-1", "batched-2"):
        sent.inc(2 * 256 * 2**20, backend="pallas", scope=scope)
        batches.inc(2, backend="pallas", scope=scope)
    assert _read("serve_h2d_mib_per_batch") == pytest.approx(256.0)
    launch = registry.histogram("serve.launch_s")
    launch.observe(3.0, backend="pallas", scope="batched-1", traced=True)
    launch.observe(0.030, backend="pallas", scope="batched-1", traced=False)
    launch.observe(0.050, backend="pallas", scope="batched-2", traced=False)
    assert _read("serve_launch_ms") == pytest.approx(40.0)
    readback = registry.histogram("serve.readback_s")
    readback.observe(0.080, backend="pallas", scope="batched-1")
    assert _read("serve_readback_ms") == pytest.approx(80.0)


def _ctx(device_ops, solves=4):
    return {"config": {"workload": "cg_sparse", "dtype": "float32",
                       "params": {"n": 1024, "iters": 4,
                                  "pattern": "laplacian5"}},
            "solves": solves,
            "trace": {"device_ops": device_ops, "busy_s": 1.0,
                      "window_s": 1.0}}


def test_scope_readers_map_op_names(monkeypatch):
    monkeypatch.setattr(device_scopes, "scopes_of", lambda config: {
        "fusion.6": "spmv_gather", "fusion.2": "spmv_gather",
        "fusion.1": "csr_layout", "while.3": "csr_layout"})
    ops = [["fusion.6", 1.6], ["cello_stream_Ap1", 0.3],
           ["fusion.2", 0.04], ["while.3", 0.02], ["copy.1", 0.01]]
    ctx = _ctx(ops)
    assert _read("gather_ms_per_solve.sparse", ctx) == \
        pytest.approx(1.64 / 4 * 1e3)
    assert device_scopes.ms_per_solve(ctx, "csr_layout") == \
        pytest.approx(0.02 / 4 * 1e3)
    # no trace, no solves, or no op of the scope among the ten: nothing
    assert _read("gather_ms_per_solve.sparse", dict(ctx, trace=None)) \
        is None
    assert _read("gather_ms_per_solve.sparse", dict(ctx, solves=0)) is None
    assert device_scopes.ms_per_solve(_ctx([["fusion.6", 1.0]]),
                                      "csr_layout") is None


def test_scopes_of_a_small_sparse_plan():
    device_scopes._scopes.cache_clear()
    scopes = device_scopes.scopes_of(_ctx([])["config"])
    assert set(scopes.values()) == {"spmv_gather", "csr_layout"}


def test_a_program_without_scopes_reads_nothing(monkeypatch):
    from repro.api import CompiledPlan
    monkeypatch.delattr(CompiledPlan, "device_scopes")
    device_scopes._scopes.cache_clear()
    try:
        assert device_scopes.scopes_of(_ctx([])["config"]) == {}
        assert _read("gather_ms_per_solve.sparse",
                     _ctx([["fusion.6", 1.0]])) is None
    finally:
        device_scopes._scopes.cache_clear()
