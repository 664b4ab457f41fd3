"""Operator kinds and traffic kinds are files found by name: a new one
is added with new files and ``BENCHMARK.json`` entries alone."""
import json

import numpy as np
import pytest

import cells
import control
import reference
import run
import work
from conftest import BENCH, make_checkout

#: a dense operator kind that no file of the benchmark knows: the
#: tridiagonal ``2.5 I - (shift up + shift down)``, symmetric positive
#: definite, stored dense
TRIDIAG = '''
import functools
import numpy as np

SERVED = False


def nnz(config):
    n = int(config["params"]["n"])
    return n * n


def operator_bytes(config, itemsize):
    return nnz(config) * itemsize


def _host(n):
    return 2.5 * np.eye(n) - np.eye(n, k=1) - np.eye(n, k=-1)


@functools.lru_cache(maxsize=None)
def _builder(n, dtype):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build():
        i = jnp.arange(n)
        d = i[:, None] - i[None, :]
        a = jnp.where(d == 0, 2.5, jnp.where(jnp.abs(d) == 1, -1.0, 0.0))
        return {"A": a.astype(dtype)}
    return build


def build(config, seed):
    return _builder(int(config["params"]["n"]), config["dtype"])()


def reference_matvec(config, seed):
    return _host(int(config["params"]["n"])).__matmul__


def bf16_operator(config, seed):
    import jax.numpy as jnp
    return jnp.asarray(_host(int(config["params"]["n"])), jnp.bfloat16)


def bf16_rows_matvec(a, p):
    return p @ a.T
'''

TRIDIAG_CONFIG = {
    "name": "tri-small", "source": "https://example.org/a-new-kind",
    "workload": "cg", "params": {"n": 128, "iters": 12},
    "dtype": "float32", "operator": {"kind": "tridiag"},
    "check_sample": 4, "check_block": 4,
    "limits": {"x_err": 1e-4, "x_maxerr": 3e-4, "r_gap": 1e-4},
    "reduced": []}

#: a traffic kind that no file of the benchmark knows: solves back to
#: back, taking the right-hand sides of the pool in reverse order
REVERSED = '''
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "closed_solve_base", pathlib.Path(__file__).with_name("closed_solve.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)


class ReversedSolve(_base.ClosedSolve):

    def _feeds(self, i):
        return dict(self.operator, b=self.rhs[-1 - i % len(self.rhs)],
                    x0=self.x0)

    def answers(self):
        return [(self.rhs[-1 - i % len(self.rhs)], x, r)
                for i, (x, r) in sorted(self.kept, key=lambda ix: ix[0])]


DRIVER = ReversedSolve
'''


def _line(capsys, rc):
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_new_operator_kind_is_added_by_files(tmp_path, capsys,
                                                monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    root = make_checkout(tmp_path / "co",
                         extra_cells={"tri-small.solve":
                                      ("tri-small", "solve-closed1")},
                         extra_configs={"tri-small": TRIDIAG_CONFIG})
    bench_dir = root / "benchmarks" / "chip"
    (bench_dir / "operators" / "tridiag.py").write_text(TRIDIAG)
    cell = cells.load(root, "tri-small.solve")
    kind = cells.operator_kind(cell)
    assert work.solve_work(cell.config, kind)["flops"] == \
        13 * 2 * 128 * 128 + 12 * 10 * 128 + 3 * 128
    line = _line(capsys, run.main(
        ["--workload", "tri-small.solve", "--seed", str(2**40 + 3),
         "--seconds", "1", "--trace", "0"], root=root, allow_cpu=True))
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0
    # its control, the recurrence in bfloat16, reads over a limit
    (rec,) = control.main(["--workload", "tri-small.solve", "--seeds", "5"],
                          root=root)
    assert any(c["value"] > c["limit"] for c in rec["compared"].values())


def test_a_new_traffic_kind_is_added_by_files(tmp_path, capsys,
                                               monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    root = make_checkout(tmp_path / "co",
                         extra_cells={"sparse-small.reversed":
                                      ("sparse-small", "solve-reversed")})
    bench_dir = root / "benchmarks" / "chip"
    (bench_dir / "drivers" / "reversed_solve.py").write_text(REVERSED)
    (bench_dir / "traffic" / "solve-reversed.json").write_text(
        json.dumps({"kind": "reversed_solve", "rhs_pool": 3}))
    cell = cells.load(root, "sparse-small.reversed")
    assert cells.driver_class(cell).__name__ == "ReversedSolve"
    line = _line(capsys, run.main(
        ["--workload", "sparse-small.reversed", "--seed", "11",
         "--seconds", "1", "--trace", "0"], root=root, allow_cpu=True))
    assert line["correct"] is True, line["compared"]


def test_a_served_cell_refuses_an_operator_the_service_does_not_make(
        tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    root = make_checkout(tmp_path / "co",
                         extra_cells={"dense-small.serve":
                                      ("dense-small", "serve-open-small")})
    with pytest.raises(RuntimeError, match="not the one the solver service"):
        run.main(["--workload", "dense-small.serve", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], root=root,
                 allow_cpu=True)


def test_laplacian5_csr_is_the_stencil_its_reference_applies():
    config = {"params": {"n": 32 * 32}, "dtype": "float32"}
    kind = cells._module(BENCH, "operators", "laplacian5")
    csr = {k: np.asarray(v) for k, v in kind.build(config, 0).items()}
    assert len(csr["A.data"]) == kind.nnz(config)
    v = np.random.default_rng(0).standard_normal(32 * 32)
    got = reference.csr_matvec(csr["A.indptr"], csr["A.indices"],
                               csr["A.data"].astype(np.float64))(v)
    np.testing.assert_allclose(got, kind.reference_matvec(config, 0)(v),
                               rtol=0, atol=1e-12)


@pytest.mark.parametrize("kind_name, config_name",
                         [("rbf_kernel", "gp-kin8nm"),
                          ("service_spd", "gp-dense-8k")])
def test_dense_kinds_are_symmetric_positive_definite(kind_name,
                                                     config_name):
    config = json.loads((BENCH / "configs" / f"{config_name}.json")
                        .read_text())
    config["params"] = dict(config["params"], n=128)
    kind = cells._module(BENCH, "operators", kind_name)
    a = kind.reference_matvec(config, 7)(np.eye(128))
    np.testing.assert_allclose(a, a.T, rtol=0, atol=1e-6)
    assert np.linalg.eigvalsh(a)[0] > 0
