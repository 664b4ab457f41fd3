"""A whole run on the CPU, past the look for a chip, with the timed path
broken underneath: ``correct`` comes out false for each fault the cell
can have, and true with none."""
import json

import numpy as np
import pytest

import run


def _run(root, cell, capsys, seed=2**33 + 5, seconds="1"):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   seconds, "--trace", "0"], root=root, allow_cpu=True)
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    # the numbers compared close standard error too
    assert out.err.strip().splitlines()[-1].startswith("compared ")
    return line


def _x_key(outs):
    return next(k for k in outs if k.startswith("x"))


def _r_key(outs):
    return next(k for k in outs if k.startswith("r"))


def _state_unchanged(monkeypatch):
    """Every solve hands back its starting point: no step applied."""
    from repro.api.artifacts import CompiledPlan
    real = CompiledPlan.run

    def run_(self, feeds=None, **kw):
        out = dict(real(self, feeds, **kw))
        out[_x_key(out)] = feeds["x0"]
        return out
    monkeypatch.setattr(CompiledPlan, "run", run_)


def _solve_answer_altered(monkeypatch):
    """One entry of every solution is changed where it is produced."""
    from repro.api.artifacts import CompiledPlan
    real = CompiledPlan.run

    def run_(self, feeds=None, **kw):
        out = dict(real(self, feeds, **kw))
        x = out[_x_key(out)]
        out[_x_key(out)] = x.at[7].add(2 * abs(x).max())
        return out
    monkeypatch.setattr(CompiledPlan, "run", run_)


def _solve_residual_altered(monkeypatch):
    """Every solve hands back its right-hand side as its residual, the
    residual of its starting point."""
    from repro.api.artifacts import CompiledPlan
    real = CompiledPlan.run

    def run_(self, feeds=None, **kw):
        out = dict(real(self, feeds, **kw))
        out[_r_key(out)] = feeds["b"]
        return out
    monkeypatch.setattr(CompiledPlan, "run", run_)


def _served_residual_altered(monkeypatch):
    """One entry of every served residual is changed where the batch
    produces it."""
    from repro.serve.batched import BatchedPlan
    real = BatchedPlan.run_many

    def run_many(self, requests, shared, **kw):
        outs = real(self, requests, shared, **kw)
        for i, out in enumerate(outs):
            r = np.array(out[_r_key(out)], copy=True)
            r[5] += np.abs(r).max() + 1e-2 * np.abs(requests[i]["b"]).max()
            outs[i] = dict(out, **{_r_key(out): r})
        return outs
    monkeypatch.setattr(BatchedPlan, "run_many", run_many)


def _half_batch_left_out(monkeypatch):
    """Each batch keeps the answers of its first half only; the rest get
    the mean of those answers."""
    from repro.serve.batched import BatchedPlan
    real = BatchedPlan.run_many

    def run_many(self, requests, shared, **kw):
        outs = real(self, requests, shared, **kw)
        keep = max(1, len(outs) // 2)
        mean = {k: np.mean([o[k] for o in outs[:keep]], axis=0)
                for k in outs[0]}
        return outs[:keep] + [dict(mean) for _ in outs[keep:]]
    monkeypatch.setattr(BatchedPlan, "run_many", run_many)


def _served_answer_altered(monkeypatch):
    """One entry of every served answer is changed where the batch
    produces it."""
    from repro.serve.batched import BatchedPlan
    real = BatchedPlan.run_many

    def run_many(self, requests, shared, **kw):
        outs = real(self, requests, shared, **kw)
        for i, out in enumerate(outs):
            x = out[_x_key(out)].copy()
            x[3] += 2 * np.abs(x).max()
            outs[i] = dict(out, **{_x_key(out): x})
        return outs
    monkeypatch.setattr(BatchedPlan, "run_many", run_many)


@pytest.mark.parametrize("cell", ["dense-small.solve", "sparse-small.solve",
                                  "served-small.serve",
                                  "served-small.serve-closed"])
def test_sound_run_is_correct(checkout, capsys, cell):
    line = _run(checkout, cell, capsys)
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) >= {"setup_s"}
    assert set(line["compared"]) == {"x_err", "x_maxerr", "r_gap"}


@pytest.mark.parametrize("cell, fault", [
    ("dense-small.solve", _state_unchanged),
    ("sparse-small.solve", _state_unchanged),
    ("dense-small.solve", _solve_answer_altered),
    ("sparse-small.solve", _solve_answer_altered),
    ("dense-small.solve", _solve_residual_altered),
    ("sparse-small.solve", _solve_residual_altered),
    ("served-small.serve-closed", _half_batch_left_out),
    ("served-small.serve", _served_answer_altered),
    ("served-small.serve-closed", _served_answer_altered),
    ("served-small.serve", _served_residual_altered),
])
def test_fault_is_caught(checkout, capsys, monkeypatch, cell, fault):
    fault(monkeypatch)
    line = _run(checkout, cell, capsys)
    assert line["correct"] is False, line["compared"]
