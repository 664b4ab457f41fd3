"""What the traffic drivers share.

A traffic file (``traffic/<mix>.json``) names its ``kind`` and gives the
parameters; the driver of that kind (``drivers/<kind>.py``, whose
``DRIVER`` is a subclass of :class:`Driver`) runs it against the program
through the entry points a user calls.  Each driver does its set-up
(operator, plan, warm-up of every shape the window uses) in ``setup``,
runs the window in ``window``, and hands back the answers due in the
window, as ``(b, x, r)`` on the host, for the reference to check.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import inputs
import reference

WINDOW_SPAN = "bench.window"
#: how long after the window closes the driver waits for answers due in it
ANSWER_WAIT_S = 60.0

Answer = Tuple[np.ndarray, np.ndarray, np.ndarray]        # (b, x, r)


def annotation(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class CompileCounter:
    """Counts JAX traces and compiles (cache loads included) while on."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kwargs) -> None:
        if self.on and event in self.EVENTS:
            self.count += 1


def stage_seconds() -> float:
    """Seconds the program's staging pipeline (trace, analyze, codesign,
    lower) has spent so far, from its ``session.stage_s`` histogram."""
    from repro import obs
    cells = obs.registry().snapshot().get("session.stage_s", {})
    return sum(c["value"]["sum"] for c in cells.get("cells", []))


def hist_totals(name: str) -> Tuple[float, int]:
    """``(sum, count)`` of one of the program's histograms, all cells."""
    from repro import obs
    cells = obs.registry().snapshot().get(name, {}).get("cells", [])
    return (sum(c["value"]["sum"] for c in cells),
            sum(c["value"]["count"] for c in cells))


class Driver:
    """Shared shape of the drivers; see the module docstring.

    ``kind`` is the file of the configuration's operator kind
    (``operators/<kind>.py``).
    """

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, seconds: float, log: Callable[[str], None],
                 kind: ModuleType):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.log = log
        self.kind = kind
        self.n = int(config["params"]["n"])
        self.iters = int(config["params"]["iters"])
        self.out_x = f"x{self.iters}"
        self.out_r = f"r{self.iters}"
        self.sample_rng = inputs.host_rng(seed, "sample")
        self.compiles = CompileCounter()
        self.setup_split: Dict[str, float] = {}
        self.solves_in_window: Optional[int] = None
        self.serve_layer: Optional[Dict[str, float]] = None

    def check(self, answers: List[Answer]) -> List[Dict[str, float]]:
        """Compare ``(b, x, r)`` answers with the float64 reference on the
        operator kind's own product, solving ``check_block`` right-hand
        sides per reference call, the calls in threads of their own (NumPy
        releases the interpreter lock)."""
        mv = self.kind.reference_matvec(self.config, self.seed)
        b = np.stack([np.asarray(a[0], np.float64) for a in answers], 1)
        step = int(self.config["check_block"])
        blocks = [b[:, i:i + step] for i in range(0, b.shape[1], step)]
        with ThreadPoolExecutor(len(blocks)) as pool:
            x_ref = np.concatenate(list(pool.map(
                lambda blk: reference.host_cg(mv, blk, self.iters)[0],
                blocks)), axis=1)
        return reference.compare_all(
            b, np.stack([np.asarray(a[1]) for a in answers], 1),
            np.stack([np.asarray(a[2]) for a in answers], 1), x_ref, mv)

    def prime(self) -> None:
        """One more pass through the timed path outside the window, after
        the profiler started, so its one-time costs fall outside it."""

    # -- what the control (control.py) reads through the cell's path -----
    def sample_inputs(self, count: int) -> np.ndarray:
        """The first ``count`` right-hand sides the window would use, as
        float64 rows."""
        raise NotImplementedError

    def solve_rows(self, b: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """The program's ``(x, r)`` for the rows of ``b`` through the
        cell's path, as float64 rows; no window, no warm-up."""
        raise NotImplementedError

    def _rows(self, outs) -> Tuple[np.ndarray, np.ndarray]:
        return tuple(np.asarray([np.asarray(o[key]) for o in outs],
                                np.float64)
                     for key in (self.out_x, self.out_r))


class ServeDriver(Driver):
    """Set-up and bookkeeping shared by the mixes of the solver service."""

    def setup(self) -> None:
        from repro.api import Session
        from repro.serve import ServeConfig, Server
        if not getattr(self.kind, "SERVED", False):
            raise RuntimeError(
                f"operator kind {self.config['operator']['kind']!r} is not "
                f"the one the solver service makes, so no served cell can "
                f"run it")
        self.max_batch = int(self.traffic["max_batch_size"])
        self.server = Server(config=ServeConfig(
            max_batch_size=self.max_batch, fallback=None),
            session=Session(use_cache=False))
        t0 = time.perf_counter()
        self.rhs = self._make_rhs()
        t1 = time.perf_counter()
        stage0 = stage_seconds()
        # the bucket's plan and shared operator, as the first request of a
        # cold bucket would build them
        self.key = self.server.router.bucket(self._request(0))
        self.server.router.plan_for(self.key)
        self.setup_split["codesign_s"] = stage_seconds() - stage0
        t2 = time.perf_counter()
        self._warm_lanes()
        t3 = time.perf_counter()
        self.setup_split.update(feeds_s=t1 - t0, plan_s=t2 - t1,
                                warmup_s=t3 - t2)

    def _make_rhs(self) -> np.ndarray:
        raise NotImplementedError

    def _request(self, i: int):
        from repro.serve import request
        return request(self.config["workload"], backend="pallas",
                       feeds={"b": self.rhs[i % len(self.rhs)]},
                       **self.config["params"])

    def prime(self) -> None:
        self.server.submit(self._request(0)).result(timeout=900)

    def sample_inputs(self, count: int) -> np.ndarray:
        return np.asarray(inputs.host_rhs(self.config, self.seed, count),
                          np.float64)

    def solve_rows(self, b: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        from repro.api import Session
        from repro.serve import ServeConfig, Server, request
        with Server(config=ServeConfig(
                max_batch_size=int(self.traffic["max_batch_size"]),
                fallback=None), session=Session(use_cache=False)) as srv:
            futs = [srv.submit(request(
                self.config["workload"], backend="pallas",
                feeds={"b": row.astype(self.config["dtype"])},
                **self.config["params"])) for row in b]
            return self._rows([f.result(timeout=900).outputs for f in futs])

    def _traces(self) -> int:
        return self.server.stats()["buckets"][self.key.label]["traces"]

    def _warm_lanes(self) -> None:
        """Compile every batch width the window can use: the service pads
        a batch to the next power of two, up to ``max_batch_size``."""
        lanes = [1 << i for i in range((self.max_batch - 1).bit_length()
                                       + 1)]
        for _ in range(3):
            for width in reversed(lanes):
                futs = [self.server.submit(self._request(i))
                        for i in range(width)]
                for f in futs:
                    f.result(timeout=900)
            if self._traces() >= len(lanes):
                return
        raise RuntimeError(f"warm-up compiled {self._traces()} batch widths, "
                           f"expected {len(lanes)}")

    def window_start(self) -> None:
        self.queue0 = hist_totals("serve.queue_wait_s")
        self.dispatch0 = hist_totals("serve.dispatch_s")
        self.compiles.on = True

    def _serve_layer(self) -> None:
        qs, qn = hist_totals("serve.queue_wait_s")
        ds, dn = hist_totals("serve.dispatch_s")
        qs, qn = qs - self.queue0[0], qn - self.queue0[1]
        ds, dn = ds - self.dispatch0[0], dn - self.dispatch0[1]
        self.serve_layer = {
            "queue_wait_ms": qs / qn * 1e3 if qn else None,
            "dispatch_ms": ds / dn * 1e3 if dn else None,
            "batches": dn}

    def finish(self) -> None:
        self._serve_layer()
        self.server.close()

    def answers(self) -> List[Answer]:
        """A sample, drawn from the seed, of the answered requests."""
        done = [i for i, f in enumerate(self.futs)
                if f.done() and f.exception() is None]
        if not done:
            return []
        k = int(self.config["check_sample"])
        picks = sorted(self.sample_rng.choice(done, size=min(k, len(done)),
                                              replace=False))
        out = []
        for i in picks:
            outputs = self.futs[i].result().outputs
            out.append((self.rhs[self.rhs_index[i] % len(self.rhs)],
                        outputs[self.out_x], outputs[self.out_r]))
        return out

    def free(self) -> None:
        self.server = self.futs = None
