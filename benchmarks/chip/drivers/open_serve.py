"""Traffic kind ``open_serve``: open-loop arrivals at the solver service.

Requests arrive at ``rate_per_s`` for the whole window, each with its own
right-hand side, at ``repro.serve.Server.submit`` (batches up to
``max_batch_size``).  The gaps between arrivals are the same set for every
seed (quantiles of the exponential distribution, so a Poisson stream in
its long-run mix) in an order drawn from the seed: every seed offers the
same work.  Each request is timed from when it was due to when its answer
came back.  Reports ``serve_p95_ms`` over all requests of the window (a
failed one counts as missing) and ``serve_solves_per_s`` completed in it.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Any, Dict, List

import numpy as np

import inputs
import stats
from driver_base import (ANSWER_WAIT_S, WINDOW_SPAN, ServeDriver,
                         annotation)


class OpenServe(ServeDriver):

    def _make_rhs(self) -> np.ndarray:
        self.count = int(round(float(self.traffic["rate_per_s"])
                               * self.seconds))
        return inputs.host_rhs(self.config, self.seed, self.count)

    def arrivals(self) -> np.ndarray:
        """Offsets of the arrivals from the window's start, in seconds."""
        rate = float(self.traffic["rate_per_s"])
        q = (np.arange(self.count) + 0.5) / self.count
        gaps = -np.log1p(-q) / rate
        gaps *= self.seconds / gaps.sum()
        inputs.host_rng(self.seed, "arrivals").shuffle(gaps)
        return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])

    def window(self, seconds: float) -> Dict[str, Any]:
        offsets = self.arrivals()
        n = len(offsets)
        self.futs: List[Any] = [None] * n
        self.rhs_index = list(range(n))
        reqs = [self._request(i) for i in range(n)]
        sent = np.zeros(n)
        done = np.full(n, math.inf)

        def stamp(i):
            def cb(_):
                done[i] = time.perf_counter()
            return cb

        def generate(t0):
            for i in range(n):
                delay = t0 + offsets[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                with annotation("bench.submit"):
                    sent[i] = time.perf_counter()
                    fut = self.server.submit(reqs[i])
                    self.futs[i] = fut
                    fut.add_done_callback(stamp(i))

        self.window_start()
        with annotation(WINDOW_SPAN):
            t0 = time.perf_counter()
            gen = threading.Thread(target=generate, args=(t0,),
                                   name="bench-generator")
            gen.start()
            end = t0 + seconds
            with annotation("bench.idle"):
                time.sleep(max(0.0, end - time.perf_counter()))
        gen.join()
        self.compiles.on = False
        backlog = int(np.sum(done > end))
        deadline = end + ANSWER_WAIT_S
        for f in self.futs:
            try:
                f.result(timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:                      # noqa: BLE001
                pass                               # counted below
        failed = sum(1 for f in self.futs
                     if not f.done() or f.exception() is not None)
        due = t0 + offsets
        lat = np.where(np.isfinite(done), done - due, deadline - due)
        late = sent - due
        completed = int(np.sum(done <= end))
        self.window_s = seconds
        self.log(f"window: offered {n} requests at "
                 f"{self.traffic['rate_per_s']}/s; {completed} answered in "
                 f"the window, backlog {backlog} at its close, {failed} "
                 f"failed")
        self.log(f"generator lateness: p50 {stats.percentile(late, 50)*1e3:.3f}"
                 f" ms, p95 {stats.percentile(late, 95)*1e3:.3f} ms, max "
                 f"{late.max()*1e3:.3f} ms")
        self.log(f"latency from due: p50 {stats.percentile(lat, 50)*1e3:.3f} "
                 f"ms, p95 {stats.percentile(lat, 95)*1e3:.3f} ms, max "
                 f"{lat.max()*1e3:.3f} ms over {n} requests")
        return {"attempted": n, "failed": failed,
                "metrics": {
                    "serve_p95_ms": stats.percentile(lat, 95) * 1e3,
                    "serve_solves_per_s": stats.rate(completed, seconds)},
                "backlog": backlog}


DRIVER = OpenServe
