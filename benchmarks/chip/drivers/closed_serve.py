"""Traffic kind ``closed_serve``: a closed loop of clients of the service.

``clients`` threads each submit one request (right-hand side ``i mod
rhs_pool`` of a pool drawn from the seed) and wait for its answer, again
and again, for the whole window, so batches of up to ``max_batch_size``
stay full.  Reports ``serve_solves_per_s``: the requests answered in the
window over the window.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, Tuple

import numpy as np

import inputs
import stats
from driver_base import (ANSWER_WAIT_S, WINDOW_SPAN, ServeDriver,
                         annotation)


class ClosedServe(ServeDriver):

    def _make_rhs(self) -> np.ndarray:
        return inputs.host_rhs(self.config, self.seed,
                                  int(self.traffic["rhs_pool"]))

    def window(self, seconds: float) -> Dict[str, Any]:
        clients = int(self.traffic["clients"])
        lock = threading.Lock()
        records: List[Tuple[int, Any, float]] = []
        stop = threading.Event()
        counter = itertools.count()
        reqs = [self._request(i) for i in range(len(self.rhs))]

        def client():
            while not stop.is_set():
                with lock:
                    i = next(counter)
                fut = self.server.submit(reqs[i % len(reqs)])
                with annotation("bench.wait"):
                    try:
                        fut.result(timeout=ANSWER_WAIT_S)
                    except Exception:              # noqa: BLE001
                        pass                       # counted below
                with lock:
                    records.append((i, fut, time.perf_counter()))

        self.window_start()
        with annotation(WINDOW_SPAN):
            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, name=f"bench-client{c}")
                       for c in range(clients)]
            for t in threads:
                t.start()
            end = t0 + seconds
            with annotation("bench.idle"):
                time.sleep(max(0.0, end - time.perf_counter()))
            stop.set()
        for t in threads:
            t.join(ANSWER_WAIT_S + 5)
        self.compiles.on = False
        if any(t.is_alive() for t in threads):
            raise RuntimeError("a client thread did not finish")
        records.sort(key=lambda r: r[0])
        self.futs = [f for _, f, _ in records]
        self.rhs_index = [i for i, _, _ in records]
        failed = sum(1 for f in self.futs if f.exception() is not None)
        completed = sum(1 for _, f, t in records
                        if t <= end and f.exception() is None)
        self.window_s = seconds
        self.log(f"window: {clients} clients, {len(records)} requests, "
                 f"{completed} answered in the window, {failed} failed")
        return {"attempted": len(records), "failed": failed,
                "metrics": {
                    "serve_solves_per_s": stats.rate(completed, seconds)}}


DRIVER = ClosedServe
