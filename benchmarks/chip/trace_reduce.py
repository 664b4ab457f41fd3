"""Reduce a profiler trace of one benchmark window to per-layer numbers.

Input: the ``.xplane.pb`` file ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (:func:`load_xplane`), or the same events as
plain data (:func:`reduce_events` takes what :func:`load_xplane` returns,
so a test can hand it a recorded trace).

Output, over the window that the harness's host span ``bench.window``
marks:

* ``busy_s``: per device, the union of the intervals in which an
  operation ran (the device's ``XLA Ops`` line), averaged over devices;
  ``window_s``: the window's length.  Idle share is ``1 - busy/window``.
* ``pallas_s`` / ``other_s``: device time in the program's Pallas
  kernels, which it names ``cello_*`` (``repro.exec.pallas``; batched
  under ``jax.vmap`` they read ``vmap_cello_*``), and in every other
  device operation (XLA's gathers, layout builds, copies).
  Nested events on one line count by their self time, so no interval is
  counted twice.
* ``device_ops``: the operations that took most device time.
* ``idle_gaps``: device idle time inside the window, summed by what the
  host was doing meanwhile: the host span (the harness's ``bench.*``
  spans, and the program's own ``serve.*`` / ``exec.*`` spans where its
  tracer mirrors them) that overlaps each gap most.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Mapping, Sequence, Tuple

WINDOW_SPAN = "bench.window"
KERNEL_PREFIX = "cello_"
_KERNEL = re.compile(r"(vmap_)*" + KERNEL_PREFIX)
HOST_PREFIXES = ("bench.", "serve.", "exec.")
OPS_LINE = "XLA Ops"
NO_SPAN = "(no host span)"

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler`` session wrote."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def op_name(text: str) -> str:
    """The HLO instruction name of a device event: a TPU trace names each
    op by its whole instruction, ``%cello_stream_Ap2.3 = (f32[...]) ...``,
    and this keeps ``cello_stream_Ap2.3``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def is_kernel(name: str) -> bool:
    """Whether a device op is one of the program's Pallas kernels."""
    return _KERNEL.match(name) is not None


def load_xplane(path: str) -> Dict[str, Any]:
    """``{"devices": {plane: [event]}, "host": [event]}`` from a trace.

    Device events are those of each accelerator plane's ``XLA Ops``
    line; host events are the named spans of the host plane whose names
    start with one of :data:`HOST_PREFIXES`.
    """
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return {"devices": devices, "host": host}


def _clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def union_intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals covered by ``events``."""
    merged: List[List[float]] = []
    for _, start, dur in sorted(events, key=lambda ev: ev[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Device time by operation name, each instant counted once: where
    events nest, the instant belongs to the innermost (latest started)."""
    bounds = sorted({t for _, s, d in events for t in (s, s + d)})
    totals: Dict[str, float] = {}
    if not bounds:
        return totals
    order = sorted(range(len(events)), key=lambda i: events[i][1])
    active: List[int] = []
    nxt = 0
    for a, b in zip(bounds, bounds[1:]):
        while nxt < len(order) and events[order[nxt]][1] <= a:
            active.append(order[nxt])
            nxt += 1
        active = [i for i in active if events[i][1] + events[i][2] > a]
        if active:
            inner = max(active, key=lambda i: (events[i][1], -events[i][2]))
            name = events[inner][0]
            totals[name] = totals.get(name, 0.0) + (b - a)
    return totals


def _attribute(gaps: Sequence[Tuple[float, float]],
               host: Sequence[Event]) -> List[str]:
    """For each gap (in time order), the host span that overlaps it most,
    the innermost on ties; one sweep over both lists."""
    spans = sorted((start, start + dur, name) for name, start, dur in host
                   if name != WINDOW_SPAN)
    names, active, nxt = [], [], 0
    for lo, hi in gaps:
        while nxt < len(spans) and spans[nxt][0] < hi:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] > lo]
        best, best_key = NO_SPAN, (0.0, 0.0)
        for start, end, name in active:
            overlap = min(hi, end) - max(lo, start)
            if overlap > 0 and (overlap, start - end) > best_key:
                best, best_key = name, (overlap, start - end)
        names.append(best)
    return names


def window_of(host: Sequence[Event]) -> Tuple[float, float]:
    """``(start_ns, end_ns)`` of the harness's window span."""
    spans = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} host span, "
                         f"found {len(spans)}")
    return spans[0]


def reduce_events(events: Mapping[str, Any], *, top: int = 10
                  ) -> Dict[str, Any]:
    """Per-layer numbers of the window (see the module docstring)."""
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    host = events["host"]
    lo, hi = window_of(host)
    window_ns = hi - lo
    busy_ns, pallas_ns, other_ns = [], 0.0, 0.0
    op_totals: Dict[str, float] = {}
    gap_totals: Dict[str, float] = {}
    for plane in sorted(devices):
        evs = _clip(devices[plane], lo, hi)
        busy = union_intervals(evs)
        busy_ns.append(sum(e - s for s, e in busy))
        for name, t in self_times(evs).items():
            op_totals[name] = op_totals.get(name, 0.0) + t
            if is_kernel(name):
                pallas_ns += t
            else:
                other_ns += t
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for (s, e), who in zip(gaps, _attribute(gaps, host)):
            gap_totals[who] = gap_totals.get(who, 0.0) + (e - s)
    n_dev = len(devices)

    def ranked(totals: Dict[str, float]) -> List[List[Any]]:
        items = sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))
        return [[k, v / n_dev * 1e-9] for k, v in items[:top]]

    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy_ns) / n_dev * 1e-9,
        "devices": n_dev,
        "pallas_s": pallas_ns / n_dev * 1e-9,
        "other_s": other_ns / n_dev * 1e-9,
        "device_ops": ranked(op_totals),
        "idle_gaps": ranked(gap_totals),
    }
