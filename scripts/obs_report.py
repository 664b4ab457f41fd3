#!/usr/bin/env python
"""Render a ``repro.obs`` artifact as a table.

Accepts any of the three export formats and auto-detects which one it got:

* a **JSONL span trace** (``CELLO_OBS=jsonl:PATH`` /
  ``tracer().export_jsonl``) — one JSON object per line;
* a **Chrome trace_event JSON** (``CELLO_OBS=chrome:PATH`` /
  ``tracer().export_chrome``) — ``{"traceEvents": [...]}``, the file you
  would load in Perfetto;
* a **metrics snapshot JSON** (``repro.obs.snapshot()`` serialized, or a
  ``benchmarks.run --json`` dump carrying it under its ``obs`` key).

Span renders show the nested timeline (indent = depth, times in ms from
the first span; spans carry the profiler's clock, so ``t0_us`` is the
offset to line them up with a device profile of the same run) plus a
per-name aggregate; metrics renders show one row per labeled cell, histograms with
count/mean/p50/p90/p99/max.

``--validate`` checks the file against the documented export schema
(``docs/observability.md``) instead of rendering — exit 0 on a valid file,
1 on the first violation.  CI's ``obs-smoke`` job gates on this.

    python scripts/obs_report.py /tmp/cello.trace.json
    python scripts/obs_report.py /tmp/cello.jsonl --validate
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Any, Dict, List

try:
    from repro.obs import tracing
except ImportError:                     # run from a checkout without install
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "src"))
    from repro.obs import tracing

_KINDS = ("counter", "gauge", "histogram")


# --------------------------------------------------------------------------
# format detection
# --------------------------------------------------------------------------

def detect(path: str) -> str:
    """"jsonl" | "chrome" | "metrics" for ``path`` (raises ValueError)."""
    with open(path) as f:
        head = f.read(1 << 20)
    try:
        doc = json.loads(head) if head.strip() else None
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict):
        if "traceEvents" in doc:
            return "chrome"
        if _looks_like_snapshot(doc) or _looks_like_snapshot(doc.get("obs")):
            return "metrics"
    # JSONL: every non-blank line its own object
    if all(line.lstrip().startswith("{")
           for line in head.splitlines() if line.strip()) and head.strip():
        return "jsonl"
    raise ValueError(f"{path}: not a span trace (jsonl/chrome) or metrics "
                     "snapshot")


def _looks_like_snapshot(doc: Any) -> bool:
    return (isinstance(doc, dict) and bool(doc)
            and all(isinstance(v, dict) and v.get("kind") in _KINDS
                    and isinstance(v.get("cells"), list)
                    for v in doc.values()))


def load_metrics(path: str) -> Dict[str, Any]:
    with open(path) as f:
        doc = json.load(f)
    if _looks_like_snapshot(doc):
        return doc
    if _looks_like_snapshot(doc.get("obs")):
        return doc["obs"]
    raise ValueError(f"{path}: no metrics snapshot found")


# --------------------------------------------------------------------------
# rendering
# --------------------------------------------------------------------------

def _fmt_args(args: Dict[str, Any]) -> str:
    return " ".join(f"{k}={v}" for k, v in sorted(args.items()))


def _span_lines(spans: List[Dict[str, Any]]) -> List[str]:
    t0 = spans[0]["ts_us"] if spans else 0.0
    lines = [f"t0_us={t0:.3f}",
             f"{'ts_ms':>10}  {'dur_ms':>10}  {'id':>6}  {'parent':>6}  span"]
    totals: Dict[str, List[float]] = {}
    for rec in spans:
        name, dur_ms = rec["name"], rec["dur_us"] / 1e3
        indent = "  " * rec.get("depth", 0)
        args = _fmt_args(rec.get("args") or {})
        parent = rec.get("parent")
        lines.append(f"{(rec['ts_us'] - t0) / 1e3:10.3f}  {dur_ms:10.3f}  "
                     f"{rec.get('id', '-'):>6}  "
                     f"{'-' if parent is None else parent:>6}  "
                     f"{indent}{name}" + (f"  [{args}]" if args else ""))
        totals.setdefault(name, []).append(dur_ms)
    lines.append("")
    lines.append(f"{'count':>6}  {'total_ms':>10}  {'mean_ms':>10}  name")
    for name in sorted(totals):
        ds = totals[name]
        lines.append(f"{len(ds):6d}  {sum(ds):10.3f}  "
                     f"{sum(ds) / len(ds):10.3f}  {name}")
    return lines


def render_jsonl(path: str) -> List[str]:
    spans = sorted(tracing.load_jsonl(path), key=lambda r: r["ts_us"])
    return _span_lines(spans)


def render_chrome(path: str) -> List[str]:
    with open(path) as f:
        doc = json.load(f)
    spans = [{"name": ev.get("name", "?"), "ts_us": ev.get("ts", 0),
              "dur_us": ev.get("dur", 0), "depth": 0,
              "id": ev.get("id"), "parent": ev.get("parent"),
              "args": ev.get("args") or {}}
             for ev in doc.get("traceEvents", [])]
    spans.sort(key=lambda r: r["ts_us"])
    # nesting from each span's parent (parents start first)
    depth: Dict[Any, int] = {}
    for rec in spans:
        rec["depth"] = depth[rec["id"]] = (
            depth.get(rec["parent"], -1) + 1
            if rec["parent"] is not None else 0)
    return _span_lines(spans)


def render_metrics(path: str) -> List[str]:
    snap = load_metrics(path)
    lines: List[str] = []
    for name in sorted(snap):
        inst = snap[name]
        unit = f" [{inst['unit']}]" if inst.get("unit") else ""
        lines.append(f"{name}{unit}  ({inst['kind']})"
                     + (f" — {inst['help']}" if inst.get("help") else ""))
        for cell in inst.get("cells", []):
            labels = _fmt_args(cell.get("labels") or {}) or "-"
            v = cell.get("value")
            if isinstance(v, dict):                    # histogram summary
                if not v.get("count"):
                    lines.append(f"    {labels:48s}  count=0")
                    continue
                qs = "  ".join(
                    f"{q}={v[q]:.6g}" for q in
                    ("mean", "p50", "p90", "p99", "max")
                    if v.get(q) is not None)
                lines.append(f"    {labels:48s}  count={v['count']}  {qs}")
            else:
                num = f"{v:g}" if isinstance(v, float) else str(v)
                lines.append(f"    {labels:48s}  {num}")
    return lines or ["(empty snapshot)"]


# --------------------------------------------------------------------------
# validation (the documented schema contract)
# --------------------------------------------------------------------------

def validate_metrics(path: str) -> int:
    snap = load_metrics(path)
    n = 0
    for name, inst in snap.items():
        where = f"{path}: {name}"
        if inst.get("kind") not in _KINDS:
            raise ValueError(f"{where}: kind must be one of {_KINDS}")
        for cell in inst.get("cells", ()):
            if not isinstance(cell.get("labels"), dict):
                raise ValueError(f"{where}: cell labels must be an object")
            v = cell.get("value")
            if inst["kind"] == "histogram":
                if not isinstance(v, dict) or "count" not in v:
                    raise ValueError(f"{where}: histogram cell value must "
                                     "be a summary object with a count")
            elif not isinstance(v, (int, float)):
                raise ValueError(f"{where}: {inst['kind']} cell value must "
                                 "be a number")
            n += 1
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python scripts/obs_report.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("file", help="jsonl span trace, Chrome trace JSON, or "
                                 "metrics snapshot JSON")
    ap.add_argument("--format", choices=("auto", "jsonl", "chrome",
                                         "metrics"), default="auto",
                    help="override format auto-detection")
    ap.add_argument("--validate", action="store_true",
                    help="check the file against the documented schema "
                         "instead of rendering")
    args = ap.parse_args(argv)
    try:
        fmt = detect(args.file) if args.format == "auto" else args.format
        if args.validate:
            n = {"jsonl": tracing.validate_jsonl,
                 "chrome": tracing.validate_chrome,
                 "metrics": validate_metrics}[fmt](args.file)
            what = "spans" if fmt == "jsonl" else (
                "events" if fmt == "chrome" else "cells")
            print(f"{args.file}: valid {fmt} ({n} {what})")
            return 0
        lines = {"jsonl": render_jsonl, "chrome": render_chrome,
                 "metrics": render_metrics}[fmt](args.file)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"obs_report: {e}", file=sys.stderr)
        return 1
    print(f"# {args.file} ({fmt})")
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
