"""Trace one instrumented CG pipeline run end to end with ``repro.obs``.

Runs the staged pipeline explicitly — ``trace → analyze → codesign →
lower → run`` — with span tracing enabled, so the exported trace carries
all four ``session.*`` stage spans, the nested ``codesign.search`` span
with its per-pass children, and the ``exec.compile`` / ``exec.dispatch``
spans. Writes a Chrome ``trace_event`` file you can load directly in
https://ui.perfetto.dev (or render with ``scripts/obs_report.py``), then
prints the span timeline and the metrics-registry table.

    python examples/observe_cg.py --n 256 --iters 8 --backend pallas \
        --trace /tmp/cello.trace.json

Equivalently, any entry point can be traced without code changes via the
environment: ``CELLO_OBS=chrome:/tmp/cello.trace.json python ...``
(see docs/observability.md).
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import tempfile

from repro import obs
from repro.runtime import enable_compile_cache
from repro.api import Session


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=256, help="operator size")
    ap.add_argument("--iters", type=int, default=8, help="CG iterations")
    ap.add_argument("--backend", default="reference",
                    help="execution backend (reference | pallas)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="Chrome trace output (default: a temp file)")
    ap.add_argument("--jsonl", default=None, metavar="PATH",
                    help="also write the JSONL span export to PATH")
    args = ap.parse_args()
    enable_compile_cache()
    trace_path = args.trace or str(pathlib.Path(tempfile.gettempdir())
                                   / "cello.trace.json")

    obs.enable(chrome=trace_path, jsonl=args.jsonl)

    # the four stages explicitly (Session.compile() would skip analyze),
    # so the exported trace shows the full pipeline shape
    sess = Session()
    traced = sess.trace(workload="cg", n=args.n, iters=args.iters)
    analyzed = traced.analyze()
    designed = analyzed.codesign()
    plan = designed.lower(backend=args.backend)
    with obs.span("example.run", backend=args.backend):
        out = plan.run()

    counts = obs.flush()
    print(f"residual leaves: {sorted(out)}")
    print(f"wrote {counts[trace_path]} spans -> {trace_path} "
          "(load in https://ui.perfetto.dev)\n")

    # render the artifacts with the bundled CLI (same output as
    # `python scripts/obs_report.py FILE`)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "scripts"))
    import obs_report
    print("# span timeline")
    print("\n".join(obs_report.render_chrome(trace_path)))

    snap_path = pathlib.Path(tempfile.gettempdir()) / "cello.metrics.json"
    import json
    snap_path.write_text(json.dumps(obs.snapshot()))
    print("\n# metrics registry")
    print("\n".join(obs_report.render_metrics(str(snap_path))))

    names = {rec["name"] for rec in obs.tracer().spans()}
    for stage in ("trace", "analyze", "codesign", "lower"):
        assert f"session.{stage}" in names, f"missing session.{stage}"
    print("\nall four pipeline stage spans recorded: verified")


if __name__ == "__main__":
    main()
