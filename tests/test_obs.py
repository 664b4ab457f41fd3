"""Observability-layer tests: metrics registry, streaming histograms,
span tracer, export schemas, and the zero-overhead disabled path.

Quantile policy under test (docs/observability.md): streaming histograms
estimate p50/p90/p99 within ``HIST_REL_ERROR`` (±5%) relative error of the
nearest-rank sample quantile, with exact count/sum/min/max.
"""
import json
import math
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import (HIST_REL_ERROR, MetricsRegistry,
                               merge_summaries, next_scope)
from repro.obs.tracing import (JSONL_KEYS, SpanTracer, load_jsonl,
                               validate_chrome, validate_jsonl)


# ---------------------------------------------------------------------------
# counters / gauges / label isolation
# ---------------------------------------------------------------------------

class TestCounters:
    def test_counter_counts_and_labels_are_isolated(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs", "requests")
        c.inc(bucket="a")
        c.inc(2.0, bucket="a")
        c.inc(bucket="b")
        assert c.value(bucket="a") == 3.0
        assert c.value(bucket="b") == 1.0
        assert c.value(bucket="never-bumped") == 0.0

    def test_counter_is_monotonic(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="only go up"):
            reg.counter("c").inc(-1.0)

    def test_get_or_define_returns_same_instrument(self):
        reg = MetricsRegistry()
        assert reg.counter("x", "first help") is reg.counter("x")

    def test_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already defined as counter"):
            reg.histogram("x")

    def test_scope_labels_never_alias_across_instances(self):
        # the pattern every instrumented object uses: one shared registry
        # definition, per-object exactness via a unique scope label
        reg = MetricsRegistry()
        c = reg.counter("dispatches")
        s1, s2 = next_scope("t"), next_scope("t")
        assert s1 != s2
        c.inc(scope=s1)
        c.inc(scope=s1)
        c.inc(scope=s2)
        assert c.value(scope=s1) == 2.0
        assert c.value(scope=s2) == 1.0

    def test_gauge_set_and_add(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        g.set(5, q="a")
        g.add(-2, q="a")
        assert g.value(q="a") == 3.0


# ---------------------------------------------------------------------------
# streaming histograms
# ---------------------------------------------------------------------------

class TestHistograms:
    def test_quantiles_within_documented_error_of_numpy(self):
        rng = np.random.default_rng(7)
        samples = rng.lognormal(mean=-6.0, sigma=1.2, size=5000)
        reg = MetricsRegistry()
        h = reg.histogram("lat", unit="s")
        for x in samples:
            h.observe(float(x))
        for q in (0.50, 0.90, 0.99):
            est = h.quantile(q)
            # nearest-rank sample quantile — the documented reference point
            exact = float(np.percentile(samples, q * 100,
                                        method="inverted_cdf"))
            assert abs(est - exact) / exact <= HIST_REL_ERROR + 1e-9, \
                f"p{q * 100:g}: {est} vs {exact}"

    def test_exact_count_sum_min_max(self):
        reg = MetricsRegistry()
        h = reg.histogram("h")
        xs = [0.003, 0.5, 12.0, 0.0001]
        for x in xs:
            h.observe(x)
        s = h.summary()
        assert s["count"] == 4
        assert s["sum"] == pytest.approx(sum(xs))
        assert s["min"] == min(xs) and s["max"] == max(xs)
        assert s["min"] <= s["p50"] <= s["max"]

    def test_empty_summary(self):
        reg = MetricsRegistry()
        s = reg.histogram("h").summary()
        assert s == {"count": 0, "sum": 0.0, "mean": None, "min": None,
                     "max": None, "p50": None, "p90": None, "p99": None}

    def test_zero_and_negative_go_to_underflow(self):
        reg = MetricsRegistry()
        h = reg.histogram("h")
        for x in (0.0, -1.0, 0.0):
            h.observe(x)
        s = h.summary()
        assert s["count"] == 3 and s["min"] == -1.0
        assert s["p50"] == 0.0    # underflow quantile reports "no time"

    def test_quantile_bounds_checked(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match=r"\[0, 1\]"):
            reg.histogram("h").quantile(1.5)

    def test_merge_summaries(self):
        reg = MetricsRegistry()
        h = reg.histogram("h")
        for x in (1.0, 2.0):
            h.observe(x, k="a")
        h.observe(10.0, k="b")
        merged = merge_summaries([h.summary(k="a"), h.summary(k="b")])
        assert merged["count"] == 3
        assert merged["sum"] == pytest.approx(13.0)
        assert merged["min"] == 1.0 and merged["max"] == 10.0


# ---------------------------------------------------------------------------
# registry: snapshot shape, scope filter, thread safety
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_snapshot_shape_and_scope_filter(self):
        reg = MetricsRegistry()
        reg.counter("c", "help text", unit="B").inc(3, scope="s1")
        reg.counter("c").inc(5, scope="s2")
        reg.histogram("h").observe(0.25, scope="s1")
        snap = reg.snapshot()
        assert snap["c"]["kind"] == "counter"
        assert snap["c"]["help"] == "help text"
        assert snap["c"]["unit"] == "B"
        assert {c["labels"]["scope"]: c["value"]
                for c in snap["c"]["cells"]} == {"s1": 3.0, "s2": 5.0}
        assert snap["h"]["cells"][0]["value"]["count"] == 1
        only = reg.snapshot("s1")
        assert [c["labels"] for c in only["c"]["cells"]] == [{"scope": "s1"}]
        # snapshots are plain JSON-serializable data
        json.dumps(snap)

    def test_racing_writers_lose_no_updates(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        h = reg.histogram("h")
        n_threads, per = 8, 2000
        snaps = []

        def writer(t):
            for i in range(per):
                c.inc(k="shared")
                h.observe(1e-3 * (i + 1), k="shared")

        def reader():
            for _ in range(50):
                snaps.append(reg.snapshot())

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)] + \
                  [threading.Thread(target=reader)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert c.value(k="shared") == n_threads * per
        s = h.summary(k="shared")
        assert s["count"] == n_threads * per
        assert s["sum"] == pytest.approx(n_threads * per * (per + 1) / 2
                                         * 1e-3)
        # every mid-race snapshot was internally sane
        for snap in snaps:
            for cell in snap.get("c", {}).get("cells", ()):
                assert 0 <= cell["value"] <= n_threads * per

    def test_racing_get_or_define_yields_one_instrument(self):
        reg = MetricsRegistry()
        seen = []

        def define():
            seen.append(reg.counter("same"))

        threads = [threading.Thread(target=define) for _ in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert all(inst is seen[0] for inst in seen)


# ---------------------------------------------------------------------------
# span tracer: no-op path, nesting, exports, validators
# ---------------------------------------------------------------------------

class TestSpans:
    def test_disabled_span_is_shared_noop(self):
        tr = SpanTracer()            # disabled is the default
        a = tr.span("x", k=1)
        b = tr.span("y")
        assert a is b                # one shared object: allocates nothing
        with a as sp:
            sp.annotate(more=2)      # annotate is a no-op, never raises
        assert tr.spans() == []

    def test_nesting_depth_and_args(self):
        tr = SpanTracer(enabled=True)
        with tr.span("outer", stage="a"):
            with tr.span("inner") as sp:
                sp.annotate(cache="hit")
            with tr.span("inner2"):
                pass
        spans = tr.spans()
        assert [(s["name"], s["depth"]) for s in spans] == \
            [("outer", 0), ("inner", 1), ("inner2", 1)]
        outer = spans[0]
        assert outer["args"] == {"stage": "a"}
        assert spans[1]["args"] == {"cache": "hit"}
        # children fall inside the parent interval
        for child in spans[1:]:
            assert child["ts_us"] >= outer["ts_us"]
            assert (child["ts_us"] + child["dur_us"]
                    <= outer["ts_us"] + outer["dur_us"] + 1e-6)

    def test_record_synthetic_spans(self):
        tr = SpanTracer(enabled=True)
        t0 = tr.now()
        tr.record("pass.order", t0, 0.25, points=3)
        (rec,) = tr.spans()
        assert rec["name"] == "pass.order"
        assert rec["dur_us"] == pytest.approx(0.25e6)
        assert rec["args"] == {"points": 3}

    def test_jsonl_roundtrip_and_schema(self, tmp_path):
        tr = SpanTracer(enabled=True)
        with tr.span("a", arch="hpc:cg"):
            with tr.span("b"):
                pass
        path = tmp_path / "spans.jsonl"
        assert tr.export_jsonl(path) == 2
        assert validate_jsonl(path) == 2
        loaded = load_jsonl(path)
        assert sorted(r["name"] for r in loaded) == ["a", "b"]
        for rec in loaded:
            assert tuple(sorted(rec)) == tuple(sorted(JSONL_KEYS))

    def test_chrome_export_and_schema(self, tmp_path):
        tr = SpanTracer(enabled=True)
        with tr.span("session.codesign", strategy="default"):
            with tr.span("codesign.search"):
                pass
        path = tmp_path / "trace.json"
        assert tr.export_chrome(path) == 2
        assert validate_chrome(path) == 2
        with open(path) as f:
            doc = json.load(f)
        assert doc["displayTimeUnit"] == "ms"
        by_name = {ev["name"]: ev for ev in doc["traceEvents"]}
        assert by_name["session.codesign"]["ph"] == "X"
        assert by_name["session.codesign"]["cat"] == "session"
        assert by_name["codesign.search"]["cat"] == "codesign"
        assert by_name["session.codesign"]["args"] == {"strategy": "default"}

    def test_validators_reject_schema_violations(self, tmp_path):
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"name": "x", "ts_us": 0}\n')
        with pytest.raises(ValueError, match="missing keys"):
            validate_jsonl(bad)
        extra = tmp_path / "extra.jsonl"
        extra.write_text(json.dumps(
            {k: ({} if k == "args" else "x" if k == "name" else 0)
             for k in JSONL_KEYS} | {"rogue": 1}) + "\n")
        with pytest.raises(ValueError, match="unexpected keys"):
            validate_jsonl(extra)
        badc = tmp_path / "bad.json"
        badc.write_text(json.dumps({"traceEvents": [
            {"name": "x", "ph": "B", "ts": 0, "dur": 0,
             "pid": 1, "tid": 1}]}))
        with pytest.raises(ValueError, match="ph must be 'X'"):
            validate_chrome(badc)

    def test_nonjson_args_are_reprd(self):
        tr = SpanTracer(enabled=True)
        with tr.span("x", shape=(4, 4)):
            pass
        (rec,) = tr.spans()
        assert rec["args"]["shape"] == repr((4, 4))

    def test_threads_record_independent_depths(self):
        tr = SpanTracer(enabled=True)

        def work(i):
            with tr.span(f"outer{i}"):
                with tr.span(f"inner{i}"):
                    pass

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        spans = tr.spans()
        assert len(spans) == 16
        depth = {s["name"]: s["depth"] for s in spans}
        for i in range(8):
            assert depth[f"outer{i}"] == 0 and depth[f"inner{i}"] == 1
        # parents never cross threads
        by_name = {s["name"]: s for s in spans}
        for i in range(8):
            assert by_name[f"outer{i}"]["parent"] is None
            assert by_name[f"inner{i}"]["parent"] == by_name[f"outer{i}"]["id"]

    def test_span_ids_and_parents(self, tmp_path):
        tr = SpanTracer(enabled=True)
        with tr.span("outer"):
            with tr.span("inner", reqs=[3, 4]):
                tr.record("synthetic", tr.now(), 0.0)
            with tr.span("inner2"):
                pass
        with tr.span("root2"):
            pass
        spans = {s["name"]: s for s in tr.spans()}
        ids = [s["id"] for s in spans.values()]
        assert len(set(ids)) == len(ids) and min(ids) >= 1
        assert spans["outer"]["parent"] is None
        assert spans["root2"]["parent"] is None
        assert spans["inner"]["parent"] == spans["outer"]["id"]
        assert spans["inner2"]["parent"] == spans["outer"]["id"]
        assert spans["synthetic"]["parent"] == spans["inner"]["id"]
        # a list of scalars (a batch's request ids) stays a list
        assert spans["inner"]["args"] == {"reqs": [3, 4]}
        jsonl, chrome = tmp_path / "s.jsonl", tmp_path / "s.json"
        tr.export_jsonl(jsonl)
        tr.export_chrome(chrome)
        assert validate_jsonl(jsonl) == validate_chrome(chrome) == 5
        events = json.loads(chrome.read_text())["traceEvents"]
        assert {(e["name"], e["id"], e["parent"]) for e in events} == \
            {(s["name"], s["id"], s["parent"]) for s in spans.values()}

    @pytest.mark.parametrize("bad", [
        {"id": 0}, {"id": "1"}, {"id": True}, {"parent": 0},
        {"parent": "x"}])
    def test_validators_reject_bad_ids(self, tmp_path, bad):
        rec = {"name": "x", "ts_us": 1.0, "dur_us": 0.0, "tid": 1, "pid": 1,
               "depth": 0, "id": 1, "parent": None, "args": {}} | bad
        path = tmp_path / "bad.jsonl"
        path.write_text(json.dumps(rec) + "\n")
        with pytest.raises(ValueError, match="id must be|parent must be"):
            validate_jsonl(path)
        ev = {"name": "x", "ph": "X", "ts": 1.0, "dur": 0.0, "pid": 1,
              "tid": 1, "id": 1, "parent": None} | bad
        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"traceEvents": [ev]}))
        with pytest.raises(ValueError, match="id must be|parent must be"):
            validate_chrome(path)

    def test_spans_carry_the_profilers_clock(self, tmp_path):
        """A span mirrored into a CPU ``jax.profiler`` trace starts, on the
        profile's host plane, where the tracer's own record says."""
        import glob
        import time

        import jax
        from jax.profiler import ProfileData
        tr = SpanTracer(enabled=True, jax_profiler=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("obs.clock_probe"):
                time.sleep(0.005)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        data = ProfileData.from_file(path)
        env = dict(data.find_plane_with_name("Task Environment").stats)
        (ev,) = [e for plane in data.planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name == "obs.clock_probe"]
        (rec,) = tr.spans()
        start_us = (env["profile_start_time"] + ev.start_ns) / 1e3
        assert abs(start_us - rec["ts_us"]) < 1e3
        assert rec["dur_us"] == pytest.approx(ev.duration_ns / 1e3, abs=1e3)


# ---------------------------------------------------------------------------
# the repro.obs facade: env spec parsing, sinks, global instrumentation
# ---------------------------------------------------------------------------

class TestFacade:
    def test_configure_from_env_off_values(self):
        assert obs.configure_from_env("") is False
        assert obs.configure_from_env("0") is False
        assert obs.configure_from_env("off") is False

    def test_configure_from_env_malformed_part_warns(self, tmp_path):
        was_enabled = obs.tracer().enabled
        try:
            with pytest.warns(UserWarning, match="unrecognized part"):
                assert obs.configure_from_env("bogus-spec") is True
        finally:
            if not was_enabled:
                obs.disable()

    def test_enable_flush_jsonl_sink(self, tmp_path):
        path = tmp_path / "out.jsonl"
        was_enabled = obs.tracer().enabled
        obs.enable(jsonl=str(path))
        try:
            with obs.span("facade.test"):
                pass
            counts = obs.flush()
            assert counts[str(path)] >= 1
            assert validate_jsonl(path) >= 1
            assert any(r["name"] == "facade.test"
                       for r in load_jsonl(path))
        finally:
            obs._SINKS[:] = [s for s in obs._SINKS if s[1] != str(path)]
            if not was_enabled:
                obs.disable()

    def test_global_session_stage_instruments_exist(self):
        # the instrumented layers define their metrics at import: one
        # registry, each name defined exactly once, kinds stable
        import repro.api.session  # noqa: F401  (defines the instruments)
        import repro.exec.base    # noqa: F401
        import repro.serve.server  # noqa: F401
        reg = obs.registry()
        names = reg.names()
        for needed in ("session.stage_s", "session.stage_runs",
                       "codesign.search_s", "codesign.points",
                       "codesign.cache.hits", "codesign.cache.misses",
                       "exec.compile_s", "exec.dispatch_s",
                       "serve.requests", "serve.e2e_latency_s"):
            assert needed in names
        with pytest.raises(TypeError):
            reg.histogram("session.stage_runs")   # defined as a counter
