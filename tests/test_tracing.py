"""Tracing-layer tests: span nesting/export round-trip, histogram
mirroring, the LHTPU_TRACE=0 no-op contract, spans as profiler
annotations, and Prometheus exposition of the new dispatch-stage metric
families through api/http_metrics."""

import glob
import json
import os
import threading
import types
import urllib.request

from lighthouse_tpu.api.http_metrics import MetricsServer
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY, Registry


class TestSpans:
    def test_nesting_and_export_round_trip(self):
        tracer = tracing.Tracer()
        with tracer.span("root", kind="test") as root:
            with tracer.span("child_a"):
                with tracer.span("grandchild"):
                    pass
            with tracer.span("child_b") as b:
                b.set(lanes=4)
        assert tracer.current() is None
        roots = tracer.roots()
        assert [r.name for r in roots] == ["root"]
        assert [c.name for c in roots[0].children] == ["child_a", "child_b"]
        assert roots[0].children[0].children[0].name == "grandchild"
        assert roots[0].duration >= sum(
            c.duration for c in roots[0].children
        )
        # the Chrome-trace export carries the structure and attrs
        events = {e["name"]: e for e in tracer.chrome_trace()}
        assert list(events) == ["root", "child_a", "grandchild", "child_b"]
        assert events["root"]["args"] == {"kind": "test"}
        assert events["child_b"]["args"] == {"lanes": 4}

        def inside(inner, outer):
            end = lambda e: e["ts"] + e["dur"]  # noqa: E731
            return (outer["ts"] <= inner["ts"]
                    and end(inner) <= end(outer) + 1e-6)

        assert inside(events["child_a"], events["root"])
        assert inside(events["grandchild"], events["child_a"])
        assert inside(events["child_b"], events["root"])
        assert not inside(events["child_b"], events["child_a"])

    def test_chrome_trace_events(self):
        tracer = tracing.Tracer()
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
        events = tracer.chrome_trace()
        assert [e["name"] for e in events] == ["outer", "inner"]
        for e in events:
            assert e["ph"] == "X"
            assert e["dur"] >= 0.0
            assert {"ts", "pid", "tid", "args"} <= set(e)
        # a Chrome trace file is just JSON of these events
        json.dumps({"traceEvents": events})

    def test_exception_recorded_and_reraised(self):
        tracer = tracing.Tracer()
        try:
            with tracer.span("doomed"):
                raise RuntimeError("kaput")
        except RuntimeError:
            pass
        (root,) = tracer.roots()
        assert root.attrs["error"] == "RuntimeError"
        assert root.duration is not None

    def test_ring_buffer_bounded(self):
        tracer = tracing.Tracer(max_spans=4)
        for i in range(10):
            with tracer.span(f"s{i}"):
                pass
        assert [r.name for r in tracer.roots()] == [
            "s6", "s7", "s8", "s9"
        ]

    def test_ring_bounded_by_spans_not_roots(self):
        tracer = tracing.Tracer(max_spans=5)

        def root(name, children):
            with tracer.span(name):
                for i in range(children):
                    with tracer.span(f"{name}/{i}"):
                        pass

        root("a", 2)
        root("b", 1)
        assert [r.size for r in tracer.roots()] == [3, 2]
        root("c", 0)  # 6 spans: the oldest root goes, whole
        assert [r.name for r in tracer.roots()] == ["b", "c"]
        root("d", 7)  # larger than the ring: kept alone, as the newest
        assert [r.name for r in tracer.roots()] == ["d"]
        assert len(tracer.chrome_trace()) == 8
        tracer.clear()
        root("e", 3)
        assert [r.name for r in tracer.roots()] == ["e"]

    def test_thread_isolation(self):
        tracer = tracing.Tracer()
        seen = {}

        def worker():
            with tracer.span("worker_root"):
                seen["inner"] = tracer.current().name

        with tracer.span("main_root"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
            # the worker's span must NOT have nested under main_root
            assert tracer.current().name == "main_root"
        assert seen["inner"] == "worker_root"
        names = {r.name for r in tracer.roots()}
        assert names == {"main_root", "worker_root"}

    def test_histogram_mirroring(self):
        reg = Registry()
        h = reg.histogram("stage_seconds", "S", ("stage",))
        tracer = tracing.Tracer()
        with tracer.span("op/pack", metric=h, labels={"stage": "pack"}):
            pass
        text = reg.gather()
        assert 'stage_seconds_count{stage="pack"} 1' in text
        # the shared by-name family in the GLOBAL registry also advanced
        before = tracing.SPAN_SECONDS
        assert 'lhtpu_span_seconds' in REGISTRY.gather()
        assert before is REGISTRY.histogram(
            "lhtpu_span_seconds", "", ("span",)
        )

    def test_unmirrored_span_is_kept_but_not_observed(self):
        def count(name):
            shard = tracing.SPAN_SECONDS._shards.get((name,))
            return shard.count if shard else 0

        reg = Registry()
        h = reg.histogram("step_seconds", "S", ("step",))
        tracer = tracing.Tracer()
        before = count("op/step"), count("op/outer")
        with tracer.span("op/outer"):
            with tracer.span("op/step", metric=h, labels={"step": "a"},
                             mirror=False, rows=3) as sp:
                pass
        assert (count("op/step"), count("op/outer")) == (
            before[0], before[1] + 1)
        assert 'step_seconds_count{step="a"} 1' in reg.gather()
        (root,) = tracer.roots()
        assert root.children == [sp] and sp.attrs == {"rows": 3}

    def test_disabled_is_noop(self, monkeypatch):
        def no_clock():
            raise AssertionError("a disabled span read the clock")

        tracer = tracing.Tracer()
        monkeypatch.setattr(tracing, "time",
                            types.SimpleNamespace(perf_counter=no_clock))
        monkeypatch.setattr(tracing, "_annotation", None)
        prev = tracing.set_enabled(False)
        try:
            sp = tracer.span("invisible", metric=None, attr=1)
            assert sp is tracing.NULL_SPAN
            assert tracing.span("invisible") is tracing.NULL_SPAN
            with sp:
                sp.set(anything="goes")
            assert tracer.roots() == []
            assert tracer.chrome_trace() == []
            # no profiler annotation was looked up: JAX was not imported
            assert tracing._annotation is None
        finally:
            tracing.set_enabled(prev)

    def test_spans_are_profiler_annotations(self, tmp_path):
        """An enabled span is written into a JAX profiler trace under
        its name (CPU: the host plane), also when its body raises; a
        disabled one is not."""
        import jax
        import jax.numpy as jnp

        prev = tracing.set_enabled(True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracing.span("lhtpu_test/annotated"):
                jnp.ones(8).sum().block_until_ready()
            try:
                with tracing.span("lhtpu_test/raised"):
                    raise ValueError("inside")
            except ValueError:
                pass
            tracing.set_enabled(False)
            with tracing.span("lhtpu_test/disabled"):
                pass
        finally:
            jax.profiler.stop_trace()
            tracing.set_enabled(prev)
        (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True)
        names = {e.name
                 for plane in jax.profiler.ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events}
        assert {"lhtpu_test/annotated", "lhtpu_test/raised"} <= names
        assert "lhtpu_test/disabled" not in names

    def test_module_level_convenience(self):
        tracing.clear()
        with tracing.span("module_root"):
            pass
        assert any(r.name == "module_root" for r in tracing.roots())
        tracing.clear()
        assert tracing.roots() == []


class TestExposition:
    def test_dispatch_families_scrapable(self):
        # Importing the backend registers the dispatch metric families
        # on the global registry; the scrape must carry them in valid
        # text exposition even before any batch ran.
        import lighthouse_tpu.jax_backend  # noqa: F401

        srv = MetricsServer().start()
        try:
            with urllib.request.urlopen(srv.url + "/metrics") as resp:
                text = resp.read().decode()
            for family, typ in (
                ("bls_dispatch_stage_seconds", "histogram"),
                ("bls_dispatch_batch_sets", "histogram"),
                ("bls_dispatch_batch_keys", "histogram"),
                ("bls_dispatch_errors_total", "counter"),
                ("bls_dispatch_batches_total", "counter"),
                ("bls_jit_cache_events_total", "counter"),
                ("bls_signature_sets_built_total", "counter"),
                ("lhtpu_span_seconds", "histogram"),
                # resilience layer (ISSUE 2): retry / breaker / ladder
                ("bls_dispatch_retries_total", "counter"),
                ("bls_breaker_state", "gauge"),
                ("bls_degraded_dispatches_total", "counter"),
                ("bls_faults_injected_total", "counter"),
                ("bls_deadline_exceeded_total", "counter"),
                ("native_backend_load_failures_total", "counter"),
            ):
                assert f"# TYPE {family} {typ}" in text, family
            with urllib.request.urlopen(srv.url + "/trace") as resp:
                trace = json.loads(resp.read().decode())
            assert "traceEvents" in trace
        finally:
            srv.stop()


class TestSlotClockMetrics:
    def test_gauges_and_lateness(self):
        from lighthouse_tpu.common.slot_clock import (
            SLOT_GAUGE,
            SLOT_LATENESS_SECONDS,
            ManualSlotClock,
        )

        clock = ManualSlotClock(genesis_time=0, seconds_per_slot=12)
        clock.set_slot(5)
        clock.advance_time(3.0)
        assert clock.now() == 5
        assert SLOT_GAUGE.value() == 5
        late = clock.record_lateness("block_import", 5)
        assert abs(late - 3.0) < 1e-6
        assert (
            'slot_clock_lateness_seconds_count{event="block_import"}'
            in REGISTRY.gather()
        )
