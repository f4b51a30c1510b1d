"""Telemetry subsystem tests (deeplearning4j_tpu/telemetry/,
docs/OBSERVABILITY.md): registry semantics + thread safety, Prometheus
exposition format (escaping, histogram buckets, counter monotonicity),
span nesting + Chrome-trace round trip, device/jit-cache gauges, the
hot-path instrumentation counters, the CLI --trace/--metrics-port
plumbing, and the instrumented-vs-bare overhead gate (generous bound;
the honest number is bench.py `telemetry`)."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.config import NeuralNetConfiguration
from deeplearning4j_tpu.datasets import DeviceFeed, ListDataSetIterator
from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.telemetry import device, exposition
from deeplearning4j_tpu.telemetry.registry import MetricsRegistry


def _net(n_in=4, n_out=3, iters=1):
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(n_in).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(iters).use_adagrad(False)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=n_out)
            .pretrain(False).build())
    return MultiLayerNetwork(conf)


def _data(n=32, n_in=4, n_out=3, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, n_in).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.randint(0, n_out, n)]
    return x, y


# ================================================================== registry
class TestRegistry:
    def test_counter_inc_and_monotonicity(self):
        reg = MetricsRegistry()
        c = reg.counter("c", "help")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError, match="monotonic"):
            c.inc(-1)

    def test_labeled_children_are_independent(self):
        reg = MetricsRegistry()
        fam = reg.counter("hits")
        fam.labels(bucket="8").inc(3)
        fam.labels(bucket="16").inc()
        assert fam.labels(bucket="8").value == 3
        assert fam.labels(bucket="16").value == 1
        # same label set -> same child
        assert fam.labels(bucket="8") is fam.labels(bucket="8")

    def test_label_name_consistency_enforced(self):
        reg = MetricsRegistry()
        fam = reg.counter("c")
        fam.labels(bucket="8")
        with pytest.raises(ValueError, match="label names"):
            fam.labels(engine="e0")

    def test_kind_mismatch_rejected(self):
        reg = MetricsRegistry()
        reg.counter("m")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("m")

    def test_get_or_create_shares_family(self):
        reg = MetricsRegistry()
        assert reg.counter("shared") is reg.counter("shared", "other help")

    def test_gauge_set_inc_and_function(self):
        reg = MetricsRegistry()
        g = reg.gauge("g")
        g.set(4.0)
        g.inc()
        g.dec(2)
        assert g.value == 3.0
        g.set_function(lambda: 42.0)
        assert g.value == 42.0
        g.set(1.0)  # static set clears the callable
        assert g.value == 1.0

    def test_gauge_function_failure_reads_last_static(self):
        reg = MetricsRegistry()
        g = reg.gauge("g")
        g.set(7.0)
        child = g._default()
        child.set_function(lambda: 1 / 0)
        assert child.value == 7.0

    def test_histogram_buckets_sum_count_percentile(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 5.0, 10.0)).labels(k="v")
        for v in (0.5, 2.0, 2.0, 7.0, 100.0):
            h.observe(v)
        assert h.count == 5
        assert h.sum == pytest.approx(111.5)
        buckets = dict(h.cumulative_buckets())
        assert buckets[1.0] == 1
        assert buckets[5.0] == 3
        assert buckets[10.0] == 4
        assert buckets[float("inf")] == 5  # +Inf == total count
        assert h.percentile(0.0) == 0.5
        assert h.percentile(1.0) == 100.0
        assert h.percentile(0.5) == 2.0

    def test_disabled_registry_records_nothing(self):
        reg = MetricsRegistry()
        c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
        telemetry.set_enabled(False)
        try:
            c.inc()
            g.set(5)
            h.observe(1.0)
        finally:
            telemetry.set_enabled(True)
        assert c.value == 0 and g.value == 0 and h.count == 0

    def test_snapshot_is_json_safe(self):
        reg = MetricsRegistry()
        reg.counter("c").labels(a="x").inc(2)
        reg.histogram("h").observe(0.1)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["c"]["series"][0] == {"labels": {"a": "x"}, "value": 2}
        assert snap["h"]["series"][0]["count"] == 1


class TestThreadSafety:
    def test_concurrent_counter_increments_are_exact(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        n_threads, per_thread = 8, 5000

        def work():
            for _ in range(per_thread):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * per_thread

    def test_concurrent_labeled_producers(self):
        """Concurrent first-touch of children + histogram observes from
        many threads must neither drop counts nor corrupt buckets."""
        reg = MetricsRegistry()
        fam = reg.counter("hits")
        hist = reg.histogram("lat", buckets=(0.5,))
        per_thread = 2000

        def work(i):
            child = fam.labels(worker=str(i % 4))
            for _ in range(per_thread):
                child.inc()
                hist.observe(0.1)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = sum(child.value for _, child in fam.children())
        assert total == 8 * per_thread
        assert hist._default().count == 8 * per_thread


# ================================================================ exposition
class TestExposition:
    def test_counter_total_suffix_and_monotonic_renders(self):
        reg = MetricsRegistry()
        c = reg.counter("dl4j_things", "things done")
        c.inc(3)
        text1 = exposition.render_prometheus(reg)
        assert "# HELP dl4j_things_total things done" in text1
        assert "# TYPE dl4j_things_total counter" in text1
        assert "dl4j_things_total 3" in text1

        def value(text):
            line = [ln for ln in text.splitlines()
                    if ln.startswith("dl4j_things_total ")][0]
            return float(line.split()[-1])

        c.inc(2)
        assert value(exposition.render_prometheus(reg)) >= value(text1)

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("c").labels(path='a"b\\c\nd').inc()
        text = exposition.render_prometheus(reg)
        assert r'c_total{path="a\"b\\c\nd"} 1' in text

    def test_histogram_rendering(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", "latency", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.labels(e="x").observe(v)
        text = exposition.render_prometheus(reg)
        assert '# TYPE lat histogram' in text
        assert 'lat_bucket{e="x",le="0.1"} 1' in text
        assert 'lat_bucket{e="x",le="1"} 2' in text
        assert 'lat_bucket{e="x",le="+Inf"} 3' in text
        assert 'lat_count{e="x"} 3' in text
        assert 'lat_sum{e="x"} 5.55' in text

    def test_nan_and_inf_values_render_not_crash(self):
        """A diverged loss (NaN gauge) must not 500 every scrape."""
        reg = MetricsRegistry()
        reg.gauge("loss").set(float("nan"))
        reg.gauge("hi").set(float("inf"))
        text = exposition.render_prometheus(reg)
        assert "loss NaN" in text
        assert "hi +Inf" in text

    def test_remove_caps_label_cardinality(self):
        reg = MetricsRegistry()
        fam = reg.counter("c")
        fam.labels(engine="e0").inc()
        fam.labels(engine="e1").inc()
        fam.remove(engine="e0")
        assert [lab for lab, _ in fam.children()] == [{"engine": "e1"}]
        fam.remove(engine="ghost")  # absent series: no-op

    def test_snapshot_route_payload(self):
        reg = MetricsRegistry()
        reg.gauge("g").set(2.0)
        code, ctype, body = exposition.handle_metrics_get("/snapshot", reg)
        assert code == 200 and ctype == "application/json"
        assert json.loads(body)["g"]["series"][0]["value"] == 2.0
        assert exposition.handle_metrics_get("/elsewhere", reg) is None

    def test_standalone_metrics_server(self):
        reg = MetricsRegistry()
        reg.counter("standalone_hits").inc(7)
        handle = exposition.start_metrics_server(registry=reg)
        try:
            with urllib.request.urlopen(
                    f"{handle.url}/metrics", timeout=10) as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                assert b"standalone_hits_total 7" in r.read()
        finally:
            handle.close()


# ===================================================================== trace
class TestTrace:
    """Every test starts and ends with no tracer: conftest's
    `no_tracer_left_behind`."""

    def test_disabled_span_records_nothing(self):
        with telemetry.span("ghost") as s:
            pass
        assert telemetry.chrome_trace() == {"traceEvents": []}
        assert s.span_id is None and s.dur_ns == 0  # it never ran

    def test_nesting_and_chrome_round_trip(self, tmp_path):
        tracer = telemetry.start_tracing()
        with telemetry.span("outer", phase="epoch"):
            with telemetry.span("inner"):
                time.sleep(0.001)
            with telemetry.span("inner"):
                pass
        spans = tracer.spans()
        assert [s.name for s in spans] == ["inner", "inner", "outer"]
        outer = spans[-1]
        assert outer.parent_id is None
        assert len({s.span_id for s in spans}) == 3
        for inner in spans[:2]:  # children nest inside the parent window
            assert inner.parent_id == outer.span_id
            assert outer.start_ns <= inner.start_ns
            assert (inner.start_ns + inner.dur_ns
                    <= outer.start_ns + outer.dur_ns)

        path = str(tmp_path / "trace.json")
        assert telemetry.save_chrome_trace(path) == path
        with open(path) as f:
            loaded = json.load(f)  # the round trip: valid Chrome JSON
        events = loaded["traceEvents"]
        assert len(events) == 3
        by_name = {}
        for e in events:
            assert e["ph"] == "X" and e["dur"] >= 0
            by_name.setdefault(e["name"], []).append(e)
        out = by_name["outer"][0]
        assert out["args"]["phase"] == "epoch"
        assert out["args"]["parent_id"] is None
        for inner in by_name["inner"]:
            assert inner["args"]["parent_id"] == out["args"]["span_id"]
            assert out["ts"] <= inner["ts"]
            assert inner["ts"] + inner["dur"] <= out["ts"] + out["dur"] + 1e-3

    def test_buffer_is_bounded(self):
        tracer = telemetry.start_tracing(max_spans=4)
        for i in range(10):
            with telemetry.span(f"s{i}"):
                pass
        assert [s.name for s in tracer.spans()] == ["s6", "s7", "s8", "s9"]

    def test_explicit_parent_and_spans_stamped_elsewhere(self):
        """Work caused from another thread names its cause; a span whose
        ends were stamped elsewhere (a request's stages) is added whole."""
        tracer = telemetry.start_tracing()
        with telemetry.span("cause") as cause:
            pass
        done = []

        def worker():
            with telemetry.span("effect", parent_id=cause.span_id,
                                request=7) as s:
                done.append(s)

        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive() and done
        root = tracer.add("request", 1_000, 5_000, thread_id=7, request=7)
        child = tracer.add("request.queued", 1_000, 2_000, parent_id=root,
                           thread_id=7, request=7)
        by_name = {s.name: s for s in tracer.spans()}
        assert by_name["effect"].parent_id == cause.span_id
        assert by_name["effect"].thread_id != by_name["cause"].thread_id
        assert by_name["request.queued"].span_id == child
        assert by_name["request.queued"].parent_id == root
        assert (by_name["request"].start_ns,
                by_name["request"].dur_ns) == (1_000, 4_000)
        events = telemetry.chrome_trace()["traceEvents"]
        assert {e["args"].get("request") for e in events} == {None, 7}

    def test_jax_annotation_bridge_smoke(self, tmp_path):
        """A span that runs is a TraceMe of the same name, with no flag
        to set: inside a `jax.profiler` window the trace holds it, and a
        span that does not run (no tracer, no totals) leaves nothing."""
        import glob

        import jax
        from jax.profiler import ProfileData

        with telemetry.span("not_recorded"):
            pass
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with telemetry.span("unseen"):
                pass
            telemetry.start_tracing()
            with telemetry.span("annotated", step=3):
                pass
        finally:
            jax.profiler.stop_trace()
        assert [s.name for s in telemetry.active_tracer().spans()] \
            == ["annotated"]
        found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        assert found
        names = {ev.name for plane in ProfileData.from_file(found[-1]).planes
                 for line in plane.lines for ev in line.events}
        assert "annotated" in names
        assert not names & {"unseen", "not_recorded"}


# ==================================================================== device
class TestDeviceMetrics:
    def test_install_registers_device_series(self):
        reg = MetricsRegistry()
        device.install(reg)
        text = exposition.render_prometheus(reg)
        assert "dl4j_device_count" in text
        import jax
        count = [c for _, c in reg.gauge("dl4j_device_count").children()]
        assert count and count[0].value == len(jax.local_devices())

    def test_watch_jit_cache_aggregates_and_propagates_unavailable(self):
        reg = MetricsRegistry()

        class Owner:
            def __init__(self, n):
                self.n = n

            def probe(self):
                return self.n

        a, b = Owner(2), Owner(3)
        label = f"test-{id(reg)}"  # module-global watch table: unique label
        device.watch_jit_cache(label, a.probe, registry=reg)
        device.watch_jit_cache(label, b.probe, registry=reg)
        assert device.jit_cache_total(label) == 5
        b.n = -1  # private-API drift is reported, not summed away
        assert device.jit_cache_total(label) == -1
        b.n = 3
        del b  # dead owners fall out via their weakrefs
        import gc
        gc.collect()
        assert device.jit_cache_total(label) == 2


# =========================================================== instrumentation
class TestInstrumentedTraining:
    def test_fit_publishes_steps_examples_and_feed_counters(self):
        reg = telemetry.get_registry()
        steps0 = reg.counter("dl4j_train_steps").value
        ex0 = reg.counter("dl4j_train_examples").value
        batches0 = reg.counter("dl4j_feed_batches").value

        net = _net()
        x, y = _data(40)
        feed = DeviceFeed(ListDataSetIterator(DataSet(x, y), 16))
        net.fit(feed, epochs=2)  # 3 batches/epoch (16, 16, 8)

        assert reg.counter("dl4j_train_steps").value - steps0 == 6
        # bucketed rows: 16+16+8(pad of ragged 8-row tail) per epoch
        assert reg.counter("dl4j_train_examples").value - ex0 == 80
        assert reg.counter("dl4j_feed_batches").value - batches0 == 6
        hist = reg.histogram("dl4j_train_step_seconds")
        assert hist.labels(source="fit").count >= 6

    def test_fit_scan_publishes_scan_series_and_loss(self):
        reg = telemetry.get_registry()
        steps0 = reg.counter("dl4j_train_steps").value
        net = _net()
        x, y = _data(32)
        score = net.fit_scan(x, y, batch_size=8, epochs=2)
        assert reg.counter("dl4j_train_steps").value - steps0 == 8
        assert reg.gauge("dl4j_train_loss").value == pytest.approx(score)
        assert reg.histogram(
            "dl4j_train_step_seconds").labels(source="scan").count >= 1

    def test_guardian_events_reach_the_registry(self):
        from deeplearning4j_tpu.optimize.guardian import GuardianPolicy

        reg = telemetry.get_registry()
        skips0 = reg.counter("dl4j_guardian_events").labels(kind="skip").value
        net = _net()
        x, y = _data(48)
        x[16:32] = np.nan  # one poisoned batch mid-stream
        net.fit(ListDataSetIterator(DataSet(x, y), 16),
                guardian=GuardianPolicy(check_every=1, snapshot_every=100,
                                        max_skips_per_window=2))
        assert reg.counter("dl4j_guardian_events").labels(
            kind="skip").value > skips0

    def test_listeners_publish_without_a_second_code_path(self):
        from deeplearning4j_tpu.optimize.listeners import (
            CollectScoresListener, StepTimeListener)

        reg = telemetry.get_registry()
        listener_hist = reg.histogram(
            "dl4j_train_step_seconds").labels(source="listener")
        before = listener_hist.count
        net = _net()
        scores, times = CollectScoresListener(), StepTimeListener()
        net.set_listeners([scores, times])
        x, y = _data(16)
        for _ in range(3):
            net.fit(x, y)
        assert len(scores.scores) == 3  # public API unchanged
        assert len(times.step_times) == 2
        assert listener_hist.count - before == 2
        assert reg.gauge("dl4j_train_loss").value \
            == pytest.approx(scores.scores[-1][1])

    def test_off_by_default_paths_bit_identical(self):
        """The instrumented fit must produce bit-identical parameters
        with telemetry enabled vs killed — recording is host counters
        only."""
        x, y = _data(32)
        net_on = _net()
        net_on.fit(x, y, epochs=3)
        telemetry.set_enabled(False)
        try:
            net_off = _net()
            net_off.fit(x, y, epochs=3)
        finally:
            telemetry.set_enabled(True)
        np.testing.assert_array_equal(np.asarray(net_on.params()),
                                      np.asarray(net_off.params()))

    def test_instrumentation_overhead_generous_bound(self):
        """Gate for the bench.py `telemetry` config's <2% CPU-smoke
        target: the per-step cost of the registry (a few counter incs +
        one histogram observe + a disabled span) must stay far under a
        generous 50% bound even on a noisy 1-core CI box."""
        net = _net()
        x, y = _data(64)
        net.fit(x, y)  # compile

        def run(n=60):
            t0 = time.perf_counter()
            for _ in range(n):
                net.fit(x, y)
            return time.perf_counter() - t0

        def bare(n=60):
            telemetry.set_enabled(False)
            try:
                return run(n)
            finally:
                telemetry.set_enabled(True)

        on = min(run() for _ in range(3))
        off = min(bare() for _ in range(3))
        overhead = (on - off) / off
        assert overhead < 0.5, f"telemetry overhead {overhead:.1%}"


# ======================================================================= cli
class TestCLITelemetry:
    def test_train_with_trace_and_metrics_port(self, tmp_path, capsys):
        from deeplearning4j_tpu.cli import main
        from deeplearning4j_tpu.datasets.iris import load_iris

        x, y = load_iris()
        data = np.hstack([np.asarray(x),
                          np.argmax(np.asarray(y), 1)[:, None]])
        csv = tmp_path / "iris.csv"
        np.savetxt(csv, data, delimiter=",", fmt="%.4f")
        conf = (NeuralNetConfiguration.builder()
                .lr(0.1).n_in(4).activation_function("tanh")
                .num_iterations(3).use_adagrad(False)
                .list(2).hidden_layer_sizes([8])
                .override(1, layer="output", loss_function="mcxent",
                          activation_function="softmax", n_out=3)
                .pretrain(False).build())
        conf_path = tmp_path / "conf.json"
        conf_path.write_text(conf.to_json())
        trace_path = tmp_path / "trace.json"

        assert main(["train", "-i", str(csv), "-m", str(conf_path),
                     "-o", str(tmp_path / "m.ckpt"),
                     "--metrics-port", "0",
                     "--trace", str(trace_path)]) == 0
        lines = capsys.readouterr().out.strip().splitlines()
        # the live endpoint is announced UP FRONT (before the fit); the
        # closing summary carries only the trace path — the endpoint is
        # already shut down, a dead URL there would mislead parsers
        first, last = json.loads(lines[0]), json.loads(lines[-1])
        assert first["metrics"].endswith("/metrics")
        assert "metrics" not in last
        assert last["trace"] == str(trace_path)
        with open(trace_path) as f:
            events = json.load(f)["traceEvents"]
        assert any(e["name"] == "train_step" for e in events)
