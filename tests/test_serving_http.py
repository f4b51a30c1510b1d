"""End-to-end HTTP serving: /predict, /generate, /healthz, /stats on an
ephemeral port; graceful shutdown releases the socket (the shared
utils/httpd.py lifecycle both this server and plot/render_server use);
CLI `serve` smoke."""

from __future__ import annotations

import json
import socket
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.config import NeuralNetConfiguration
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_transformer_params)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import InferenceEngine, serve_network

CFG = TransformerConfig(vocab_size=17, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=64, interpret=True)


def _net(n_in=4, n_out=3):
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(n_in).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(1).use_adagrad(False)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=n_out)
            .pretrain(False).build())
    return MultiLayerNetwork(conf)


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


class TestHTTPRoundTrip:
    def test_predict_healthz_stats_and_shutdown(self):
        net = _net()
        handle = serve_network(net, n_replicas=2, max_batch_size=16,
                               max_delay_ms=1.0, warmup_shape=(4,))
        try:
            assert handle.port != 0  # ephemeral port was bound
            health = _get(f"{handle.url}/healthz")
            assert health["ok"] and health["replicas"] == 2

            x = np.random.RandomState(0).rand(3, 4)
            out = _post(f"{handle.url}/predict",
                        {"inputs": x.tolist()})
            assert np.asarray(out["outputs"]).shape == (3, 3)
            assert len(out["classes"]) == 3
            ref = np.asarray(net.output(x.astype(np.float32)))
            np.testing.assert_allclose(np.asarray(out["outputs"]), ref,
                                       atol=1e-5)

            stats = _get(f"{handle.url}/stats")
            assert stats["replicas"]["rows"] >= 3
            assert stats["batcher"]["completed"] >= 1
            assert stats["batcher"]["queue_depth"] >= 0
            # per-bucket forward counts (3 rows -> the 8-bucket)
            assert sum(stats["replicas"]["bucket_forwards"].values()) >= 1
            assert stats["uptime_s"] >= 0
        finally:
            handle.close()

    def test_metrics_e2e_scrape(self):
        """Acceptance bar: a /metrics scrape on a live serve instance
        returns Prometheus text carrying train/serve/guardian/device
        series (docs/OBSERVABILITY.md)."""
        net = _net()
        with serve_network(net, n_replicas=1, max_batch_size=16,
                           max_delay_ms=1.0) as handle:
            x = np.random.RandomState(0).rand(2, 4)
            _post(f"{handle.url}/predict", {"inputs": x.tolist()})
            with urllib.request.urlopen(f"{handle.url}/metrics",
                                        timeout=30) as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                text = r.read().decode()
            for series in (
                    "dl4j_serve_requests_total",      # serve
                    "dl4j_serve_latency_seconds_bucket",
                    "dl4j_serve_bucket_forwards_total",
                    "dl4j_batcher_queue_depth",
                    "dl4j_train_steps_total",         # train
                    "dl4j_guardian_events_total",     # guardian
                    "dl4j_device_count",              # device
                    "dl4j_device_memory_bytes",
                    "dl4j_jit_programs",
            ):
                assert series in text, f"{series} missing from /metrics"
            # this serve instance's engine actually counted the request
            assert 'dl4j_serve_requests_total{engine="' in text
            snap = _get(f"{handle.url}/snapshot")
            assert "dl4j_serve_requests" in snap
        # socket actually released: reconnect must fail fast
        with pytest.raises((ConnectionError, urllib.error.URLError, OSError)):
            _get(f"{handle.url}/healthz", timeout=2)
        # and the port is rebindable (server_close ran)
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", handle.port))

    def test_generate_endpoint(self):
        """Backward-compat: the legacy n_tokens request shape returns
        the same {"tokens": [[prompt+generated]]} rows — now served by
        the continuous-batching slot scheduler."""
        params = init_transformer_params(jax.random.PRNGKey(0), CFG)
        gen = InferenceEngine.for_transformer(params, CFG)
        with serve_network(_net(), n_replicas=1, max_delay_ms=1.0,
                           generate_engine=gen, slots=4,
                           page_size=8) as handle:
            prompt = [[1, 2, 3, 4]]
            out = _post(f"{handle.url}/generate",
                        {"prompt": prompt, "n_tokens": 5})
            toks = np.asarray(out["tokens"])
            assert toks.shape == (1, 9)
            assert (toks[:, :4] == np.asarray(prompt)).all()
            assert ((0 <= toks) & (toks < CFG.vocab_size)).all()
            assert out["finish_reasons"] == ["max_tokens"]

    def test_generate_eos_and_per_request_max_tokens(self):
        """ISSUE satellite: per-request max_tokens + EOS-token early
        termination on /generate (ragged rows in one request)."""
        from deeplearning4j_tpu.serving.kv_cache import generate_cached
        import jax.numpy as jnp

        params = init_transformer_params(jax.random.PRNGKey(0), CFG)
        gen = InferenceEngine.for_transformer(params, CFG)
        prompt = [1, 2, 3, 4]
        ref = np.asarray(generate_cached(
            params, jnp.asarray([prompt], jnp.int32), CFG, 12))[0, 4:]
        eos = int(ref[3])
        first = int(np.argmax(ref == eos))
        with serve_network(_net(), n_replicas=1, max_delay_ms=1.0,
                           generate_engine=gen, slots=4,
                           page_size=8) as handle:
            out = _post(f"{handle.url}/generate",
                        {"prompt": [prompt, [5, 6, 7]],
                         "max_tokens": 12, "eos_id": eos})
            # row 0 stopped at ITS eos; row 1 ran its own course
            assert out["tokens"][0] == prompt + ref[:first + 1].tolist()
            assert out["finish_reasons"][0] == "eos"
            assert out["finish_reasons"][1] in ("eos", "max_tokens")

    def test_generate_streaming_chunked(self):
        """ISSUE tentpole: streaming /generate — chunked transfer, one
        NDJSON line per token as slots emit, final summary line."""
        params = init_transformer_params(jax.random.PRNGKey(0), CFG)
        gen = InferenceEngine.for_transformer(params, CFG)
        with serve_network(_net(), n_replicas=1, max_delay_ms=1.0,
                           generate_engine=gen, slots=4,
                           page_size=8) as handle:
            req = urllib.request.Request(
                f"{handle.url}/generate",
                data=json.dumps({"prompt": [[1, 2, 3, 4], [5, 6, 7]],
                                 "max_tokens": 6,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                assert r.headers["Content-Type"].startswith(
                    "application/x-ndjson")
                # tokens arrive line-by-line BEFORE the body ends
                events = []
                while True:
                    line = r.readline()
                    if not line:
                        break
                    events.append(json.loads(line))
            token_events = [e for e in events if "token" in e]
            final = events[-1]
            assert final["done"] is True
            assert len(token_events) == 12  # 6 per row
            # per-row order of streamed tokens == final row content
            for row in (0, 1):
                streamed = [e["token"] for e in token_events
                            if e["row"] == row]
                plen = len(final["tokens"][row]) - 6
                assert final["tokens"][row][plen:] == streamed
            # non-streaming twin returns the same rows (same greedy
            # decode through the same slot scheduler)
            out = _post(f"{handle.url}/generate",
                        {"prompt": [[1, 2, 3, 4], [5, 6, 7]],
                         "max_tokens": 6})
            assert out["tokens"] == final["tokens"]

    def test_decode_loop_metrics_e2e(self):
        """ISSUE satellite: dl4j_kv_pages_* / dl4j_decode_active_slots /
        streamed-token counters appear on a live /metrics scrape after
        /generate traffic."""
        params = init_transformer_params(jax.random.PRNGKey(0), CFG)
        gen = InferenceEngine.for_transformer(params, CFG)
        with serve_network(_net(), n_replicas=1, max_delay_ms=1.0,
                           generate_engine=gen, slots=4,
                           page_size=8) as handle:
            _post(f"{handle.url}/generate",
                  {"prompt": [[1, 2, 3, 4]], "max_tokens": 5})
            with urllib.request.urlopen(f"{handle.url}/metrics",
                                        timeout=30) as r:
                text = r.read().decode()
            for series in (
                    "dl4j_kv_pages_total",
                    "dl4j_kv_pages_in_use",
                    "dl4j_decode_active_slots",
                    "dl4j_decode_tokens_streamed_total",
                    "dl4j_decode_requests_total",
                    "dl4j_decode_kv_read_bytes_total",
                    "dl4j_decode_step_seconds",
            ):
                assert series in text, f"{series} missing from /metrics"
            # the KV traffic counters carry both lane figures — the
            # streamed-kernel figure must undercut the dense one
            # (this loop's: the registry keeps every loop the process
            # ever built, and one that never ran a step counts 0)
            label = gen.decode_loop.label
            kv_read = {}
            for ln in text.splitlines():
                if (ln.startswith("dl4j_decode_kv_read_bytes_total{")
                        and f'loop="{label}"' in ln):
                    for path in ("kernel", "gather"):
                        if f'path="{path}"' in ln:
                            kv_read[path] = float(ln.split()[-1])
            assert kv_read.get("kernel", 0) > 0
            assert kv_read["gather"] > kv_read["kernel"]
            # the pool gauge reports this loop's configured size and
            # the request actually streamed its tokens
            assert (f'dl4j_kv_pages_total{{loop="{label}"}} '
                    f'{gen.decode_loop.n_pages}') in text
            streamed = [ln for ln in text.splitlines()
                        if ln.startswith("dl4j_decode_tokens_streamed")
                        and f'loop="{label}"' in ln]
            assert streamed and float(streamed[0].split()[-1]) >= 5
            # /stats carries the decode-loop occupancy surface
            stats = _get(f"{handle.url}/stats")
            dec = stats["generate"]["decode"]
            assert dec["pages_total"] == gen.decode_loop.n_pages
            assert dec["pages_in_use"] == 0  # request finished
            assert dec["decode_step_programs"] == 1

    def test_keepalive_connection_survives_early_reply_paths(self):
        """HTTP/1.1 keep-alive: a reply sent before the POST body was
        parsed (404 routes) must still consume the body, or the
        leftover bytes desync the connection for the next request."""
        import http.client

        with serve_network(_net(), n_replicas=1,
                           max_delay_ms=1.0) as handle:
            conn = http.client.HTTPConnection("127.0.0.1", handle.port,
                                              timeout=30)
            try:
                body = json.dumps({"prompt": [[1, 2]], "n_tokens": 2})
                # no generate engine -> 404 BEFORE the body is parsed
                conn.request("POST", "/generate", body=body,
                             headers={"Content-Type": "application/json"})
                assert conn.getresponse().read() is not None
                # unknown route with a body -> 404, body still drained
                conn.request("POST", "/nowhere", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                assert resp.status == 404
                resp.read()  # client must drain before reusing the conn
                # the SAME connection must still serve a real request
                x = np.random.RandomState(0).rand(2, 4)
                conn.request("POST", "/predict",
                             body=json.dumps({"inputs": x.tolist()}),
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                assert resp.status == 200
                assert np.asarray(
                    json.loads(resp.read())["outputs"]).shape == (2, 3)
            finally:
                conn.close()

    def test_generate_slots_zero_selects_legacy_path(self):
        """slots=0 opts out of continuous batching: /generate serves
        the per-request compiled scan; stream/eos_id are rejected."""
        params = init_transformer_params(jax.random.PRNGKey(0), CFG)
        gen = InferenceEngine.for_transformer(params, CFG)
        with serve_network(_net(), n_replicas=1, max_delay_ms=1.0,
                           generate_engine=gen, slots=0) as handle:
            assert gen.decode_loop is None
            out = _post(f"{handle.url}/generate",
                        {"prompt": [[1, 2, 3, 4]], "n_tokens": 5})
            assert len(out["tokens"][0]) == 9
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"{handle.url}/generate",
                      {"prompt": [[1, 2]], "max_tokens": 2,
                       "stream": True})
            assert e.value.code == 400

    def test_generate_bad_row_does_not_orphan_row_mates(self):
        """All rows validate before any submits: a malformed row 400s
        the request and leaves no stream running in a slot."""
        params = init_transformer_params(jax.random.PRNGKey(0), CFG)
        gen = InferenceEngine.for_transformer(params, CFG)
        with serve_network(_net(), n_replicas=1, max_delay_ms=1.0,
                           generate_engine=gen, slots=2,
                           page_size=8) as handle:
            overlong = list(range(CFG.max_len - 2))  # + max_tokens > max_len
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"{handle.url}/generate",
                      {"prompt": [[1, 2, 3], overlong], "max_tokens": 8})
            assert e.value.code == 400
            snap = gen.decode_loop.snapshot()
            assert snap["occupied_slots"] == 0 and snap["queued"] == 0
            assert snap["requests"] == 0  # nothing was submitted

    def test_error_paths(self):
        with serve_network(_net(), n_replicas=1,
                           max_delay_ms=1.0) as handle:
            # bad JSON -> 400
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"{handle.url}/predict", {"nope": 1})
            assert e.value.code == 400
            # feature-width mismatch surfaces as a request error
            with pytest.raises(urllib.error.HTTPError):
                _post(f"{handle.url}/predict",
                      {"inputs": [[1.0, 2.0]]})
            # /generate without a transformer engine -> 404
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"{handle.url}/generate",
                      {"prompt": [[1]], "n_tokens": 2})
            assert e.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(f"{handle.url}/nowhere")
            assert e.value.code == 404


class TestReadiness:
    """ISSUE 7 satellite: /healthz stays liveness; /readyz gates on
    warmup completion and decode-loop health."""

    def test_async_warmup_gates_readyz(self):
        import threading

        from deeplearning4j_tpu.serving import ReplicaSet

        net = _net()
        rs = ReplicaSet.for_network(net, n_replicas=1, max_batch_size=16)
        gate = threading.Event()
        inner_warmup = rs.warmup

        def gated_warmup(shape, **kw):
            assert gate.wait(30)
            inner_warmup(shape, **kw)

        rs.warmup = gated_warmup
        handle = serve_network(replicas=rs, max_delay_ms=1.0,
                               warmup_shape=(4,), warmup_async=True)
        try:
            # alive immediately, NOT ready until the warmup lands
            assert _get(f"{handle.url}/healthz")["ok"]
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(f"{handle.url}/readyz")
            assert e.value.code == 503
            body = json.loads(e.value.read())
            assert body["ready"] is False
            assert "warmup" in body["reason"]
            gate.set()
            deadline = 30
            import time
            t0 = time.monotonic()
            while True:
                try:
                    ready = _get(f"{handle.url}/readyz")
                    break
                except urllib.error.HTTPError:
                    assert time.monotonic() - t0 < deadline
                    time.sleep(0.05)
            assert ready["ready"] and ready["warmup_done"]
            assert rs.engines[0].warmed_up
        finally:
            gate.set()
            handle.close()

    def test_sync_warmup_is_ready_from_first_connection(self):
        with serve_network(_net(), n_replicas=1, max_delay_ms=1.0,
                           warmup_shape=(4,)) as handle:
            assert _get(f"{handle.url}/readyz")["ready"] is True

    def test_dead_decode_loop_flips_readyz(self):
        params = init_transformer_params(jax.random.PRNGKey(0), CFG)
        gen = InferenceEngine.for_transformer(params, CFG)
        with serve_network(_net(), n_replicas=1, max_delay_ms=1.0,
                           generate_engine=gen, slots=2,
                           page_size=8) as handle:
            assert _get(f"{handle.url}/readyz")["decode_loop_alive"]
            gen.decode_loop.close()  # the loop dies under the server
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(f"{handle.url}/readyz")
            assert e.value.code == 503
            body = json.loads(e.value.read())
            assert "decode loop" in body["reason"]
            # liveness is unaffected — the split is the point
            assert _get(f"{handle.url}/healthz")["ok"]


class TestOverloadShedding:
    """ISSUE 7 satellite: saturation answers 503 + Retry-After +
    {"error": "overloaded", "retry_after_ms": N} — machine-actionable
    end to end, on both /predict (batcher queue) and /generate
    (decode admission queue)."""

    def test_predict_queue_full_sheds_503_with_retry_after(self):
        import threading

        from deeplearning4j_tpu.serving import ReplicaSet

        gate = threading.Event()

        class GatedEngine:
            """Duck-typed engine: blocks until released."""

            decode_loop = None

            def infer(self, x):
                assert gate.wait(30)
                return np.zeros((x.shape[0], 3), np.float32)

            def snapshot(self):
                return {"requests": 0, "rows": 0, "errors": 0}

            def program_cache_size(self):
                return 0

        handle = serve_network(replicas=ReplicaSet([GatedEngine()]),
                               max_delay_ms=1.0, max_queue=1)
        try:
            results = []

            def post_bg():
                try:
                    results.append(_post(f"{handle.url}/predict",
                                         {"inputs": [[1.0, 2.0]]}))
                except Exception as e:  # noqa: BLE001
                    results.append(e)

            # request 1 occupies the engine; request 2 fills the queue
            threads = [threading.Thread(target=post_bg, daemon=True)
                       for _ in range(2)]
            threads[0].start()
            import time
            time.sleep(0.3)  # worker has dequeued req 1 into the engine
            threads[1].start()
            time.sleep(0.3)  # req 2 is parked in the queue
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"{handle.url}/predict", {"inputs": [[1.0, 2.0]]})
            assert e.value.code == 503
            assert int(e.value.headers["Retry-After"]) >= 1
            body = json.loads(e.value.read())
            assert body["error"] == "overloaded"
            assert body["retry_after_ms"] > 0
            gate.set()
            for t in threads:
                t.join(timeout=30)
            assert all(isinstance(r, dict) for r in results)
            assert handle.batcher.snapshot()["shed"] == 1
        finally:
            gate.set()
            handle.close()

    def test_generate_admission_full_sheds_503(self):
        params = init_transformer_params(jax.random.PRNGKey(0), CFG)
        gen = InferenceEngine.for_transformer(params, CFG)
        with serve_network(_net(), n_replicas=1, max_delay_ms=1.0,
                           generate_engine=gen, slots=1, page_size=8,
                           max_waiting=0) as handle:
            assert gen.decode_loop.max_waiting == 0
            # request 1 occupies the single slot for ~max_len tokens;
            # reading its first streamed token proves it holds the slot
            req = urllib.request.Request(
                f"{handle.url}/generate",
                data=json.dumps({"prompt": [[1, 2, 3, 4]],
                                 "max_tokens": 48,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            r = urllib.request.urlopen(req, timeout=60)
            first = json.loads(r.readline())
            assert "token" in first
            # slot busy + max_waiting=0 -> the second request sheds
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"{handle.url}/generate",
                      {"prompt": [[5, 6]], "max_tokens": 2})
            assert e.value.code == 503
            assert int(e.value.headers["Retry-After"]) >= 1
            body = json.loads(e.value.read())
            assert body["error"] == "overloaded"
            r.close()
            assert gen.decode_loop.snapshot()["shed"] == 1


class TestHotReload:
    """ISSUE satellite: POST /reload hot-swaps replica weights from a
    checkpoint path without dropping in-flight requests."""

    def _checkpoints(self, tmp_path):
        """Two nets with the same architecture but different weights,
        each checkpointed: (net_a, net_b, sharded_dir_b, npz_path_b)."""
        from deeplearning4j_tpu.checkpoint import ShardedModelSaver
        from deeplearning4j_tpu.scaleout.checkpoint import DefaultModelSaver

        net_a, net_b = _net(), _net()
        x, y = (np.random.RandomState(1).rand(48, 4).astype(np.float32),
                np.eye(3, dtype=np.float32)[
                    np.random.RandomState(2).randint(0, 3, 48)])
        net_b.fit(x, y, epochs=3)  # diverge the weights
        sharded = str(tmp_path / "sharded")
        with ShardedModelSaver(sharded, sync=True) as saver:
            saver.save(net_b, iterator_position=3)
        npz = str(tmp_path / "b.ckpt")
        DefaultModelSaver(npz, keep_old=False).save(net_b)
        return net_a, net_b, sharded, npz

    def test_reload_swaps_weights_without_dropping_requests(self,
                                                            tmp_path):
        import threading

        net_a, net_b, sharded, _ = self._checkpoints(tmp_path)
        x = np.random.RandomState(0).rand(3, 4).astype(np.float32)
        ref_a = np.asarray(net_a.output(x))
        ref_b = np.asarray(net_b.output(x))
        assert not np.allclose(ref_a, ref_b)  # the swap is observable

        with serve_network(net_a, n_replicas=2, max_batch_size=16,
                           max_delay_ms=1.0, warmup_shape=(4,)) as handle:
            out = _post(f"{handle.url}/predict", {"inputs": x.tolist()})
            np.testing.assert_allclose(np.asarray(out["outputs"]), ref_a,
                                       atol=1e-5)

            # hammer /predict from the side WHILE reloading: every
            # response must be valid (old or new weights, never an error)
            stop = threading.Event()
            failures = []

            def hammer():
                while not stop.is_set():
                    try:
                        r = _post(f"{handle.url}/predict",
                                  {"inputs": x.tolist()})
                        got = np.asarray(r["outputs"])
                        if not (np.allclose(got, ref_a, atol=1e-5)
                                or np.allclose(got, ref_b, atol=1e-5)):
                            failures.append("torn outputs")
                    except Exception as e:  # noqa: BLE001
                        failures.append(repr(e))

            t = threading.Thread(target=hammer, daemon=True)
            t.start()
            try:
                res = _post(f"{handle.url}/reload", {"path": sharded})
            finally:
                stop.set()
                t.join(timeout=30)
            assert res["reloaded"] and res["replicas"] == 2
            assert res["step"] == 3
            assert failures == []

            # all replicas now serve net_b's weights
            out2 = _post(f"{handle.url}/predict", {"inputs": x.tolist()})
            np.testing.assert_allclose(np.asarray(out2["outputs"]), ref_b,
                                       atol=1e-5)
            stats = _get(f"{handle.url}/stats")
            assert stats["last_reload"]["step"] == 3

    def test_reload_accepts_legacy_npz_checkpoints(self, tmp_path):
        net_a, net_b, _, npz = self._checkpoints(tmp_path)
        x = np.random.RandomState(0).rand(2, 4).astype(np.float32)
        ref_b = np.asarray(net_b.output(x))
        with serve_network(net_a, n_replicas=1,
                           max_delay_ms=1.0) as handle:
            _post(f"{handle.url}/reload", {"path": npz})
            out = _post(f"{handle.url}/predict", {"inputs": x.tolist()})
            np.testing.assert_allclose(np.asarray(out["outputs"]), ref_b,
                                       atol=1e-5)

    def test_reload_error_paths(self, tmp_path):
        net_a, _, sharded, npz = self._checkpoints(tmp_path)
        with serve_network(net_a, n_replicas=1,
                           max_delay_ms=1.0) as handle:
            # missing path key -> 400
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"{handle.url}/reload", {})
            assert e.value.code == 400
            # nonexistent checkpoint -> 404
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"{handle.url}/reload",
                      {"path": str(tmp_path / "nope")})
            assert e.value.code == 404
            # step pin against a single-file npz -> 400, not a silent
            # load of whatever the file holds
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"{handle.url}/reload", {"path": npz, "step": 5})
            assert e.value.code == 400
            assert "no steps" in json.loads(e.value.read())["error"]
            # architecture mismatch -> 400 naming the leaf
            from deeplearning4j_tpu.checkpoint import ShardedModelSaver
            other_conf = (NeuralNetConfiguration.builder()
                          .lr(0.1).n_in(4).activation_function("tanh")
                          .optimization_algo("iteration_gradient_descent")
                          .num_iterations(1).use_adagrad(False)
                          .list(2).hidden_layer_sizes([16])
                          .override(1, layer="output",
                                    loss_function="mcxent",
                                    activation_function="softmax",
                                    n_out=3)
                          .pretrain(False).build())
            wide = MultiLayerNetwork(other_conf)
            wrong = str(tmp_path / "wrong")
            with ShardedModelSaver(wrong, sync=True) as saver:
                saver.save(wide)
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(f"{handle.url}/reload", {"path": wrong})
            assert e.value.code == 400
            body = json.loads(e.value.read())
            assert "0/W" in body["error"]  # names the mismatched leaf
            # the serving weights are untouched after the failed reload
            x = np.random.RandomState(0).rand(2, 4).astype(np.float32)
            out = _post(f"{handle.url}/predict", {"inputs": x.tolist()})
            np.testing.assert_allclose(np.asarray(out["outputs"]),
                                       np.asarray(net_a.output(x)),
                                       atol=1e-5)


class TestCLIServe:
    def test_serve_smoke(self, tmp_path, capsys):
        from deeplearning4j_tpu.cli import main
        from deeplearning4j_tpu.scaleout.checkpoint import DefaultModelSaver

        ckpt = str(tmp_path / "m.ckpt")
        DefaultModelSaver(ckpt).save(_net())
        assert main(["serve", "-m", ckpt, "--replicas", "1",
                     "--max-delay-ms", "1", "--smoke"]) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["serving"].startswith("http://127.0.0.1:")
        assert out["replicas"] == 1
