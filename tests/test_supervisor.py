"""Self-healing elastic training drills (ISSUE 9).

The supervisor composes pieces that each have their own unit tests —
wave barrier + orphan requeue (test_runtime_native), heartbeat
staleness (test_scaleout), sharded checkpoint reshard (TestReshardMatrix)
— into a run that SURVIVES losing a worker process. Tier-1 runs the
fast seeded-chaos drills (deterministic, replayable); the SIGKILL /
SIGSTOP process soaks carry @slow on top of @elastic and the bench
(`bench.py train_elastic`) gates the bit-identity and resharded-resume
acceptance criteria on every record.
"""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.config import NeuralNetConfiguration
from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.datasets.iris import load_iris
from deeplearning4j_tpu.scaleout.api import CollectionJobIterator
from deeplearning4j_tpu.scaleout.registry import ConfigRegistry
from deeplearning4j_tpu.scaleout.supervisor import (DEAD, EVICTED,
                                                    TrainingSupervisor,
                                                    WorkerSpawner,
                                                    _ProgressListener)
from deeplearning4j_tpu.testing import chaos

pytestmark = pytest.mark.elastic


def _conf_json(momentum=0.0, iters=2):
    return (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(4).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(iters).use_adagrad(False).momentum(momentum)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=3)
            .pretrain(False).build().to_json())


def _jobs(n=6, bs=24, seed=0):
    x, y = load_iris()
    x, y = np.asarray(x), np.asarray(y)
    rng = np.random.RandomState(seed)
    return [DataSet(x[i], y[i])
            for i in (rng.choice(len(x), bs, replace=False)
                      for _ in range(n))]


def _supervisor(tmp_path, tag, jobs, n_workers=2, env_for=None, **kw):
    cj = _conf_json()
    registry_root = str(tmp_path / f"reg_{tag}")
    kw.setdefault("heartbeat_timeout", 3.0)
    kw.setdefault("progress_timeout", 90.0)  # cold-compile headroom
    sup = TrainingSupervisor(
        CollectionJobIterator(list(jobs)), run_name=tag,
        registry=ConfigRegistry(registry_root),
        performer_class=("deeplearning4j_tpu.scaleout.perform."
                         "NeuralNetWorkPerformer"),
        performer_conf={"conf_json": cj, "epochs": 1},
        n_workers=n_workers, conf_json=cj,
        spawner=WorkerSpawner(registry_root, tag, env_for=env_for),
        **kw)
    return sup


def _get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read()


# ---------------------------------------------------------------- units
class TestWorkerSpawner:
    def test_command_names_entrypoint_and_worker(self, tmp_path):
        sp = WorkerSpawner(str(tmp_path), "run1")
        cmd = sp.command("w3")
        assert "deeplearning4j_tpu.scaleout.worker" in cmd
        assert "w3" in cmd and "run1" in cmd

    def test_env_carries_package_root_and_per_worker_extras(self,
                                                           tmp_path):
        sp = WorkerSpawner(
            str(tmp_path), "run1", env={"PATH": os.environ["PATH"]},
            env_for=lambda wid: ({"X_DRILL": wid} if wid == "w1"
                                 else {}))
        import deeplearning4j_tpu

        pkg_root = os.path.dirname(os.path.dirname(
            deeplearning4j_tpu.__file__))
        assert pkg_root in sp.env["PYTHONPATH"].split(os.pathsep)
        assert sp.env_for("w1") == {"X_DRILL": "w1"}
        assert sp.env_for("w1r1") == {}

    def test_each_child_gets_its_own_chip_by_index(self, tmp_path,
                                                   monkeypatch):
        """On a TPU host a child started with the parent's environment
        asks for every chip: with an allocator, spawn N confines worker
        N to the lowest chip index no live child holds, and a dead
        child's chip is handed out again."""
        from deeplearning4j_tpu.scaleout import supervisor as sup_mod
        from deeplearning4j_tpu.utils import procs

        started = []

        class FakeProc:
            def __init__(self, cmd, env=None, **kw):
                self.env, self.dead, self.pid = env, False, 0
                started.append(self)

            def poll(self):
                return 0 if self.dead else None

        monkeypatch.setattr(sup_mod.subprocess, "Popen", FakeProc)
        monkeypatch.setattr(procs, "register_spawned", lambda p: None)
        sp = WorkerSpawner(str(tmp_path), "run1",
                           chips=procs.ChipAllocator())
        for wid in ("w0", "w1", "w2"):
            sp.spawn(wid)
        assert [p.env["TPU_VISIBLE_CHIPS"] for p in started] == [
            "0", "1", "2"]
        assert all(p.env["TPU_PROCESS_BOUNDS"] == "1,1,1"
                   and p.env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
                   for p in started)
        started[1].dead = True
        sp.spawn("w1r1")
        assert started[-1].env["TPU_VISIBLE_CHIPS"] == "1"
        # without an allocator (CPU hosts, tests) nothing is confined
        WorkerSpawner(str(tmp_path), "run1").spawn("w9")
        assert "TPU_VISIBLE_CHIPS" not in started[-1].env


class TestProgressListener:
    def test_lines_drive_alive_and_progress_eof_drives_gone(self):
        alive, progress, gone = [], [], []
        lst = _ProgressListener(alive.append,
                                lambda w, d: progress.append((w, d)),
                                gone.append, poll_s=0.05)
        try:
            s = socket.create_connection((lst.host, lst.port), timeout=5)
            s.sendall(b'{"worker_id": "wA"}\n')
            s.sendall(b'{"worker_id": "wA", "performed": 2, '
                      b'"job_s": 0.5}\n')
            deadline = time.time() + 5
            while len(progress) < 2 and time.time() < deadline:
                time.sleep(0.01)
            assert ("wA", {"worker_id": "wA", "performed": 2,
                           "job_s": 0.5}) in progress
            assert "wA" in alive
            s.close()
            deadline = time.time() + 5
            while not gone and time.time() < deadline:
                time.sleep(0.01)
            assert gone == ["wA"]
        finally:
            lst.close()

    def test_open_but_silent_connection_keeps_liveness(self):
        """The SIGSTOP shape: an ESTABLISHED socket with no lines must
        keep producing alive ticks — the watermark, not liveness, is
        what catches a stopped worker."""
        alive, gone = [], []
        lst = _ProgressListener(alive.append, lambda w, d: None,
                                gone.append, poll_s=0.05)
        try:
            s = socket.create_connection((lst.host, lst.port), timeout=5)
            s.sendall(b'{"worker_id": "wB"}\n')
            deadline = time.time() + 5
            while alive.count("wB") < 3 and time.time() < deadline:
                time.sleep(0.01)  # ticks without any further lines
            assert alive.count("wB") >= 3
            assert not gone
        finally:
            try:
                s.close()
            except OSError:
                pass
            lst.close()

    def test_drop_severs_an_evicted_workers_liveness(self):
        alive, gone = [], []
        lst = _ProgressListener(alive.append, lambda w, d: None,
                                gone.append, poll_s=0.05)
        try:
            s = socket.create_connection((lst.host, lst.port), timeout=5)
            s.sendall(b'{"worker_id": "wC"}\n')
            deadline = time.time() + 5
            while not alive and time.time() < deadline:
                time.sleep(0.01)
            lst.drop("wC")
            deadline = time.time() + 5
            while not gone and time.time() < deadline:
                time.sleep(0.01)
            assert gone == ["wC"]
        finally:
            lst.close()


class TestShardParamsReshard:
    def test_sharded_leaf_reassembles_on_any_topology(self, tmp_path):
        """The supervisor's checkpoint writes one params shard per
        worker; restore must stitch the global vector back whatever the
        survivor count — the elastic resume's resharded restore."""
        from deeplearning4j_tpu.checkpoint import format as ckfmt
        from deeplearning4j_tpu.checkpoint.restore import \
            load_payload_tree

        vec = np.arange(103, dtype=np.float32)
        leaf = TrainingSupervisor.shard_params(vec, 4)
        assert isinstance(leaf, ckfmt.HostLeaf)
        assert len(leaf.shards) == 4
        root = str(tmp_path / "ck")
        ckfmt.write_checkpoint(root, 7, {"params": leaf,
                                         "iterator_position": 7})
        payload, manifest = load_payload_tree(root, 7)
        np.testing.assert_array_equal(payload["params"], vec)
        assert len(manifest["leaves"]["params"]["shards"]) == 4

    def test_single_worker_and_tiny_vectors_stay_plain(self):
        vec = np.arange(5, dtype=np.float32)
        assert isinstance(TrainingSupervisor.shard_params(vec, 1),
                          np.ndarray)
        assert isinstance(TrainingSupervisor.shard_params(vec, 8),
                          np.ndarray)


class TestDiscoverLatest:
    def test_latest_committed_step_is_found(self, tmp_path):
        from deeplearning4j_tpu.checkpoint import format as ckfmt
        from deeplearning4j_tpu.checkpoint.restore import discover_latest

        root = str(tmp_path / "ck")
        ckfmt.write_checkpoint(root, 2, {"iterator_position": 2})
        ckfmt.write_checkpoint(root, 5, {"iterator_position": 5})
        assert discover_latest(root) == (root, 5)

    def test_torn_only_dir_error_lists_candidates(self, tmp_path):
        from deeplearning4j_tpu.checkpoint import format as ckfmt
        from deeplearning4j_tpu.checkpoint.restore import discover_latest

        root = str(tmp_path / "ck")
        torn = os.path.join(root, ckfmt.step_dir_name(9))
        os.makedirs(torn)
        with open(os.path.join(torn, ckfmt.MANIFEST), "w") as f:
            f.write("{}")
        with pytest.raises(ckfmt.CheckpointError) as exc:
            discover_latest(root)
        assert "step_0000000009" in str(exc.value)
        assert "torn" in str(exc.value)

    def test_empty_root_has_distinct_error(self, tmp_path):
        from deeplearning4j_tpu.checkpoint import format as ckfmt
        from deeplearning4j_tpu.checkpoint.restore import discover_latest

        with pytest.raises(ckfmt.CheckpointError, match="no sharded"):
            discover_latest(str(tmp_path / "empty"))


class TestStatusHealth:
    def test_healthz_flips_503_when_quorum_verdict_fails(self):
        from deeplearning4j_tpu.scaleout.statetracker import \
            InMemoryStateTracker
        from deeplearning4j_tpu.scaleout.status import StatusServer

        verdict = {"ok": True, "live_workers": 2, "min_workers": 2}
        server = StatusServer(InMemoryStateTracker(),
                              health=lambda: dict(verdict)).start()
        try:
            code, body = _get(server.address + "/healthz")
            assert code == 200 and json.loads(body)["live_workers"] == 2
            verdict["ok"] = False
            verdict["live_workers"] = 1
            try:
                code, body = _get(server.address + "/healthz")
            except urllib.error.HTTPError as e:
                code, body = e.code, e.read()
            assert code == 503
            assert json.loads(body)["live_workers"] == 1
        finally:
            server.stop()


# ------------------------------------------------------- process drills
class TestSupervisedRun:
    def test_trains_checkpoints_and_reports_lifecycle(self, tmp_path):
        """Happy path end to end: 2 worker processes, every batch folds
        exactly once, resharded checkpoints commit with the cursor, and
        the StatusServer surfaces worker lifecycle + quorum health."""
        from deeplearning4j_tpu.checkpoint import format as ckfmt

        jobs = _jobs(4)
        ckpt = str(tmp_path / "ckpt")
        sup = _supervisor(tmp_path, "happy", jobs, checkpoint_dir=ckpt,
                          status_port=0)
        status_url = sup.status_server.address
        seen = {}

        def poll():
            deadline = time.time() + 120
            while time.time() < deadline and not seen.get("done"):
                try:
                    _, body = _get(status_url + "/status.json",
                                   timeout=5)
                    s = json.loads(body)
                except (OSError, ValueError):
                    return
                extra = s.get("extra", {})
                for wid, rec in (extra.get("workers") or {}).items():
                    if rec.get("state") == "running":
                        seen[wid] = rec
                try:
                    code, _ = _get(status_url + "/healthz", timeout=5)
                    seen["healthz"] = code
                except (OSError, urllib.error.HTTPError):
                    pass
                time.sleep(0.05)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        final = sup.run(timeout=240.0)
        seen["done"] = True
        poller.join(timeout=10)
        assert final is not None and final.ndim == 1
        assert sorted(sup.folded_seqs) == list(range(len(jobs)))
        steps = ckfmt.list_steps(ckpt)
        assert steps and steps[-1] == len(jobs)
        manifest = ckfmt.read_manifest(ckpt, steps[-1])
        assert manifest["mesh"]["axes"]["workers"] >= 1
        assert seen.get("healthz") == 200
        lifecycle = [v for k, v in seen.items()
                     if k not in ("healthz", "done")]
        assert lifecycle, "status.json never showed a running worker"
        assert all("last_step" in rec and "generation" in rec
                   for rec in lifecycle)

    def test_spawn_crash_is_respawned_via_seeded_chaos(self, tmp_path):
        """A worker whose process dies at boot (seeded `worker.spawn`
        error, injected only into w1's env) is evicted and respawned;
        the run completes with every batch folded once."""
        jobs = _jobs(4)
        plan = chaos.env_spec([chaos.Rule("worker.spawn", "error")],
                              seed=7)

        def env_for(wid):
            return plan if wid == "w1" else {}

        sup = _supervisor(tmp_path, "spawncrash", jobs, env_for=env_for,
                          max_respawns=2, respawn_backoff_s=0.05)
        final = sup.run(timeout=240.0)
        assert final is not None
        assert sorted(sup.folded_seqs) == list(range(len(jobs)))
        assert sup.respawns_used >= 1
        evicted = [r for r in sup.members.values()
                   if r.state in (EVICTED, DEAD)]
        assert any((r.eviction_reason or "").startswith("spawn_failed")
                   for r in evicted)

    def test_hung_worker_caught_by_progress_watermark(self, tmp_path):
        """The seeded, replayable twin of the SIGSTOP drill: a chaos
        `hang` at worker.step (after one good job) freezes w1's train
        loop while its reporter thread keeps the socket warm — liveness
        holds, only the progress watermark can evict it. The eviction
        reason must say hung, and the wave must re-form."""
        jobs = _jobs(6)
        plan = chaos.env_spec(
            [chaos.Rule("worker.step", "hang", after=1)], seed=11)

        def env_for(wid):
            return plan if wid == "w1" else {}

        sup = _supervisor(tmp_path, "hangdrill", jobs, env_for=env_for,
                          max_respawns=1, respawn_backoff_s=0.05,
                          heartbeat_timeout=60.0,  # staleness CANNOT fire
                          progress_timeout=3.0, startup_grace=120.0)
        t0 = time.monotonic()
        final = sup.run(timeout=240.0)
        assert final is not None
        assert sorted(sup.folded_seqs) == list(range(len(jobs)))
        hung = [r for r in sup.members.values()
                if (r.eviction_reason or "").startswith("hung")]
        assert hung, {r.id: r.eviction_reason
                      for r in sup.members.values()}
        assert sup.respawns_used == 1
        # detection bounded: the whole run (including the hang window)
        # finishes well under the heartbeat timeout that could never
        # have caught it
        assert time.monotonic() - t0 < 200

    def test_capacity_lost_at_startup_shrinks_to_survivors(self,
                                                           tmp_path):
        """Respawn budget 0 + a worker that can never boot: capacity is
        durably lost before any checkpoint exists, so the run continues
        on the surviving topology with nothing dropped."""
        jobs = _jobs(4)
        plan = chaos.env_spec([chaos.Rule("worker.spawn", "error")],
                              seed=3)

        def env_for(wid):
            return plan if wid.startswith("w1") else {}

        sup = _supervisor(tmp_path, "shrink", jobs, env_for=env_for,
                          max_respawns=0)
        final = sup.run(timeout=240.0)
        assert final is not None
        assert sorted(sup.folded_seqs) == list(range(len(jobs)))
        assert sup.n_workers == 1
        assert sup.state_counts()[DEAD] == 1

    def test_straggler_flagged_evicted_and_respawned(self, tmp_path):
        """A seeded per-worker delay makes w1 persistently ~20x slower
        than the wave median: flagged, evicted as a straggler after the
        configured strikes, replaced — and the replacement (no delay
        plan under its new id) finishes the run."""
        jobs = _jobs(10)
        plan = chaos.env_spec(
            [chaos.Rule("worker.step", "delay", delay_s=1.2)], seed=5)

        def env_for(wid):
            return plan if wid == "w1" else {}

        sup = _supervisor(tmp_path, "straggler", jobs, env_for=env_for,
                          max_respawns=1, respawn_backoff_s=0.05,
                          straggler_factor=3.0,
                          straggler_min_samples=2, straggler_strikes=1)
        final = sup.run(timeout=240.0)
        assert final is not None
        assert sorted(sup.folded_seqs) == list(range(len(jobs)))
        straggled = [r for r in sup.members.values()
                     if (r.eviction_reason or "").startswith("straggler")]
        assert straggled and straggled[0].id == "w1"
        assert sup.respawns_used == 1
        assert int(sup._m_straggler.value) >= 1


# --------------------------------------------------- slow process soaks
@pytest.mark.slow
class TestKillDrills:
    def _reference(self, tmp_path, jobs):
        return _supervisor(tmp_path, "ref", jobs).run(timeout=240.0)

    def test_sigkill_respawn_is_bit_identical(self, tmp_path):
        """SIGKILL one of two workers mid-run: eviction -> respawn ->
        wave re-forms -> final params BIT-IDENTICAL to the
        uninterrupted run at the same wave schedule (the acceptance
        gate `bench.py train_elastic` also pins)."""
        jobs = _jobs(6)
        ref = self._reference(tmp_path, jobs)
        sup = _supervisor(tmp_path, "sigkill", jobs,
                          checkpoint_dir=str(tmp_path / "ck_kill"),
                          max_respawns=2, respawn_backoff_s=0.05,
                          heartbeat_timeout=2.0)
        killed = {}

        def killer():
            deadline = time.time() + 120
            while time.time() < deadline:
                for rec in list(sup.members.values()):
                    if (rec.performed >= 1 and rec.proc is not None
                            and rec.generation == 0):
                        chaos.sigkill(rec.proc)
                        killed["id"] = rec.id
                        return
                time.sleep(0.01)

        threading.Thread(target=killer, daemon=True).start()
        final = sup.run(timeout=240.0)
        assert killed, "fault was never injected"
        assert sup.respawns_used >= 1
        assert sorted(sup.folded_seqs) == list(range(len(jobs)))
        np.testing.assert_array_equal(ref, final)

    def test_capacity_loss_resumes_resharded_on_survivor(self, tmp_path):
        """SIGKILL with respawn budget 0: the supervisor flushes, then
        restarts the wave from the last COMMITTED checkpoint resharded
        2 -> 1 workers, with zero lost or double-trained examples
        (folded_seqs covers the stream exactly once)."""
        jobs = _jobs(6)
        sup = _supervisor(tmp_path, "caploss", jobs,
                          checkpoint_dir=str(tmp_path / "ck_lost"),
                          max_respawns=0, heartbeat_timeout=2.0)
        killed = {}

        def killer():
            deadline = time.time() + 120
            while time.time() < deadline:
                if sup.waves >= 1:
                    for rec in list(sup.members.values()):
                        if rec.performed >= 1 and rec.proc is not None:
                            chaos.sigkill(rec.proc)
                            killed["id"] = rec.id
                            return
                time.sleep(0.01)

        threading.Thread(target=killer, daemon=True).start()
        final = sup.run(timeout=240.0)
        assert killed and final is not None
        assert sorted(sup.folded_seqs) == list(range(len(jobs)))
        assert sup.resume_events, "elastic resume never happened"
        ev = sup.resume_events[-1]
        assert ev["resharded"] and ev["survivors"] == 1
        assert ev["recovery_s"] < 60

    def test_sigstop_detected_by_watermark_within_window(self, tmp_path):
        """The real-process SIGSTOP soak: a stopped worker still holds
        TCP (liveness never lapses — heartbeat_timeout is far beyond
        the run), and only the progress watermark evicts it, within
        the configured window."""
        jobs = _jobs(8)
        sup = _supervisor(tmp_path, "sigstop", jobs,
                          max_respawns=1, respawn_backoff_s=0.05,
                          heartbeat_timeout=60.0, progress_timeout=2.0)
        stopped = {}

        def stopper():
            deadline = time.time() + 120
            while time.time() < deadline:
                for rec in list(sup.members.values()):
                    if (rec.performed >= 1 and rec.proc is not None
                            and rec.generation == 0):
                        chaos.sigstop(rec.proc)
                        stopped["id"] = rec.id
                        stopped["t"] = time.monotonic()
                        return
                time.sleep(0.01)

        threading.Thread(target=stopper, daemon=True).start()
        final = sup.run(timeout=240.0)
        assert stopped, "fault was never injected"
        rec = sup.members[stopped["id"]]
        assert (rec.eviction_reason or "").startswith("hung"), \
            rec.eviction_reason
        detected_in = rec.evicted_at - stopped["t"]
        assert detected_in < 3 * sup.progress_timeout + 5.0
        assert final is not None
        assert sorted(sup.folded_seqs) == list(range(len(jobs)))
