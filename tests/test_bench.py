"""bench.py harness logic tests (no TPU, fake configs): the driver's
perf record depends on this machinery — protocol migration, per-platform
pinning, budget skipping, streaming summary lines, error isolation."""

import json

import pytest

import bench


@pytest.fixture
def hist_path(tmp_path, monkeypatch):
    path = tmp_path / "BENCH_HISTORY.json"
    monkeypatch.setattr(bench, "HIST_PATH", str(path))
    return path


def run_main(monkeypatch, configs, env=None, platform="tpu"):
    """Run bench.main() with fake configs; returns printed JSON lines."""
    monkeypatch.setattr(bench, "CONFIGS", configs)
    for k in ("BENCH_CONFIGS", "BENCH_BUDGET_S"):
        monkeypatch.delenv(k, raising=False)
    for k, v in (env or {}).items():
        monkeypatch.setenv(k, v)

    class FakeDevice:
        def __init__(self, platform):
            self.platform = platform

    import jax
    monkeypatch.setattr(jax, "devices", lambda: [FakeDevice(platform)])
    lines = []
    monkeypatch.setattr("builtins.print",
                        lambda s, **kw: lines.append(str(s)))
    run_main.exit_code = bench.main()
    return [json.loads(ln) for ln in lines]


class TestHistory:
    def test_protocol_migration_archives_old_pins(self, hist_path):
        hist_path.write_text(json.dumps(
            {"baselines": {"mlp": 123.0}, "runs": [{"ts": 1}]}))
        hist = bench._load_history()
        assert hist["protocol"] == bench.PROTOCOL
        assert hist["baselines"] == {}
        assert hist["baselines_v1"] == {"mlp": 123.0}
        assert hist["runs"] == [{"ts": 1}]

    def test_flat_pins_migrate_to_platform_scoping(self, hist_path):
        hist_path.write_text(json.dumps(
            {"protocol": bench.PROTOCOL,
             "baselines": {"mlp": 5505.0}, "runs": []}))
        assert bench._load_history()["baselines"] == {}

    def test_corrupt_history_starts_fresh(self, hist_path):
        hist_path.write_text("{not json")
        hist = bench._load_history()
        assert hist["baselines"] == {} and hist["runs"] == []


class TestMain:
    def test_pins_are_per_platform(self, hist_path, monkeypatch):
        cfg = {"mlp": lambda: {"value": 100.0, "unit": "u"}}
        cpu_lines = run_main(monkeypatch, cfg, platform="cpu")
        lines = run_main(monkeypatch, cfg, platform="tpu")
        hist = json.loads(hist_path.read_text())
        assert hist["baselines"]["cpu"]["mlp"] == 100.0
        assert hist["baselines"]["tpu"]["mlp"] == 100.0
        assert lines[-1]["vs_baseline"] == 1.0
        # a run that did not measure the device says so FIRST, on every
        # line; a TPU run carries no such key
        assert all(next(iter(ln)) == "not_a_device_measurement"
                   and "platform=cpu" in ln["not_a_device_measurement"]
                   for ln in cpu_lines)
        assert all("not_a_device_measurement" not in ln for ln in lines)
        assert run_main.exit_code == 0

    def test_vs_baseline_lower_is_better(self, hist_path, monkeypatch):
        vals = iter([2.0, 1.0])
        cfg = {"mlp": lambda: {"value": next(vals), "unit": "ms",
                               "lower_is_better": True}}
        run_main(monkeypatch, cfg)
        lines = run_main(monkeypatch, cfg)
        assert lines[-1]["vs_baseline"] == 2.0  # halved time = 2x better

    def test_streaming_cumulative_lines(self, hist_path, monkeypatch):
        cfg = {"mlp": lambda: {"value": 1.0, "unit": "u"},
               "extra1": lambda: {"value": 2.0, "unit": "u"}}
        lines = run_main(monkeypatch, cfg)
        assert len(lines) == 2
        assert lines[0]["extra"] == {}
        assert lines[1]["extra"]["extra1"]["value"] == 2.0
        # every line is a full, parseable summary (driver reads the last)
        assert all("metric" in ln and "protocol" in ln for ln in lines)

    def test_error_isolated_and_null_vs_baseline(self, hist_path,
                                                 monkeypatch):
        def boom():
            raise RuntimeError("kaput")

        cfg = {"mlp": boom, "ok": lambda: {"value": 3.0, "unit": "u"}}
        lines = run_main(monkeypatch, cfg)
        last = lines[-1]
        assert last["value"] is None
        assert last["vs_baseline"] is None  # never 1.0 for a missing run
        assert "kaput" in json.dumps(last["extra"]) or "kaput" in str(last)
        assert last["extra"]["ok"]["value"] == 3.0
        assert run_main.exit_code == 1  # an errored config fails the run

    def test_child_spawning_configs_cannot_run_under_a_held_chip(
            self, hist_path, monkeypatch):
        """bench.py touches JAX, so on a TPU it holds the chip its
        `cli serve` children would need: those configs are left out of
        the default selection there and error when named."""
        ran = []
        cfg = {"mlp": lambda: {"value": 1.0, "unit": "u"},
               "fleet": lambda: ran.append("fleet") or {"value": 2.0}}
        lines = run_main(monkeypatch, cfg, platform="tpu")
        assert "fleet" not in lines[-1]["extra"] and not ran
        assert run_main.exit_code == 0
        lines = run_main(monkeypatch, cfg, platform="tpu",
                         env={"BENCH_CONFIGS": "mlp,fleet"})
        assert "holds" in lines[-1]["extra"]["fleet"]["error"] and not ran
        assert run_main.exit_code == 1
        run_main(monkeypatch, cfg, platform="cpu")
        assert ran == ["fleet"]

    def test_budget_skips_not_yet_started(self, hist_path, monkeypatch):
        cfg = {"mlp": lambda: {"value": 1.0, "unit": "u"},
               "late": lambda: {"value": 2.0, "unit": "u"}}
        lines = run_main(monkeypatch, cfg, env={"BENCH_BUDGET_S": "0"})
        assert lines[-1]["value"] == 1.0  # first config always runs
        assert "skipped" in lines[-1]["extra"]["late"]
        hist = json.loads(hist_path.read_text())
        assert "late" not in hist["baselines"].get("tpu", {})

    def test_history_written_incrementally(self, hist_path, monkeypatch):
        seen = []

        def snapshooter():
            seen.append(json.loads(hist_path.read_text())
                        if hist_path.exists() else None)
            return {"value": 1.0, "unit": "u"}

        cfg = {"mlp": lambda: {"value": 9.0, "unit": "u"},
               "second": snapshooter}
        run_main(monkeypatch, cfg)
        # by the time the second config runs, the first is on disk
        assert seen[0] is not None
        assert seen[0]["runs"][-1]["results"]["mlp"]["value"] == 9.0
