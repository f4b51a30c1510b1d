"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's distributed-without-a-cluster test strategy (SURVEY §4:
embedded Hazelcast tracker / IRUnit in-process cluster) — multi-chip sharding
logic runs in one process against fake devices. Must set flags BEFORE jax
imports anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = os.environ.get("DL4J_TPU_TEST_PLATFORM", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# Persistent XLA compile cache: compiles dominate suite wall time (a DBN
# example: 68 s cold vs 17 s cached). The suite's default is its own
# `.jax_cache`, NOT the program's `.jax_program_cache`: it compiles for
# eight virtual devices, and one-device programs under the same keys
# break its exact-equality tests. Exporting the variable also hands the
# cache to every subprocess the suite launches.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

from deeplearning4j_tpu.utils import jaxenv  # noqa: E402

jaxenv.place_compile_cache(".jax_cache")

import jax  # noqa: E402

# a pytest plugin may have imported jax before this file set the
# environment: tell the live config too
jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: opt-in real-chip lane — runs only under "
        "DL4J_TPU_TEST_PLATFORM=tpu pytest -m tpu (README 'Testing')")
    config.addinivalue_line(
        "markers",
        "slow: long soak/drill tests excluded from tier-1 (which runs "
        "-m 'not slow'); run explicitly with pytest -m slow")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection drills (deeplearning4j_tpu.testing."
        "chaos); the fast deterministic subset runs in tier-1, the "
        "randomized soak and real-process SIGSTOP drills also carry "
        "@slow — run the whole layer with pytest -m chaos")
    config.addinivalue_line(
        "markers",
        "elastic: self-healing elastic-training drills (scaleout."
        "supervisor); fast seeded-chaos drills run in tier-1, the "
        "SIGKILL/SIGSTOP process soaks also carry @slow — run the "
        "whole layer with pytest -m elastic")
    config.addinivalue_line(
        "markers",
        "pallas: Pallas kernel lane (flash + paged decode); tier-1 "
        "runs these through the interpreter on CPU, the same kernel "
        "code compiles on TPU — run just this layer with "
        "pytest -m pallas")
    config.addinivalue_line(
        "markers",
        "pipeline: train->serve deployment-controller drills "
        "(deploy/controller.py conveyor: watch -> eval gate -> canary "
        "promote -> rollback); the in-process drills run in tier-1 — "
        "run the whole layer with pytest -m pipeline")
    config.addinivalue_line(
        "markers",
        "spec: speculative-decoding lane (serving/speculation.py + the "
        "DecodeLoop draft-and-verify dispatch); deterministic drills "
        "run in tier-1 — run just this layer with pytest -m spec")
    config.addinivalue_line(
        "markers",
        "slo: SLO-tier lane (priority classes, weighted-fair batch "
        "share, lossless preemption — docs/SERVING.md \"Priority "
        "tiers\"); the in-process drills run in tier-1, the "
        "SIGKILL-mid-preemption process drill also carries @slow — "
        "run the whole layer with pytest -m slo")
    config.addinivalue_line(
        "markers",
        "aot: AOT warm-start lane (compilecache: persistent program "
        "store, warmup plans, chaos-faulted cache drills — "
        "docs/WARMUP.md); the in-process drills run in tier-1, the "
        "fresh-subprocess replay drill also carries @slow — run the "
        "whole layer with pytest -m aot")
    config.addinivalue_line(
        "markers",
        "fleetkv: fleet KV plane lane (serving/fleetkv.py: prefix-"
        "affinity routing + peer-to-peer page shipping — docs/FLEET.md "
        "\"Fleet KV plane\"); the in-process drills run in tier-1 — "
        "run the whole layer with pytest -m fleetkv")
    config.addinivalue_line(
        "markers",
        "disagg: disaggregated prefill/decode + multi-model routing "
        "lane (replica roles, /prefill handoff, per-model fleet "
        "registry — docs/FLEET.md \"Disaggregated roles\"); the "
        "in-process drills run in tier-1, the SIGKILL-mid-handoff "
        "process drill also carries @slow — run the whole layer with "
        "pytest -m disagg")


def pytest_collection_modifyitems(config, items):
    on_real_chip = os.environ.get("DL4J_TPU_TEST_PLATFORM", "cpu") != "cpu"
    skip_tpu = pytest.mark.skip(
        reason="real-chip lane: set DL4J_TPU_TEST_PLATFORM=tpu")
    skip_cpu_only = pytest.mark.skip(
        reason="CPU-tier test skipped on the real-chip lane (run the "
        "default suite for these)")
    for item in items:
        if "tpu" in item.keywords:
            if not on_real_chip:
                item.add_marker(skip_tpu)
        elif on_real_chip:
            # the real-chip lane runs ONLY @tpu tests: the CPU tiers pin
            # jax to cpu per-process state these tests would fight
            item.add_marker(skip_cpu_only)


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)


@pytest.fixture(autouse=True)
def no_tracer_left_behind():
    """Tracing is process-wide state: whichever test starts it (a
    `start_tracing()`, a `cli ... --trace` run in this process), the
    next one begins without it."""
    from deeplearning4j_tpu import telemetry

    telemetry.stop_tracing()
    yield
    telemetry.stop_tracing()
