"""Per-device gauges: accelerator memory stats + recompile counters.

HBM pressure and program-cache growth are the two signals GSPMD-era
tuning decisions hang off (arXiv:2004.13336 treats per-step memory /
communication telemetry as optimization input, not log output); this
module makes both one scrape away:

- `dl4j_device_memory_bytes{device=...,stat=...}` — sampled from
  `jax.local_devices()[i].memory_stats()` at scrape time via gauge
  callables (no background thread; backends without memory stats —
  the CPU test mesh — simply render 0).
- `dl4j_jit_programs{cache=...}` — the existing
  `utils/jitcache.jit_cache_size`-backed recompile counters
  (`MultiLayerNetwork.train_step_cache_size` /
  `predict_step_cache_size`, `InferenceEngine.program_cache_size`)
  aggregated per cache label over every live owner. Owners register via
  `watch_jit_cache`; bound-method probes are held through weakrefs so
  watching never extends a network's or engine's lifetime. A probe
  returning -1 (jax private API drift) makes the whole label read -1 —
  "counter unavailable", never a fake 0.

`install()` is idempotent and cheap; `exposition.metrics_payload` calls
it so a /metrics mount in a process that computes gets device series
without extra wiring. It starts the JAX back end if nothing has yet, so
control-plane processes mount /metrics with `device_gauges=False`.
"""

from __future__ import annotations

import threading
import weakref
from typing import Callable, Dict, List, Optional

from deeplearning4j_tpu.telemetry.registry import (MetricsRegistry,
                                                   get_registry)

__all__ = ["install", "watch_jit_cache", "jit_cache_total"]

_MEM_STATS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")

_lock = threading.Lock()
_watches: Dict[str, List] = {}
_installed_on: "weakref.WeakSet" = weakref.WeakSet()


def _probe_ref(probe: Callable[[], int]):
    """Weakly reference a bound-method probe (the common case: a
    network's / engine's cache-size method); plain callables are held
    strongly — callers own their lifetime."""
    if hasattr(probe, "__self__"):
        return weakref.WeakMethod(probe)
    return lambda: probe


def watch_jit_cache(label: str, probe: Callable[[], int],
                    registry: Optional[MetricsRegistry] = None) -> None:
    """Aggregate `probe()` (a jit_cache_size-style compiled-program
    counter) into the `dl4j_jit_programs{cache=label}` gauge. Many
    owners may share one label (every MultiLayerNetwork watches
    "train_step"); dead owners fall out via their weakrefs."""
    reg = registry if registry is not None else get_registry()
    with _lock:
        refs = _watches.setdefault(label, [])
        refs.append(_probe_ref(probe))
        if len(refs) > 64:  # prune dead owners opportunistically
            refs[:] = [r for r in refs if r() is not None]
    reg.gauge(
        "dl4j_jit_programs",
        "compiled XLA programs per jitted-function cache (-1: counter "
        "unavailable)",
    ).labels(cache=label).set_function(lambda: jit_cache_total(label))


def jit_cache_total(label: str) -> int:
    """Sum of live probes under `label`; -1 if any live probe reports
    the private jax counter API drifted."""
    with _lock:
        refs = list(_watches.get(label, ()))
    total = 0
    for ref in refs:
        probe = ref()
        if probe is None:
            continue
        try:
            size = int(probe())
        except Exception:
            continue
        if size < 0:
            return -1
        total += size
    return total


def install(registry: Optional[MetricsRegistry] = None) -> None:
    """Register the device gauges on `registry` (default: the global).
    Idempotent per registry; gauge callables sample live at scrape."""
    reg = registry if registry is not None else get_registry()
    if reg in _installed_on:
        return
    import jax

    # a back end that refuses to start is the scrape's error to report,
    # not something to render as "no devices"
    devices = jax.local_devices()
    _installed_on.add(reg)

    reg.gauge("dl4j_device_count",
              "local accelerator devices").set(len(devices))
    mem = reg.gauge(
        "dl4j_device_memory_bytes",
        "per-device memory stats sampled from jax memory_stats()")
    for d in devices:
        for stat in _MEM_STATS:
            def sample(_d=d, _s=stat) -> float:
                try:
                    stats = _d.memory_stats()
                except Exception:
                    stats = None
                return float((stats or {}).get(_s, 0))

            mem.labels(device=str(d), stat=stat).set_function(sample)
