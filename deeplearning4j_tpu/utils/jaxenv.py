"""Process-level JAX settings, applied by every entry point before JAX
starts a back end (`cli`, `scaleout.worker`, `bench.py`, the examples,
`chip_smoke.py`'s children; `tests/conftest.py` uses the cache half).

Imports nothing heavy: a control-plane process (fleet router, elastic
supervisor) calls `configure()` and still never touches a device.

Two rules live here and nowhere else:

- **Compile cache.** Where `JAX_COMPILATION_CACHE_DIR` is set the
  program uses it and sets no other directory. Where it is not, the
  cache is `<checkout>/.jax_program_cache` — a fixed path, because the
  path is part of the cache key — exported so spawned children share
  it. The test suite keeps its own `.jax_cache`: it compiles for eight
  virtual CPU devices, and a one-device program writing under the same
  keys poisons its exact-equality tests.
- **Platform.** With `JAX_PLATFORMS` unset JAX tries the TPU quietly and
  carries on from the CPU when it fails. Where libtpu is installed the
  machine is meant to have a chip, so the platform is pinned to
  `tpu,cpu`: a chip that is missing or held by another process is then
  a start-up error, not a slow run. Set `JAX_PLATFORMS=cpu` to run on
  the CPU on purpose.

This is JAX's own persistent cache. The project's `--compile-cache`
AOT program store (`compilecache/`) is a different thing.
"""

from __future__ import annotations

import importlib.util
import os
import sys

__all__ = ["CACHE_ENV", "PLATFORM_ENV", "PROGRAM_CACHE_DIRNAME",
           "configure", "place_compile_cache", "pin_platform", "wants_tpu",
           "keep_off_accelerator", "compile_cache_entries",
           "device_report", "chip_env"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
PLATFORM_ENV = "JAX_PLATFORMS"
PROGRAM_CACHE_DIRNAME = ".jax_program_cache"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache(default_dirname: str = PROGRAM_CACHE_DIRNAME) -> str:
    """Apply the compile-cache rule; returns the directory in use."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    path = os.path.join(_CHECKOUT, default_dirname)
    os.environ[CACHE_ENV] = path
    if "jax" in sys.modules:
        # jax read its environment at import: tell the live config too
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path


def pin_platform() -> None:
    """Apply the platform rule (exported, so children inherit it)."""
    if not os.environ.get(PLATFORM_ENV) \
            and importlib.util.find_spec("libtpu") is not None:
        os.environ[PLATFORM_ENV] = "tpu,cpu"
        if "jax" in sys.modules:
            sys.modules["jax"].config.update("jax_platforms", "tpu,cpu")


def wants_tpu() -> bool:
    """Whether this process's children will start on a TPU back end
    (read from the environment `pin_platform` settled — no JAX)."""
    return "tpu" in (os.environ.get(PLATFORM_ENV) or "").split(",")


def configure() -> None:
    """Both rules. Idempotent; call first thing in an entry point."""
    pin_platform()
    place_compile_cache()


def keep_off_accelerator() -> None:
    """For a control-plane process that still runs a little JAX (the
    elastic supervisor scores the final model): pin THIS process to
    the CPU back end. The environment is left alone, so the children
    it spawns still start on the platform `pin_platform` named."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def compile_cache_entries() -> int:
    """Files in the compile cache now (0 when the directory does not
    exist yet) — a start-vs-end difference of 0 on a non-empty cache is
    what `chip_smoke.py` reports as a warm run."""
    try:
        return len(os.listdir(os.environ.get(CACHE_ENV, "")))
    except OSError:
        return 0


def device_report() -> dict:
    """The device as JAX reports it. STARTS the back end: only a
    process that computes calls this, never a router or supervisor."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def chip_env(index: int) -> dict:
    """Environment that confines one child process to local TPU chip
    `index` (libtpu reads these at start-up), so a parent that itself
    stays off the accelerator can run one replica per chip."""
    return {"TPU_VISIBLE_CHIPS": str(int(index)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}
