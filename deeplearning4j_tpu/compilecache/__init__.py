"""AOT warm-start: persistent program cache + warmup plans.

Every machine that spawns a serving or training process used to pay
full jit compilation before doing useful work — the autoscaler, router
capacity repair, canary promotion, SLO scale-up, and elastic respawn
all brought up replicas that compiled their whole program set (bucket
ladder, decode step, prefill-ctx pairs, verify widths, draft scan)
before `/readyz` flipped. This package makes the program set a
persisted artifact instead:

- `store`   — fingerprinted, crash-atomic, LRU-bounded on-disk store of
              serialized XLA executables;
- `aot`     — `AotDispatch`, the jit wrapper that loads-or-compiles
              per argument signature through the store;
- `warmup`  — JSON warmup plans: record the program set one replica
              compiled, replay it on the next boot via
              `lower().compile()` / deserialize, no execution needed.

Process activation model: ONE optional process-global compiler. When
inactive (the default — no env var, no `activate()` call) every hook
in the tree (`maybe_wrap`) is an identity function and nothing about
compilation changes. Activation happens explicitly (`cli serve
--compile-cache DIR`, `serve_network(compile_cache=...)`) or lazily
from the environment: spawners stamp `DL4J_TPU_COMPILE_CACHE` into
child environments (`export_env`), so fleet members, pipeline
replicas, and elastic workers inherit the cache with no per-call-site
plumbing. Runbook and tuning: docs/WARMUP.md.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

from deeplearning4j_tpu.compilecache.aot import (  # noqa: F401
    AotCompiler,
    AotDispatch,
    config_digest,
)
from deeplearning4j_tpu.compilecache.store import (  # noqa: F401
    ProgramStore,
    key_digest,
    runtime_fingerprint,
)

__all__ = [
    "CACHE_ENV",
    "ProgramStore", "AotCompiler", "AotDispatch",
    "config_digest", "key_digest", "runtime_fingerprint",
    "activate", "deactivate", "active_compiler", "active_dir",
    "maybe_wrap", "export_dir", "export_env",
    "default_dir_for_checkpoints", "stats",
]

log = logging.getLogger(__name__)

#: child processes find their cache dir here (spawners set it; see
#: `export_env`)
CACHE_ENV = "DL4J_TPU_COMPILE_CACHE"

_lock = threading.Lock()
_compiler: Optional[AotCompiler] = None
_env_checked = False


def activate(root: str, *, size_budget_bytes: Optional[int] = None,
             fingerprint: Optional[str] = None) -> AotCompiler:
    """Open (or switch to) the persistent cache at `root` for this
    process and export it to future children via the environment.
    Idempotent for the same root."""
    global _compiler, _env_checked
    root = os.path.abspath(root)
    with _lock:
        if _compiler is not None and _compiler.store.root == root:
            return _compiler
        _compiler = AotCompiler(ProgramStore(
            root, size_budget_bytes=size_budget_bytes,
            fingerprint=fingerprint))
        _env_checked = True
        os.environ[CACHE_ENV] = root
        log.info("compile cache active at %s (fingerprint %s)",
                 root, _compiler.store.fingerprint)
        return _compiler


def deactivate() -> None:
    """Drop the process-global compiler and the env export. Callables
    already wrapped keep their loaded programs; new `maybe_wrap` calls
    become identity again. (Primarily for tests.)"""
    global _compiler, _env_checked
    with _lock:
        _compiler = None
        _env_checked = True
        os.environ.pop(CACHE_ENV, None)


def active_compiler() -> Optional[AotCompiler]:
    """The process compiler, auto-activating once from
    `DL4J_TPU_COMPILE_CACHE` — how spawned children pick up the cache
    their parent exported without any code path knowing about it."""
    global _compiler, _env_checked
    with _lock:
        if _compiler is None and not _env_checked:
            _env_checked = True
            root = os.environ.get(CACHE_ENV)
            if root:
                try:
                    _compiler = AotCompiler(ProgramStore(root))
                    log.info("compile cache activated from env: %s",
                             root)
                except Exception as e:
                    log.warning("compile cache env activation failed "
                                "(%s: %s) — running uncached",
                                type(e).__name__, e)
        return _compiler


def active_dir() -> Optional[str]:
    comp = active_compiler()
    return comp.store.root if comp is not None else None


def maybe_wrap(jit_fn, key: Optional[str], *,
               static_argnums=()):
    """The one hook call sites use: wrap `jit_fn` in an `AotDispatch`
    when a cache is active and a key is given, else return it
    untouched. Call sites therefore carry zero cache logic and zero
    behavior change when the subsystem is off."""
    if key is None:
        return jit_fn
    comp = active_compiler()
    if comp is None:
        return jit_fn
    return AotDispatch(jit_fn, key=key, compiler=comp,
                       static_argnums=static_argnums)


def export_dir(root: str) -> str:
    """Hand the cache at `root` to FUTURE CHILDREN through the
    environment without opening it here. Opening a store fingerprints
    the device (`runtime_fingerprint` -> `jax.devices()`), and a
    control-plane process — fleet router, elastic supervisor — must
    stay off the accelerator its children need; they activate lazily
    from the variable and take the fingerprint themselves."""
    root = os.path.abspath(root)
    os.environ[CACHE_ENV] = root
    return root


def export_env(env: dict) -> dict:
    """Stamp this process's cache dir into a child-process environment
    (spawners call this; no-op when there is none or the caller set
    one). Never opens the store — see `export_dir`. Returns `env` for
    chaining."""
    with _lock:
        root = (_compiler.store.root if _compiler is not None
                else os.environ.get(CACHE_ENV))
    if root and CACHE_ENV not in env:
        env[CACHE_ENV] = root
    return env


def default_dir_for_checkpoints(checkpoint_dir: str) -> str:
    """`--compile-cache auto`: co-locate the program cache with the
    checkpoint dir, so whatever ships/mounts checkpoints ships warm
    programs too."""
    return os.path.join(os.path.abspath(checkpoint_dir),
                        "compile_cache")


def stats() -> Optional[dict]:
    """The active store's stats dict (the /stats "compile_cache"
    section), or None when inactive."""
    comp = active_compiler()
    return comp.store.stats() if comp is not None else None
