"""AOT compile-or-load: wrap a `jax.jit` callable with a persistent
program cache.

`AotDispatch` is the dispatch layer of the warm-start subsystem
(docs/WARMUP.md). It fronts ONE jitted callable and, per distinct
argument signature (shapes + dtypes + static values + pytree
structure), either

- **loads** a serialized executable from the `ProgramStore`
  (`jax.experimental.serialize_executable.deserialize_and_load` —
  skips tracing AND XLA compilation, the whole cold-boot tax), or
- **compiles** via the AOT workflow `jit_fn.lower(*args).compile()`
  and writes the serialized executable back for the next process.

Calling conventions (probed against the in-tree jax):

- `lower()` takes the FULL argument list, static args included, and
  accepts `jax.ShapeDtypeStruct` placeholders for array arguments —
  which is how `warm()` precompiles a program set without executing
  anything (execution during warmup would donate buffers and mutate
  state like the decode loop's page pool).
- A `Compiled` (fresh or deserialized) is invoked WITHOUT the static
  args — they are baked into the program — so `__call__` strips the
  static positions before dispatching to a cached executable.
- A deserialized executable accepts plain host numpy arrays and
  commits them to the devices it was compiled for.

Every failure in the AOT path (store fault, deserialize rejection,
un-serializable executable, exotic argument) falls back PERMANENTLY
(per signature) to the wrapped jit — behavior identical to not having
a cache, never an error surfaced to the caller.

`_cache_size()` mirrors the private accounting attribute on jitted
callables so `utils.jitcache.jit_cache_size` — and every recompile
guard and program-count pin built on it — sees AOT-loaded programs
and traced programs as one number, with zero changes to callers.
"""

from __future__ import annotations

import hashlib
import logging
import pickle
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax

from deeplearning4j_tpu.compilecache.store import ProgramStore

__all__ = ["AotCompiler", "AotDispatch", "config_digest"]

log = logging.getLogger(__name__)


def config_digest(obj: Any) -> str:
    """Short stable digest of a config-ish object (dataclass, dict, or
    anything with a deterministic repr) for embedding in program keys.
    Two configs that produce different jitted programs at identical
    input shapes — different layer sizes, kernels, horizons — must
    land on different keys."""
    import dataclasses

    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        obj = dataclasses.asdict(obj)
    if isinstance(obj, dict):
        text = repr(sorted(obj.items()))
    else:
        text = repr(obj)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _sig_entries(args: Sequence[Any]) -> Tuple:
    """Hashable per-argument signature: (shape, dtype) for array-likes
    (jax arrays, numpy arrays, ShapeDtypeStructs), ("py", repr) for
    static python values. Pytree containers are flattened with their
    structure recorded, so two arg lists that flatten to the same
    leaves but different trees cannot share a program."""
    entries = []
    for a in args:
        leaves, treedef = jax.tree_util.tree_flatten(a)
        leaf_sigs = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is not None and dtype is not None:
                leaf_sigs.append((tuple(shape), str(dtype)))
            else:
                leaf_sigs.append(("py", repr(leaf)))
        entries.append((str(treedef), tuple(leaf_sigs)))
    return tuple(entries)


class AotCompiler:
    """Serialize/deserialize bridge between Compiled executables and a
    `ProgramStore`. Shared by every `AotDispatch` in the process."""

    def __init__(self, store: ProgramStore):
        self.store = store

    def load(self, key: str):
        """The stored executable for `key`, loaded, or None. A payload
        the runtime refuses to deserialize is quarantined so it cannot
        fail again next boot."""
        payload = self.store.get(key)
        if payload is None:
            return None
        try:
            from jax.experimental import serialize_executable

            triple = pickle.loads(payload)
            return serialize_executable.deserialize_and_load(*triple)
        except Exception as e:
            log.warning("compile cache: deserialize failed for %s "
                        "(%s: %s) — recompiling", key,
                        type(e).__name__, e)
            self.store.invalidate(key, reason="load_error")
            return None

    def save(self, key: str, compiled):
        """Serialize, VALIDATE, and commit one executable. Returns True
        (persisted), "invalid" (the payload fails to load back — see
        below), or False (unserializable / store write fault). Never
        raises.

        The validation load-back exists because jax's own persistent
        compilation cache (JAX_COMPILATION_CACHE_DIR) can hand
        `compile()` an executable whose serialized payload is missing
        its object code — it serializes fine and then fails
        `deserialize_and_load` with "Symbols not found". Persisting
        that would poison every warm boot; "invalid" tells the
        dispatcher to recompile once with that cache bypassed."""
        try:
            from jax.experimental import serialize_executable

            triple = serialize_executable.serialize(compiled)
            payload = pickle.dumps(triple)
        except Exception as e:
            log.warning("compile cache: serialize failed for %s "
                        "(%s: %s) — entry not persisted", key,
                        type(e).__name__, e)
            return False
        try:
            serialize_executable.deserialize_and_load(
                *pickle.loads(payload))
        except Exception as e:
            log.warning("compile cache: payload for %s fails to load "
                        "back (%s: %s) — executable likely served from "
                        "jax's own compilation cache; will recompile "
                        "uncached", key, type(e).__name__, e)
            return "invalid"
        return self.store.put(key, payload)


class AotDispatch:
    """Callable wrapper: persistent-cache AOT dispatch over one
    `jax.jit` function (see module docstring). Drop-in: same call
    signature, same outputs, donation/device semantics baked into the
    loaded executables."""

    def __init__(self, jit_fn, *, key: str, compiler: AotCompiler,
                 static_argnums: Sequence[int] = ()):
        self._jit = jit_fn
        self.key = key
        self._compiler = compiler
        self._static = tuple(static_argnums)
        self._programs: Dict[Tuple, Any] = {}   # sig -> Compiled
        self._fallback: set = set()             # sigs pinned to plain jit
        self._lock = threading.Lock()

    # ------------------------------------------------------------ keys
    def _store_key(self, sig: Tuple) -> str:
        digest = hashlib.sha256(repr(sig).encode()).hexdigest()[:24]
        return f"{self.key}:{digest}"

    def keys_for(self, *args) -> str:
        """The store key this argument list dispatches to (round-trip
        tests compare these across processes)."""
        return self._store_key(_sig_entries(args))

    # -------------------------------------------------------- dispatch
    def _obtain(self, sig: Tuple, args: Sequence[Any]):
        """Load-or-compile the program for `sig`; None pins the sig to
        the plain-jit fallback. Caller holds no lock; the store is
        process-safe (atomic rename) and double-compile is benign."""
        key = self._store_key(sig)
        compiled = self._compiler.load(key)
        if compiled is not None:
            self._compiler.store.record_hit()
            return compiled
        try:
            compiled = self._jit.lower(*args).compile()
        except Exception as e:
            log.warning("AOT lower/compile failed for %s (%s: %s) — "
                        "falling back to jit dispatch", key,
                        type(e).__name__, e)
            return None
        self._compiler.store.record_miss()
        if self._compiler.save(key, compiled) == "invalid":
            fresh = self._compile_uncached(args)
            if fresh is not None \
                    and self._compiler.save(key, fresh) is True:
                compiled = fresh
        return compiled

    def _compile_uncached(self, args: Sequence[Any]):
        """Recompile with jax's persistent compilation cache bypassed —
        the remedy for cache-served executables whose serialized
        payload is unloadable (see AotCompiler.save).

        Disabling the config flag alone is NOT enough, twice over:

        - jax memoizes the cache-is-used decision process-wide on the
          first compile (`compilation_cache.is_cache_used`), so the
          flag is never re-read. `reset_cache()` drops that memo;
          resetting inside the disabled context makes the re-check see
          "disabled", and resetting again afterwards re-arms the cache
          for every later compile in the process.
        - jax ALSO memoizes compiled executables in-memory
          (`pxla._cached_compilation`, a weakref LRU keyed by the
          lowered module) — without clearing it, `lower().compile()`
          hands back the very same defective executable and XLA is
          never invoked. Clearing costs recompiles for other live jits
          only if they re-trace, and this path runs at most once per
          poisoned program."""
        from jax._src import compilation_cache as jax_cc
        from jax._src.config import enable_compilation_cache
        from jax._src.interpreters import pxla

        try:
            with enable_compilation_cache(False):
                jax_cc.reset_cache()
                pxla._cached_compilation.cache_clear()
                try:
                    return self._jit.lower(*args).compile()
                finally:
                    jax_cc.reset_cache()
        except Exception as e:
            log.warning("AOT uncached recompile failed for %s "
                        "(%s: %s) — keeping the in-process program; "
                        "entry not persisted", self.key,
                        type(e).__name__, e)
            return None

    def __call__(self, *args):
        sig = _sig_entries(args)
        with self._lock:
            compiled = self._programs.get(sig)
            fallback = sig in self._fallback
        if compiled is None and not fallback:
            compiled = self._obtain(sig, args)
            with self._lock:
                if compiled is None:
                    self._fallback.add(sig)
                else:
                    self._programs.setdefault(sig, compiled)
        if compiled is None:
            return self._jit(*args)
        call_args = [a for i, a in enumerate(args)
                     if i not in self._static]
        try:
            return compiled(*call_args)
        except Exception as e:
            # a loaded program that won't execute (layout drift, device
            # mismatch) must not poison serving: pin to plain jit
            log.warning("AOT executable for %s failed at call time "
                        "(%s: %s) — pinned to jit fallback", self.key,
                        type(e).__name__, e)
            with self._lock:
                self._programs.pop(sig, None)
                self._fallback.add(sig)
            return self._jit(*args)

    # ---------------------------------------------------------- warmup
    def warm(self, *args) -> bool:
        """Load-or-compile the program for this argument signature
        WITHOUT executing it. Arguments may be (and for donating
        programs must be) `jax.ShapeDtypeStruct` placeholders; static
        args are passed as real values. Returns True if the program is
        resident afterwards."""
        sig = _sig_entries(args)
        with self._lock:
            if sig in self._programs:
                return True
            if sig in self._fallback:
                return False
        compiled = self._obtain(sig, args)
        with self._lock:
            if compiled is None:
                self._fallback.add(sig)
                return False
            self._programs.setdefault(sig, compiled)
        return True

    # ------------------------------------------------------ accounting
    def _cache_size(self) -> int:
        """Resident program count: AOT-held executables plus anything
        the fallback jit traced. `utils.jitcache.jit_cache_size` calls
        this, which keeps every recompile pin in the tree working
        unchanged on wrapped callables."""
        inner = 0
        try:
            inner = int(self._jit._cache_size())
        except Exception:
            pass
        with self._lock:
            return len(self._programs) + inner

    def aot_programs(self) -> int:
        with self._lock:
            return len(self._programs)

    def store_keys(self) -> set:
        """Store keys of the programs this dispatcher has resident."""
        with self._lock:
            sigs = list(self._programs)
        return {self._store_key(s) for s in sigs}

    # jit-attribute passthrough (e.g. .lower for diagnostics)
    def __getattr__(self, name):
        return getattr(self._jit, name)
