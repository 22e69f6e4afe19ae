"""ExecutableRegistry — the shared in-memory executable cache in front of the
on-disk :class:`~hydragnn_tpu.cache.store.ExecutableStore`
(docs/COMPILE_CACHE.md).

One registry instance replaces both the serve engine's ``_executables`` dict
and the trainer's per-program compiled-step dispatch: every consumer goes
through the SAME locked lookup → (compile outside the lock) → store path:

1. locked in-memory get — the steady-state hit, one lock acquisition;
2. on miss, OUTSIDE the lock (a 10–50 s lowering must never block a
   concurrent submit or /healthz read): disk hydrate when a store is bound
   (verified read + deserialize — fires NO XLA compile event, so
   ``no_recompile()`` and the ``jax/compiles`` telemetry stay truthful),
   else ``lower().compile()`` fresh, then serialize+install into the store;
3. locked publish into the in-memory map — a racing duplicate compile is a
   benign last-wins overwrite of an equivalent executable.

Outcomes are counted into the graftel registry under ``cache/*``
(``cache/hit``, ``cache/hydrate``, ``cache/miss``, ``cache/hydrate_s``,
``cache/store_s``, ``cache/compile_s``) so every consumer's cache behavior
is visible on one surface (/metrics, train_metrics.prom, flight dumps).
"""

from __future__ import annotations

import threading
import time
import warnings
from typing import Any, Callable, Dict, Hashable, Optional, Tuple

from ..analysis import tsan
from ..telemetry import graftel as telemetry
from .store import (
    CacheEntryError,
    CacheKey,
    ExecutableStore,
    deserialize_compiled,
    hydratable,
    serialize_compiled,
)

# lookup_or_compile outcomes.
OUTCOME_MEMORY = "memory"
OUTCOME_DISK = "disk"
OUTCOME_COMPILED = "compiled"


class ExecutableRegistry:
    """Locked in-memory executable map + optional persistent store.

    ``mem_key`` (any hashable — the serve engine uses the padded bucket
    tuple, the trainer a (program, shape-signature) pair) addresses the
    in-memory map; the full :class:`CacheKey` addresses the disk store and
    is only consulted on an in-memory miss, so hit paths never pay
    fingerprint arithmetic."""

    def __init__(
        self, store: Optional[ExecutableStore] = None, name: str = "registry"
    ):
        self.name = name
        self._store = store
        self._lock = tsan.instrument_lock(
            threading.Lock(), f"ExecutableRegistry._lock[{name}]"
        )
        # program-keyed executables: written by warmup callers (main), the
        # serve dispatch thread, and restart paths.
        self._mem: Dict[Hashable, Any] = {}  # guarded-by: self._lock
        # One-time diagnostics (serialization unavailable on this backend).
        self._serialize_unavailable = False  # guarded-by: self._lock, dirty-reads(monotonic bool; a stale False retries serialization once more, which is harmless)

    # ------------------------------------------------------------- inspection
    @property
    def store(self) -> Optional[ExecutableStore]:
        return self._store

    def __len__(self) -> int:
        with self._lock:
            return len(self._mem)

    def get(self, mem_key: Hashable) -> Optional[Any]:
        with self._lock:
            return self._mem.get(mem_key)

    # ------------------------------------------------------------ the one path
    def lookup_or_compile(
        self,
        mem_key: Hashable,
        key: "Optional[CacheKey | Callable[[], Optional[CacheKey]]]",
        lower: Callable[[], Any],
    ) -> Tuple[Any, str, float]:
        """THE lookup path: returns ``(executable, outcome, seconds)`` where
        outcome is ``"memory"`` | ``"disk"`` | ``"compiled"`` and seconds is
        the hydrate or compile wall (0.0 for memory hits). ``lower`` returns
        a ``jax.stages.Lowered`` (called only on a full miss). ``key`` may be
        a zero-arg callable producing the :class:`CacheKey` — it is invoked
        only on an in-memory miss, so hot hit paths never pay fingerprint
        arithmetic."""
        with self._lock:
            exe = self._mem.get(mem_key)
        if exe is not None:
            telemetry.counter("cache/hit")
            return exe, OUTCOME_MEMORY, 0.0

        if callable(key):
            key = key()
        if key is not None and not hydratable(key.devices):
            key = None  # compiled every time, never stored (store.hydratable)
        outcome = OUTCOME_COMPILED
        seconds = 0.0
        exe = None
        if self._store is not None and key is not None:
            t0 = time.perf_counter()
            exe = self._hydrate(key)
            if exe is not None:
                seconds = time.perf_counter() - t0
                outcome = OUTCOME_DISK
                telemetry.counter("cache/hydrate")
                telemetry.counter("cache/hydrate_s", seconds)
        if exe is None:
            t0 = time.perf_counter()
            lowered = lower()
            compiled = lowered.compile()
            seconds = time.perf_counter() - t0
            telemetry.counter("cache/miss")
            telemetry.counter("cache/compile_s", seconds)
            if self._store is not None and key is not None:
                self._persist(key, compiled, lowered)
            exe = compiled

        with self._lock:
            # Racing duplicate (two threads missed the same key): last wins;
            # both executables are equivalent programs, so either is correct.
            self._mem[mem_key] = exe
        return exe, outcome, seconds

    def put(self, mem_key: Hashable, exe: Any) -> None:
        """Direct in-memory install (tests, pre-hydrated executables)."""
        with self._lock:
            self._mem[mem_key] = exe

    # ------------------------------------------------------------- disk halves
    def _hydrate(self, key: CacheKey) -> Optional[Any]:
        """Verified store read + deserialize, or None (miss / corrupt entry /
        StableHLO-only entry). Never raises: every failure class here must
        degrade to a fresh compile."""
        assert self._store is not None
        got = self._store.get(key)
        if got is None:
            return None
        sections, exe_format = got
        if exe_format != "pjrt":
            # StableHLO-only entry: JAX's own persistent cache (placed by
            # cache/jaxcache.py at the entry point) absorbs the compile wall;
            # the entry itself exists for diagnostics and ls/verify. Treat
            # as a miss here.
            return None
        try:
            return deserialize_compiled(sections, key.devices)
        except CacheEntryError as e:
            # Verified bytes that still fail to load (jax minor drift inside
            # an identical version string, foreign-arch payload): quarantine
            # exactly like corruption — loud, then fresh compile.
            self._store._quarantine(self._store.entry_path(key), key, str(e))
            return None

    def _persist(self, key: CacheKey, compiled: Any, lowered: Any = None) -> None:
        """Serialize + install one freshly compiled executable; on backends
        without executable serialization, persist the StableHLO lowering
        only — JAX's own persistent cache stays wherever the entry point put
        it (cache/jaxcache.py; never re-pointed under the store). Store
        failures are warnings — a full disk must not fail the train/serve
        path."""
        assert self._store is not None
        # The key's devices are the caller's word; the executable knows. An
        # entry under a key that names other devices would be loaded there.
        import jax

        local = {d.id for d in jax.local_devices()}
        built_for = tuple(
            d.id for d in compiled.runtime_executable().local_devices()
        )
        if built_for != tuple(i for i in key.devices if i in local):
            warnings.warn(
                f"graftcache[{self.name}]: {key.program} was compiled for "
                f"devices {list(built_for)} but keyed for "
                f"{list(key.devices)}; not stored",
                RuntimeWarning,
                stacklevel=3,
            )
            return
        t0 = time.perf_counter()
        try:
            sections = serialize_compiled(compiled)
            if sections is not None:
                self._store.put(key, sections, exe_format="pjrt")
            else:
                with self._lock:
                    first = not self._serialize_unavailable
                    self._serialize_unavailable = True
                if first:
                    warnings.warn(
                        f"graftcache[{self.name}]: backend "
                        f"{key.backend!r} cannot serialize executables; "
                        "persisting StableHLO only — warm starts rely on "
                        "JAX's own persistent compilation cache",
                        RuntimeWarning,
                        stacklevel=3,
                    )
                hlo = _lowering_text(lowered if lowered is not None else compiled)
                if hlo is not None:
                    self._store.put(
                        key,
                        {"stablehlo": hlo.encode()},
                        exe_format="stablehlo",
                    )
        except OSError as e:
            warnings.warn(
                f"graftcache[{self.name}]: store write failed ({e}); "
                "continuing without persistence",
                RuntimeWarning,
                stacklevel=3,
            )
            return
        telemetry.counter("cache/store")
        telemetry.counter("cache/store_s", time.perf_counter() - t0)


def _lowering_text(stage: Any) -> Optional[str]:
    """Best-effort StableHLO/HLO text of a Lowered (preferred) or Compiled
    stage — the fallback entry's payload."""
    try:
        return stage.as_text()
    except Exception:  # noqa: BLE001 — diagnostics-only payload
        return None
