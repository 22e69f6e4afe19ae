"""On-disk AOT executable store — keys, entry format, manifest, GC
(docs/COMPILE_CACHE.md).

Entry files reuse the checkpoint layer's v2 integrity container
(checkpoint/format.encode: magic + per-section sha256 digests) and its
fsync'd unique-tmp + atomic-rename install (checkpoint/io.write_checkpoint_blob)
— one durability/integrity implementation for every artifact the stack
persists. A store entry is::

    <cache_dir>/<key-digest>.hexe       # v2 container:
        header:   {"kind": "graftcache-exe/v2", "exe_format": ..., "key": {...}}
        sections: {"executable": <bytes>, "trees": <pickled treedefs>}
    <cache_dir>/manifest.json           # advisory index (ls/gc); lookups go
                                        # by key digest, so a lost manifest
                                        # update can never serve a wrong entry

``exe_format`` is ``"pjrt"`` (``jax.experimental.serialize_executable``
payload — deserialization fires NO XLA compile event, so the recompile
sentinel and the telemetry ``jax/compiles`` counters stay truthful) or
``"stablehlo"`` (the lowering text, persisted where the backend cannot
serialize executables; hydration then recompiles while JAX's own persistent
compilation cache — placed by cache/jaxcache.py at every entry point, never
under ``<cache_dir>`` — absorbs the XLA wall).

Corruption policy: a damaged entry (bad magic, torn container, digest
mismatch, undecodable trees) is LOUD — ``FaultCounters['exec_cache_corrupt']``
increments, a ``cache/corrupt_fallback`` event lands in the telemetry ring —
and the entry is quarantined (renamed ``*.corrupt``) so the caller falls back
to a fresh compile; it is never a crash and never poisons the engine.

Concurrency: the store is written from the serve dispatcher, the warmup
caller, and restart paths, possibly from several PROCESSES sharing one
directory (replicas). Entry installs are atomic renames with writer-owned
unique tmp names (two writers of the same key: last completed rename wins,
both files are valid). The manifest is read-modify-write under the in-process
lock and merged with the on-disk state at each update, so concurrent
processes lose at most a bookkeeping row, never an entry.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
from dataclasses import asdict, dataclass
from typing import Any, Dict, List, Optional, Tuple

from ..analysis import tsan
from ..checkpoint import format as ckpt_format
from ..checkpoint.format import CheckpointCorruptError, param_fingerprint
from ..checkpoint.io import atomic_write_json, write_checkpoint_blob

# v2: the key names the devices the program runs on, so every digest moved and
# a v1 entry (which does not say where it was compiled) is never looked up.
ENTRY_KIND = "graftcache-exe/v2"
ENTRY_SUFFIX = ".hexe"
MANIFEST = "manifest.json"


class CacheEntryError(RuntimeError):
    """A store entry failed integrity verification or deserialization."""


def environment_fingerprint() -> Dict[str, str]:
    """The environment half of every key: jax/jaxlib versions plus a
    backend + device-topology string. Deterministic across processes on the
    same box/config — the property the cross-process warm-start rests on.
    Codegen-affecting environment (XLA_FLAGS, LIBTPU_INIT_ARGS, x64 mode)
    folds into the topology string: an executable compiled under different
    compiler flags must read as a MISS, exactly as JAX's own compilation
    cache keys compile options (the bit-exact-vs-fresh-compile contract). So
    does the version of the named-scope vocabulary (telemetry/scopes.py): the
    names are in the executable's operation metadata, and one compiled under
    other names would show them in every trace."""
    import jax
    import jaxlib

    from ..telemetry import scopes

    devices = jax.devices()
    codegen = hashlib.sha256(
        "|".join(
            (
                os.environ.get("XLA_FLAGS", ""),
                os.environ.get("LIBTPU_INIT_ARGS", ""),
                f"x64={bool(jax.config.jax_enable_x64)}",
                f"scopes={scopes.VERSION}",
            )
        ).encode()
    ).hexdigest()[:12]
    topology = (
        f"{jax.default_backend()}|{len(devices)}x{devices[0].device_kind}"
        f"|procs={jax.process_count()}|codegen={codegen}"
    )
    return {
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "backend": jax.default_backend(),
        "topology": topology,
    }


def tree_signature(tree: Any) -> str:
    """Structure digest of an arbitrary pytree (key paths, shapes, dtypes) —
    the checkpoint layer's param-tree fingerprint applied to any argument
    tree. Two programs traced from signature-identical args lower
    identically for a fixed config, which is what makes this a safe
    argument-side key component."""
    return param_fingerprint(tree)


@dataclass(frozen=True)
class CacheKey:
    """Full environment+program fingerprint of one compiled executable.

    Every field participates in the digest; a mismatch in ANY of them is a
    cache miss (tests/test_compile_cache.py locks each rejection class).

    ``config_fingerprint`` is the caller's model/run identity — built on the
    checkpoint layer's param-tree fingerprint (serve: params+batch_stats
    structure + the model's field repr; train: run_training's digest over
    the Training+Architecture config blocks). ``flags`` carries program-mode
    switches (``donate``, ``guard``); ``bucket`` is the padded arena shape
    ``(N_pad, E_pad, G_pad)`` (zeros when the program is not bucket-shaped);
    ``args_digest`` is the full argument-signature fingerprint
    (:func:`tree_signature`), which subsumes the bucket for correctness —
    the bucket stays a named field for observability (ls/manifest).

    ``mesh`` is the graftmesh axis-layout component
    (``parallel.distributed.mesh_descriptor``, e.g. ``"data:4xgraph:2"``):
    shard_map programs compiled for one mesh shape must never hydrate
    another's entries even when every array shape agrees (the environment
    topology pins the device COUNT; this pins the axis FACTORIZATION).
    Empty = single-device program.

    ``devices`` is the ids of the devices the program runs on, in assignment
    order: the mesh's for a mesh program, the one chip for a single-device
    program. A serialized executable is bound to them, so replicas on
    different chips of one host, or meshes over different chips, never share
    an entry; hydration loads the executable onto exactly these."""

    program: str
    jax_version: str
    jaxlib_version: str
    backend: str
    topology: str
    config_fingerprint: str
    flags: Tuple[str, ...] = ()
    bucket: Tuple[int, int, int] = (0, 0, 0)
    args_digest: str = ""
    mesh: str = ""
    devices: Tuple[int, ...] = ()

    @classmethod
    def for_environment(
        cls,
        program: str,
        config_fingerprint: str,
        flags: Tuple[str, ...] = (),
        bucket: Tuple[int, int, int] = (0, 0, 0),
        args_digest: str = "",
        env: Optional[Dict[str, str]] = None,
        mesh: str = "",
        devices: Optional[Tuple[int, ...]] = None,
    ) -> "CacheKey":
        """``devices=None`` means the process's default device, where a jit
        over uncommitted arguments runs."""
        env = env if env is not None else environment_fingerprint()
        if devices is None:
            import jax

            devices = (jax.devices()[0].id,)
        return cls(
            program=program,
            jax_version=env["jax_version"],
            jaxlib_version=env["jaxlib_version"],
            backend=env["backend"],
            topology=env["topology"],
            config_fingerprint=config_fingerprint,
            flags=tuple(sorted(flags)),
            bucket=(int(bucket[0]), int(bucket[1]), int(bucket[2])),
            args_digest=args_digest,
            mesh=str(mesh),
            devices=tuple(int(i) for i in devices),
        )

    def to_json(self) -> Dict[str, Any]:
        doc = asdict(self)
        doc["flags"] = list(self.flags)
        doc["bucket"] = list(self.bucket)
        doc["devices"] = list(self.devices)
        return doc

    @classmethod
    def from_json(cls, doc: Dict[str, Any]) -> "CacheKey":
        bucket = doc.get("bucket") or (0, 0, 0)
        return cls(
            program=doc["program"],
            jax_version=doc["jax_version"],
            jaxlib_version=doc["jaxlib_version"],
            backend=doc["backend"],
            topology=doc["topology"],
            config_fingerprint=doc["config_fingerprint"],
            flags=tuple(doc.get("flags") or ()),
            bucket=(int(bucket[0]), int(bucket[1]), int(bucket[2])),
            args_digest=doc.get("args_digest", ""),
            mesh=doc.get("mesh", ""),
            devices=tuple(int(i) for i in doc.get("devices") or ()),
        )

    def digest(self) -> str:
        """Canonical-JSON sha256 — the entry filename and the identity the
        round-trip test pins (same fields ⇒ same digest across processes)."""
        blob = json.dumps(self.to_json(), sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()


class ExecutableStore:
    """Directory-backed executable store with verified reads and atomic
    writes. Thread-safe; multi-process-safe at the entry level (atomic
    renames), advisory at the manifest level (see module docstring)."""

    def __init__(self, cache_dir: str, keep_max_entries: int = 0):
        self.cache_dir = cache_dir
        # keep_max_entries <= 0: unbounded (GC only via the CLI / explicit
        # gc()); > 0: put() prunes oldest-serial entries beyond the cap.
        self.keep_max_entries = int(keep_max_entries)
        self._lock = tsan.instrument_lock(
            threading.Lock(), "ExecutableStore._lock"
        )
        os.makedirs(cache_dir, exist_ok=True)

    # ------------------------------------------------------------ path layout
    def entry_path(self, key: CacheKey) -> str:
        return os.path.join(self.cache_dir, key.digest() + ENTRY_SUFFIX)

    def _manifest_path(self) -> str:
        return os.path.join(self.cache_dir, MANIFEST)

    # ----------------------------------------------------------------- write
    def put(
        self,
        key: CacheKey,
        sections: Dict[str, bytes],
        exe_format: str,
        extra: Optional[Dict[str, Any]] = None,
    ) -> str:
        """Install one entry: digest container + fsync + atomic rename, then
        the advisory manifest row. Returns the entry path."""
        header = {
            "kind": ENTRY_KIND,
            "exe_format": exe_format,
            "key": key.to_json(),
        }
        blob = ckpt_format.encode(dict(sections), header)
        path = self.entry_path(key)
        write_checkpoint_blob(path, blob)
        with self._lock:
            self._manifest_add(key, exe_format, len(blob), extra or {})
        return path

    def _manifest_add(
        self, key: CacheKey, exe_format: str, nbytes: int, extra: Dict[str, Any]
    ) -> None:
        # Merge-with-disk read-modify-write: a concurrent process's rows are
        # re-read here, so the manifest converges instead of ping-ponging.
        manifest = self._read_manifest()
        entries = [
            e for e in manifest.get("entries", []) if e.get("digest") != key.digest()
        ]
        serial = max((e.get("serial", 0) for e in entries), default=0) + 1
        entries.append(
            {
                "digest": key.digest(),
                "key": key.to_json(),
                "exe_format": exe_format,
                "bytes": int(nbytes),
                "created_utc": time.strftime(
                    "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
                ),
                "serial": serial,
            }
            | ({"extra": extra} if extra else {})
        )
        if self.keep_max_entries > 0 and len(entries) > self.keep_max_entries:
            entries.sort(key=lambda e: e.get("serial", 0))
            for drop in entries[: -self.keep_max_entries]:
                self._remove_file(drop.get("digest", ""))
            entries = entries[-self.keep_max_entries :]
        atomic_write_json(
            self._manifest_path(),
            {"kind": "graftcache-manifest/v1", "entries": entries},
        )

    def _read_manifest(self) -> Dict[str, Any]:
        try:
            with open(self._manifest_path()) as f:
                doc = json.load(f)
            return doc if isinstance(doc, dict) else {}
        except (OSError, ValueError):
            return {}

    def _remove_file(self, digest: str) -> None:
        if not digest:
            return
        try:
            os.remove(os.path.join(self.cache_dir, digest + ENTRY_SUFFIX))
        except OSError:
            pass

    # ------------------------------------------------------------------ read
    def get(self, key: CacheKey) -> Optional[Tuple[Dict[str, bytes], str]]:
        """Verified read of one entry → (sections, exe_format), or None on a
        miss. A CORRUPT entry (torn container, digest mismatch, key-field
        disagreement) is quarantined loudly and reads as a miss — the caller
        compiles fresh; the store never crashes a serving path."""
        path = self.entry_path(key)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                blob = f.read()
            header, sections = ckpt_format.decode(blob, path)
            if header.get("kind") != ENTRY_KIND:
                raise CheckpointCorruptError(
                    path, f"not a graftcache entry (kind={header.get('kind')!r})"
                )
            stored_key = CacheKey.from_json(header.get("key") or {})
            if stored_key != key:
                # A digest collision is cryptographically out of reach; a
                # disagreement here means the file was tampered with or a
                # foreign file landed under this name — same fallback.
                raise CheckpointCorruptError(path, "stored key != lookup key")
            return dict(sections), str(header.get("exe_format", "pjrt"))
        except ckpt_format.CheckpointError as e:
            self._quarantine(path, key, str(e))
            return None

    def _quarantine(self, path: str, key: CacheKey, reason: str) -> None:
        """Loud corruption fallback: count it, ring-event it, move the file
        aside so the follow-up fresh compile can re-install cleanly."""
        from ..faults import FaultCounters
        from ..telemetry import graftel as telemetry

        FaultCounters.inc("exec_cache_corrupt")
        telemetry.event(
            "cache/corrupt_fallback",
            program=key.program,
            bucket=list(key.bucket),
            entry=os.path.basename(path),
            reason=reason[:300],
        )
        try:
            os.replace(path, path + ".corrupt")
        except OSError:
            try:
                os.remove(path)
            except OSError:
                pass

    # ------------------------------------------------------- CLI / maintenance
    def ls(self) -> List[Dict[str, Any]]:
        """Manifest rows merged with the directory truth: rows whose entry
        file vanished are dropped, on-disk entries the manifest missed (a
        lost concurrent update) are listed from their own headers."""
        with self._lock:
            manifest = self._read_manifest()
        rows = {
            e.get("digest"): dict(e)
            for e in manifest.get("entries", [])
            if os.path.exists(
                os.path.join(self.cache_dir, str(e.get("digest")) + ENTRY_SUFFIX)
            )
        }
        for fname in sorted(os.listdir(self.cache_dir)):
            if not fname.endswith(ENTRY_SUFFIX):
                continue
            digest = fname[: -len(ENTRY_SUFFIX)]
            if digest in rows:
                continue
            report = self.verify_entry(os.path.join(self.cache_dir, fname))
            if report.get("ok"):
                rows[digest] = {
                    "digest": digest,
                    "key": report["key"],
                    "exe_format": report["exe_format"],
                    "bytes": report["bytes"],
                    "created_utc": None,
                    "serial": 0,
                }
        return [rows[d] for d in sorted(rows)]

    @staticmethod
    def verify_entry(path: str) -> Dict[str, Any]:
        """Non-raising integrity report for one entry file (the ``verify``
        CLI — the checkpoint CLI's verify analog)."""
        report: Dict[str, Any] = {"file": path}
        try:
            with open(path, "rb") as f:
                blob = f.read()
            header, sections = ckpt_format.decode(blob, path)
            if header.get("kind") != ENTRY_KIND:
                raise CheckpointCorruptError(
                    path, f"not a graftcache entry (kind={header.get('kind')!r})"
                )
        except ckpt_format.CheckpointError as e:
            report.update(ok=False, error=str(e))
            return report
        report.update(
            ok=True,
            key=header.get("key"),
            exe_format=header.get("exe_format"),
            bytes=len(blob),
            sections=sorted(sections),
        )
        return report

    def verify(self) -> List[Dict[str, Any]]:
        return [
            self.verify_entry(os.path.join(self.cache_dir, f))
            for f in sorted(os.listdir(self.cache_dir))
            if f.endswith(ENTRY_SUFFIX)
        ]

    def gc(self, keep_last: int = 0, max_age_days: Optional[float] = None) -> List[str]:
        """Prune entries beyond ``keep_last`` (newest-serial kept) and/or
        older than ``max_age_days`` (file mtime). Returns removed digests.
        Also sweeps ``*.corrupt`` quarantine files and stale ``*.tmp``."""
        removed: List[str] = []
        with self._lock:
            manifest = self._read_manifest()
            entries = sorted(
                manifest.get("entries", []), key=lambda e: e.get("serial", 0)
            )
            keep = entries[-keep_last:] if keep_last > 0 else list(entries)
            drop = entries[:-keep_last] if keep_last > 0 else []
            now = time.time()
            if max_age_days is not None:
                still = []
                for e in keep:
                    p = os.path.join(
                        self.cache_dir, str(e.get("digest")) + ENTRY_SUFFIX
                    )
                    try:
                        old = (now - os.path.getmtime(p)) > max_age_days * 86400.0
                    except OSError:
                        old = True
                    (drop if old else still).append(e)
                keep = still
            for e in drop:
                self._remove_file(str(e.get("digest")))
                removed.append(str(e.get("digest")))
            for fname in os.listdir(self.cache_dir):
                p = os.path.join(self.cache_dir, fname)
                if fname.endswith(".tmp"):
                    # A .tmp may be a LIVE concurrent writer's in-flight
                    # install (multi-replica shared store) — only sweep ones
                    # old enough that no real write is still running (the
                    # checkpoint layer scopes its sweep to run startup for
                    # the same reason).
                    try:
                        stale = (now - os.path.getmtime(p)) > 3600.0
                    except OSError:
                        continue
                    if not stale:
                        continue
                elif not fname.endswith(".corrupt"):
                    continue
                try:
                    os.remove(p)
                    removed.append(fname)
                except OSError:
                    pass
            atomic_write_json(
                self._manifest_path(),
                {"kind": "graftcache-manifest/v1", "entries": keep},
            )
        return removed


# ------------------------------------------------- executable (de)serialization
def hydratable(devices: Tuple[int, ...]) -> bool:
    """Whether a program on ``devices`` can come back from the store. A
    single-device program on a NON-default device cannot: loaded with its own
    chip as the execution device, the TPU runtime (jaxlib 0.9, four chips,
    PR 21) reports that chip and then dies at the first call, "replica is
    assigned to device TPU_0". The registry neither stores nor looks up such
    a program; it compiles."""
    import jax

    return len(devices) != 1 or devices[0] == jax.devices()[0].id


def serialize_compiled(compiled: Any) -> Optional[Dict[str, bytes]]:
    """``jax.stages.Compiled`` → store sections, or None when the backend
    cannot serialize executables (the StableHLO fallback engages then).
    Treedefs ride along pickled — custom pytree nodes (GraphBatch,
    TrainState, optax states) unpickle against the SAME registered types, so
    hydration must happen after the defining modules imported (they have:
    the engine/trainer import them before any lookup)."""
    try:
        from jax.experimental import serialize_executable as se

        payload, in_tree, out_tree = se.serialize(compiled)
        return {
            "executable": payload,
            "trees": pickle.dumps((in_tree, out_tree)),
        }
    except Exception:  # noqa: BLE001 — backend capability probe, not an error
        return None


def deserialize_compiled(sections: Dict[str, bytes], devices: Tuple[int, ...]) -> Any:
    """Store sections → executable loaded onto ``devices``, the key's: the
    one chip of a single-device program, the mesh's devices for a mesh
    program. Left to its default, ``deserialize_and_load`` loads onto EVERY
    visible device and the first call asks for one shard per device (seen on
    the four-chip host, PR 21). Raises :class:`CacheEntryError` on any decode
    failure (the registry turns that into quarantine + fresh compile).
    Deserialization fires NO XLA compile monitoring event — the
    sentinel-truthfulness property tests/test_compile_cache.py pins."""
    import jax
    from jax.experimental import serialize_executable as se

    by_id = {d.id: d for d in jax.devices()}
    try:
        # graftlint: disable=pickle-load-outside-compat(pytree defs inside a GSHD cache container whose digest was verified before this call — no untrusted bytes reach the unpickler)
        in_tree, out_tree = pickle.loads(sections["trees"])
        return se.deserialize_and_load(
            sections["executable"],
            in_tree,
            out_tree,
            execution_devices=[by_id[i] for i in devices],
        )
    except Exception as e:  # noqa: BLE001 — one failure class for callers
        raise CacheEntryError(
            f"executable deserialization failed ({type(e).__name__}: {e})"
        ) from e
