"""Replica backends behind one interface (docs/SERVING.md "Multi-replica
tier").

The router dispatches to :class:`Replica` objects and never sees what is
behind them:

* :class:`InProcessReplica` — an ``InferenceEngine`` in this process (the
  test/bench topology, and the ``--replicas N`` CLI mode where one host
  runs several engines over one shared graftcache store);
* :class:`HttpReplica` — a ``python -m hydragnn_tpu.serve`` process reached
  over HTTP (same host via :func:`spawn_serve_replica`, or any remote
  host). Correlation ids ride the ``X-HydraGNN-Request-Id`` header both
  ways, so a request keeps one id across replica hops.

Error taxonomy (what the router's retry logic keys on):

* :class:`ReplicaBackpressureError` — the replica shed load (engine 429
  path); carries the replica's own retry-after hint and queue depth. The
  replica is HEALTHY; the router may retry elsewhere within the request's
  deadline or surface the hint fleet-wide.
* :class:`ReplicaDownError` — the replica cannot serve (poisoned/closed
  engine, connection refused, 503). The router retries elsewhere and the
  health loop confirms ejection.

Anything else (per-request validation errors, timeouts) propagates: a
malformed graph is malformed on every replica — retrying would amplify it.
"""

from __future__ import annotations

import json
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..graphs.sample import GraphSample
from ..serve.server import MODEL_VERSION_HEADER, REQUEST_ID_HEADER


class ReplicaError(RuntimeError):
    """Base class for dispatch failures the router knows how to handle."""


class ReplicaBackpressureError(ReplicaError):
    """The replica shed this request (its bounded queue is full)."""

    def __init__(
        self,
        message: str,
        retry_after_s: float,
        queue_depth: Optional[int] = None,
    ):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)
        self.queue_depth = queue_depth


class ReplicaDownError(ReplicaError):
    """The replica cannot serve at all (poisoned, closed, unreachable)."""


class Replica:
    """One engine replica the router can dispatch to.

    Implementations must be safe to call from multiple router caller
    threads concurrently (both backends are: the engine's submit path and
    one-urllib-connection-per-call are thread-safe).
    """

    name: str = ""

    def predict(
        self,
        samples: Sequence[GraphSample],
        timeout: float = 60.0,
        request_id: Optional[str] = None,
    ) -> List[List[np.ndarray]]:
        """One synchronous prediction call; per-graph per-head outputs,
        numerically identical to a direct ``InferenceEngine.predict``."""
        raise NotImplementedError

    def predict_versioned(
        self,
        samples: Sequence[GraphSample],
        timeout: float = 60.0,
        request_id: Optional[str] = None,
    ) -> Tuple[List[List[np.ndarray]], Optional[str]]:
        """``(results, model_version)`` — the version tag the lifecycle
        layer threads through RouteResult and the response header
        (docs/SERVING.md "Live model lifecycle"). Backends that cannot
        report a version return None; both shipped backends can."""
        return self.predict(samples, timeout=timeout, request_id=request_id), None

    def health(self) -> Dict[str, Any]:
        """The replica's /healthz view (ok, degraded, queue depth, compiled
        buckets, fault counters, hydration counters). Raising == down."""
        raise NotImplementedError

    def swap_checkpoint(
        self,
        path: str,
        version: Optional[str] = None,
        expected_identity: Optional[str] = None,
    ) -> Dict[str, Any]:
        """Hot-swap this replica onto the v2 checkpoint at ``path`` (a
        shared-storage path the replica's own process can read) — the
        fleet-orchestration surface ``LifecycleManager`` drives for replicas
        it holds no engine object for (docs/SERVING.md "Live model
        lifecycle"). Same refusal semantics as ``engine.swap_weights``:
        identity/fingerprint/tolerance mismatches raise and the replica
        keeps serving its current version."""
        raise NotImplementedError

    def close(self) -> None:  # pragma: no cover - interface default
        pass


class InProcessReplica(Replica):
    """An ``InferenceEngine`` in this process."""

    def __init__(self, name: str, engine):
        self.name = str(name)
        self.engine = engine

    def predict(
        self,
        samples: Sequence[GraphSample],
        timeout: float = 60.0,
        request_id: Optional[str] = None,
    ) -> List[List[np.ndarray]]:
        return self.predict_versioned(
            samples, timeout=timeout, request_id=request_id
        )[0]

    def predict_versioned(
        self,
        samples: Sequence[GraphSample],
        timeout: float = 60.0,
        request_id: Optional[str] = None,
    ) -> Tuple[List[List[np.ndarray]], Optional[str]]:
        from ..serve.engine import (
            BackpressureError,
            EngineClosedError,
            EngineFailedError,
        )

        try:
            results, versions = self.engine.predict_versioned(
                samples, timeout=timeout, request_id=request_id
            )
        except BackpressureError as e:
            raise ReplicaBackpressureError(
                str(e),
                retry_after_s=e.retry_after_s,
                queue_depth=self.engine._queue.qsize(),
            ) from e
        except (EngineClosedError, EngineFailedError) as e:
            raise ReplicaDownError(
                f"replica {self.name}: {e}"
            ) from e
        tagged = [v for v in versions if v]
        return results, tagged[-1] if tagged else None

    def health(self) -> Dict[str, Any]:
        engine = self.engine
        counters = engine.metrics.read_counters(
            "bad_batches_total",
            "nonfinite_total",
            "engine_restarts_total",
            "exec_cache_hydrated_total",
            "cache_misses_total",
            "weight_swaps_total",
            "swap_rejected_total",
        )
        # Mirrors the HTTP /healthz payload (serve/server.py) so the router
        # consumes ONE schema regardless of backend.
        return {
            "ok": engine.running,
            "degraded": engine.degraded,
            "degraded_events": engine.degraded_events,
            "queue_depth": engine._queue.qsize(),
            "queue_limit": engine.queue_limit,
            "compiled_buckets": engine.compiled_buckets,
            "device": engine.device,
            "precision": engine.precision,
            "model_version": engine.model_version,
            "weight_swaps": counters["weight_swaps_total"],
            "swaps_rejected": counters["swap_rejected_total"],
            "bad_batches": counters["bad_batches_total"],
            "nonfinite_outputs": counters["nonfinite_total"],
            "restarts": counters["engine_restarts_total"],
            "hydrated_buckets": counters["exec_cache_hydrated_total"],
            "compiled_fresh_buckets": counters["cache_misses_total"],
            "replica": self.name,
        }

    def swap_checkpoint(
        self,
        path: str,
        version: Optional[str] = None,
        expected_identity: Optional[str] = None,
    ) -> Dict[str, Any]:
        from ..serve.engine import swap_from_checkpoint

        return swap_from_checkpoint(
            self.engine, path, version=version,
            expected_identity=expected_identity,
        )

    def close(self) -> None:
        self.engine.close()


def graph_doc(sample: GraphSample) -> Dict[str, Any]:
    """One GraphSample as the /predict request-graph JSON object (the
    inverse of serve/server.py ``parse_graph``)."""
    doc: Dict[str, Any] = {"x": np.asarray(sample.x).tolist()}
    if sample.edge_index is not None:
        doc["edge_index"] = np.asarray(sample.edge_index).tolist()
    if sample.edge_attr is not None:
        doc["edge_attr"] = np.asarray(sample.edge_attr).tolist()
    if sample.pos is not None:
        doc["pos"] = np.asarray(sample.pos).tolist()
    return doc


class HttpReplica(Replica):
    """A serve process reached over HTTP (subprocess or remote host).

    Numerical note: /predict serializes float32 outputs via ``tolist()``
    (repr round-trip, exact for float32) and this class casts back to
    float32 — HTTP replicas stay bit-exact with in-process ones.

    ``health_timeout_s`` bounds the /healthz probe separately from request
    traffic: the router's health loop polls replicas SEQUENTIALLY, so a
    wedged replica holding a 60 s request timeout would freeze the whole
    fleet's drain/eject/readmit cadence — a health probe that cannot answer
    in a few seconds IS the down signal.
    """

    def __init__(
        self,
        name: str,
        base_url: str,
        timeout_s: float = 60.0,
        health_timeout_s: float = 5.0,
    ):
        self.name = str(name)
        self.base_url = base_url.rstrip("/")
        self.timeout_s = float(timeout_s)
        self.health_timeout_s = float(health_timeout_s)

    def _read_json(self, resp) -> Dict[str, Any]:
        try:
            return json.loads(resp.read() or b"{}")
        except (ValueError, OSError):
            return {}

    def predict(
        self,
        samples: Sequence[GraphSample],
        timeout: float = 60.0,
        request_id: Optional[str] = None,
    ) -> List[List[np.ndarray]]:
        return self.predict_versioned(
            samples, timeout=timeout, request_id=request_id
        )[0]

    def predict_versioned(
        self,
        samples: Sequence[GraphSample],
        timeout: float = 60.0,
        request_id: Optional[str] = None,
    ) -> Tuple[List[List[np.ndarray]], Optional[str]]:
        body = json.dumps(
            {"graphs": [graph_doc(s) for s in samples]}
        ).encode()
        headers = {"Content-Type": "application/json"}
        if request_id:
            headers[REQUEST_ID_HEADER] = request_id
        req = urllib.request.Request(
            self.base_url + "/predict", data=body, headers=headers
        )
        version: Optional[str] = None
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                doc = self._read_json(resp)
                version = (
                    doc.get("model_version")
                    or resp.headers.get(MODEL_VERSION_HEADER)
                )
        except urllib.error.HTTPError as e:
            payload = self._read_json(e)
            if e.code == 429:
                raise ReplicaBackpressureError(
                    payload.get("error", "replica backpressure"),
                    retry_after_s=float(
                        payload.get("retry_after_s")
                        or e.headers.get("Retry-After")
                        or 1.0
                    ),
                ) from e
            if e.code in (502, 503):
                raise ReplicaDownError(
                    f"replica {self.name}: HTTP {e.code}: "
                    f"{payload.get('error', '')}"
                ) from e
            if e.code == 400:
                raise ValueError(
                    payload.get("error", f"replica rejected request: {e}")
                ) from e
            if e.code == 504:
                raise TimeoutError(
                    payload.get("error", "replica request timed out")
                ) from e
            raise ReplicaError(
                f"replica {self.name}: HTTP {e.code}"
            ) from e
        except (urllib.error.URLError, ConnectionError, OSError) as e:
            raise ReplicaDownError(f"replica {self.name}: {e}") from e
        return [
            [np.asarray(h, dtype=np.float32) for h in per_graph]
            for per_graph in doc["predictions"]
        ], version

    def swap_checkpoint(
        self,
        path: str,
        version: Optional[str] = None,
        expected_identity: Optional[str] = None,
    ) -> Dict[str, Any]:
        """POST /swap on the replica (it must run with ``--admin``): the
        replica loads ``path`` from ITS filesystem — a fleet shares the
        checkpoint store the same way it shares the graftcache store."""
        doc: Dict[str, Any] = {"checkpoint": path}
        if version:
            doc["version"] = version
        if expected_identity:
            doc["expected_identity"] = expected_identity
        req = urllib.request.Request(
            self.base_url + "/swap",
            data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout_s) as resp:
                return self._read_json(resp)
        except urllib.error.HTTPError as e:
            payload = self._read_json(e)
            err = payload.get("error", f"HTTP {e.code}")
            if e.code in (502, 503):
                raise ReplicaDownError(f"replica {self.name}: {err}") from e
            # 403 (admin disabled), 409 (refused swap), 400 (bad file): the
            # replica is healthy and KEPT its version — surface the refusal.
            raise ReplicaError(
                f"replica {self.name}: swap refused (HTTP {e.code}): {err}"
            ) from e
        except (urllib.error.URLError, ConnectionError, OSError) as e:
            raise ReplicaDownError(f"replica {self.name}: {e}") from e

    def health(self) -> Dict[str, Any]:
        try:
            with urllib.request.urlopen(
                self.base_url + "/healthz", timeout=self.health_timeout_s
            ) as resp:
                return self._read_json(resp)
        except urllib.error.HTTPError as e:
            if e.code == 503:  # down-but-answering: the payload is honest
                doc = self._read_json(e)
                doc.setdefault("ok", False)
                return doc
            raise ReplicaDownError(
                f"replica {self.name}: healthz HTTP {e.code}"
            ) from e
        except (urllib.error.URLError, ConnectionError, OSError) as e:
            raise ReplicaDownError(
                f"replica {self.name}: healthz {e}"
            ) from e


_LISTEN_RE = re.compile(r"listening on (http://[\w.:\-]+)")


def spawn_serve_replica(
    name: str,
    serve_args: Sequence[str],
    startup_timeout_s: float = 300.0,
) -> Tuple[HttpReplica, "subprocess.Popen[str]"]:
    """Spawn ``python -m hydragnn_tpu.serve <serve_args>`` as a subprocess
    replica and return (HttpReplica, process) once its listen line appears.

    Pass ``--port 0`` in ``serve_args`` for an ephemeral port — the bound
    address is parsed from the server's startup line. Point every spawned
    replica's ``--compile-cache`` at the shared graftcache store so spin-up
    hydrates instead of compiling (docs/COMPILE_CACHE.md). The caller owns
    the process (terminate it after ``replica.close()``)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "hydragnn_tpu.serve", *serve_args],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    # The pipe is scanned on a reader thread: readline() has no timeout, so
    # a child that stays alive but never prints (wedged checkpoint load,
    # silent hang) must not block the caller past startup_timeout_s — the
    # deadline is enforced on the Event wait, and the reader dies with the
    # killed process's EOF.
    lines: List[str] = []
    url_box: List[str] = []
    found = threading.Event()

    def _scan() -> None:
        assert proc.stdout is not None
        for line in proc.stdout:
            if not found.is_set():
                lines.append(line)
                m = _LISTEN_RE.search(line)
                if m:
                    url_box.append(m.group(1))
                    found.set()
            # After startup keep DRAINING (and discarding) the merged
            # stdout/stderr pipe for the replica's lifetime: a child that
            # keeps logging into a full 64 KB pipe would block mid-write
            # and wedge the serve process.
        found.set()  # EOF without a listen line: stop waiting

    reader = threading.Thread(
        target=_scan, name="hydragnn-route-spawn-reader", daemon=True
    )
    reader.start()
    t0 = time.monotonic()
    while time.monotonic() - t0 < startup_timeout_s:
        if found.wait(timeout=0.25):
            break
        if proc.poll() is not None:
            found.wait(timeout=2.0)  # let the reader drain the final output
            break
    if url_box:
        return HttpReplica(name, url_box[0]), proc
    proc.kill()
    raise RuntimeError(
        f"spawned replica {name!r} never printed its listen line within "
        f"{startup_timeout_s:g}s; output:\n" + "".join(lines[-20:])
    )
