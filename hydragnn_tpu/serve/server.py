"""Stdlib-only HTTP front end for the inference engine (docs/SERVING.md).

Endpoints:
  POST /predict  — JSON graphs in, per-head predictions out (200);
                   400 on malformed input, 429 + Retry-After under
                   backpressure, 503 after a worker failure.
  GET  /healthz  — liveness + queue depth (JSON).
  GET  /metrics  — Prometheus text exposition of the serving metrics.

Deliberately ``http.server`` (ThreadingHTTPServer): the container bakes no
web framework, and the engine does all the concurrency work — each handler
thread only parses JSON, blocks on its requests' futures, and serializes the
answer. Request batching across connections happens INSIDE the engine, so
even this simple threaded server gets micro-batched device execution.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional

import numpy as np

from ..graphs.sample import GraphSample
from ..telemetry import graftel as telemetry
from ..telemetry import render_prometheus
from .engine import BackpressureError, EngineFailedError, InferenceEngine

REQUEST_ID_HEADER = "X-HydraGNN-Request-Id"
# Replica-mode plumbing (docs/SERVING.md "Multi-replica tier"): a serve
# process running as one replica of a routed fleet labels every response so
# the router's hop logs and clients can attribute answers to replicas.
REPLICA_ID_HEADER = "X-HydraGNN-Replica"
# Live model lifecycle (docs/SERVING.md "Live model lifecycle"): every
# response names the model version that answered it — echoed on ALL paths
# like the request-id header, so a client (and the swap-under-load drill)
# can assert no response is ever version-torn across a hot swap.
MODEL_VERSION_HEADER = "X-HydraGNN-Model-Version"


def parse_graph(doc: dict) -> GraphSample:
    """One request graph: {"x": [[...]], "edge_index": [[s...],[r...]],
    "edge_attr": [[...]]?, "pos": [[...]]?}."""
    if not isinstance(doc, dict) or "x" not in doc:
        raise ValueError('each graph must be an object with an "x" field')
    x = np.asarray(doc["x"], dtype=np.float32)
    if x.ndim != 2:
        raise ValueError('"x" must be a [num_nodes, F] nested list')
    edge_index = None
    if doc.get("edge_index") is not None:
        edge_index = np.asarray(doc["edge_index"], dtype=np.int32)
        if edge_index.ndim != 2 or edge_index.shape[0] != 2:
            raise ValueError('"edge_index" must be [2, num_edges]')
        if edge_index.size and (
            edge_index.min() < 0 or edge_index.max() >= x.shape[0]
        ):
            raise ValueError('"edge_index" references nodes outside "x"')
    edge_attr = None
    if doc.get("edge_attr") is not None:
        edge_attr = np.asarray(doc["edge_attr"], dtype=np.float32)
        if edge_attr.ndim != 2 or (
            edge_index is not None and edge_attr.shape[0] != edge_index.shape[1]
        ):
            raise ValueError('"edge_attr" must be [num_edges, D]')
    pos = None
    if doc.get("pos") is not None:
        pos = np.asarray(doc["pos"], dtype=np.float32)
    return GraphSample(x=x, pos=pos, edge_index=edge_index, edge_attr=edge_attr)


class RequestPlumbing:
    """Shared HTTP plumbing for the engine and router front ends
    (route/server.py): request-id hygiene and JSON/text response emission.
    A mixin, NOT a BaseHTTPRequestHandler subclass — each concrete handler
    keeps ``BaseHTTPRequestHandler`` as an explicit base so graftrace's
    handler-thread-root discovery still sees it. One implementation of the
    PR-9 contract: the correlation id is echoed on EVERY response path, and
    a malformed caller header is REPLACED, never echoed."""

    def log_message(self, fmt, *args):  # quiet by default
        if getattr(self.server, "verbose", False):  # type: ignore[attr-defined]
            super().log_message(fmt, *args)  # type: ignore[misc]

    def _request_id(self) -> str:
        """This request's correlation id — echoed on EVERY response path
        (200/400/404/429/5xx — docs/OBSERVABILITY.md)."""
        rid = getattr(self, "_rid", None)
        return rid if rid is not None else self._begin_request()

    # Caller-supplied ids are reflected into response headers, telemetry
    # records, /healthz payloads, and flight dumps: restrict to a safe
    # charset and length so a crafted header (CRLF folds = response-header
    # injection; megabyte values = ring/artifact bloat) is REPLACED by a
    # generated id rather than echoed.
    _RID_SAFE = frozenset(
        "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789.-_/"
    )
    _RID_MAX_LEN = 64

    def _begin_request(self) -> str:
        """Per-request id (re)set — handler instances persist across
        keep-alive requests, so the id must NOT be cached beyond one
        request; honors a well-formed caller header, generates otherwise."""
        raw = self.headers.get(REQUEST_ID_HEADER) or ""  # type: ignore[attr-defined]
        ok = (
            0 < len(raw) <= self._RID_MAX_LEN
            and all(c in self._RID_SAFE for c in raw)
        )
        self._rid = raw if ok else telemetry.new_request_id()
        # Per-request model-version override (the router front end sets it
        # from the answering replica's RouteResult); handler instances
        # persist across keep-alive requests, so it must reset here.
        self._mv_override: Optional[str] = None
        return self._rid

    def _model_version(self) -> Optional[str]:
        """The model version this response reports: a per-request override
        (router path — whatever replica answered) or the server-wide
        provider (engine path — the engine's CURRENT version, which is the
        honest answer on non-predict paths like /healthz and 4xx)."""
        override = getattr(self, "_mv_override", None)
        if override:
            return override
        fn = getattr(self.server, "model_version_fn", None)  # type: ignore[attr-defined]
        return fn() if fn is not None else None

    def _send_json(self, code: int, payload: dict, headers: Optional[dict] = None):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.send_header(REQUEST_ID_HEADER, self._request_id())
        replica_id = getattr(self.server, "replica_id", None)
        if replica_id:
            self.send_header(REPLICA_ID_HEADER, replica_id)
        model_version = self._model_version()
        if model_version:
            self.send_header(MODEL_VERSION_HEADER, model_version)
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _send_text(self, code: int, text: str, content_type: str):
        body = text.encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.send_header(REQUEST_ID_HEADER, self._request_id())
        replica_id = getattr(self.server, "replica_id", None)
        if replica_id:
            self.send_header(REPLICA_ID_HEADER, replica_id)
        model_version = self._model_version()
        if model_version:
            self.send_header(MODEL_VERSION_HEADER, model_version)
        self.end_headers()
        self.wfile.write(body)


class _Handler(RequestPlumbing, BaseHTTPRequestHandler):
    # Engine injected by InferenceServer via the server object.
    protocol_version = "HTTP/1.1"

    @property
    def engine(self) -> InferenceEngine:
        return self.server.engine  # type: ignore[attr-defined]

    # ---------------------------------------------------------------- routes
    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler API
        self._begin_request()
        if self.path == "/healthz":
            engine = self.engine
            # Three health states instead of the old binary: ok (200),
            # degraded-but-serving (200, degraded: true — bad batches,
            # non-finite outputs, or a worker restart happened), down (503).
            fault_counters = engine.metrics.read_counters(
                "bad_batches_total",
                "nonfinite_total",
                "engine_restarts_total",
                # Warmup provenance for the router's warm-spin-up gate
                # (docs/COMPILE_CACHE.md): how many buckets came from the
                # persistent store vs fresh compiles.
                "exec_cache_hydrated_total",
                "cache_misses_total",
                # Lifecycle (docs/SERVING.md "Live model lifecycle"): the
                # router's health map learns which version each replica
                # runs and whether swaps happened/were refused.
                "weight_swaps_total",
                "swap_rejected_total",
            )
            self._send_json(
                200 if engine.running else 503,
                {
                    "ok": engine.running,
                    "replica": getattr(self.server, "replica_id", None),
                    "degraded": engine.degraded,
                    # Recent degraded transitions with the correlation ids
                    # that tripped them (docs/OBSERVABILITY.md).
                    "degraded_events": engine.degraded_events,
                    "queue_depth": engine._queue.qsize(),
                    "queue_limit": engine.queue_limit,
                    "compiled_buckets": engine.compiled_buckets,
                    # The device the weights live on, as JAX reports it.
                    "device": engine.device,
                    # Serving arm (docs/PRECISION.md): operators must see at
                    # a glance whether this replica answers under the
                    # bit-exactness contract or a tolerance gate.
                    "precision": engine.precision,
                    # Which model version this replica answers with — the
                    # router's per-replica version view (docs/SERVING.md
                    # "Live model lifecycle").
                    "model_version": engine.model_version,
                    "weight_swaps": fault_counters["weight_swaps_total"],
                    "swaps_rejected": fault_counters["swap_rejected_total"],
                    "bad_batches": fault_counters["bad_batches_total"],
                    "nonfinite_outputs": fault_counters["nonfinite_total"],
                    "restarts": fault_counters["engine_restarts_total"],
                    "hydrated_buckets": fault_counters[
                        "exec_cache_hydrated_total"
                    ],
                    "compiled_fresh_buckets": fault_counters[
                        "cache_misses_total"
                    ],
                },
            )
        elif self.path == "/metrics":
            # Engine-scoped serving metrics + the process-wide graftel
            # registry (timer totals, fault counters, training gauges when
            # this process also trains) — one scrape, one registry.
            self._send_text(
                200,
                self.engine.metrics.render_prometheus()
                + render_prometheus(),
                "text/plain; version=0.0.4",
            )
        else:
            self._send_json(404, {"error": f"unknown path {self.path}"})

    def do_POST(self):  # noqa: N802
        rid = self._begin_request()
        # Always drain the body first: HTTP/1.1 keep-alive would otherwise
        # parse leftover body bytes as the NEXT request line after a 404.
        length = int(self.headers.get("Content-Length", "0"))
        body = self.rfile.read(length) if length else b""
        if self.path == "/swap":
            self._handle_swap(body, rid)
            return
        if self.path != "/predict":
            self._send_json(404, {"error": f"unknown path {self.path}"})
            return
        try:
            doc = json.loads(body or b"{}")
            graphs_doc = doc.get("graphs")
            if not isinstance(graphs_doc, list) or not graphs_doc:
                raise ValueError('body must be {"graphs": [<graph>, ...]}')
            samples = [parse_graph(g) for g in graphs_doc]
        except (ValueError, TypeError, json.JSONDecodeError) as e:
            self._send_json(400, {"error": str(e), "request_id": rid})
            return

        engine = self.engine
        try:
            results, versions = engine.predict_versioned(
                samples,
                timeout=getattr(self.server, "request_timeout_s", 60.0),
                request_id=rid,
            )
        except BackpressureError as e:
            self._send_json(
                429,
                {
                    "error": str(e),
                    "retry_after_s": e.retry_after_s,
                    "request_id": rid,
                },
                headers={"Retry-After": f"{max(1, round(e.retry_after_s))}"},
            )
            return
        except (ValueError, TypeError) as e:  # per-graph validation
            self._send_json(400, {"error": str(e), "request_id": rid})
            return
        except TimeoutError as e:
            self._send_json(504, {"error": str(e), "request_id": rid})
            return
        except (EngineFailedError, RuntimeError) as e:
            # NonFiniteOutputError lands here too (RuntimeError subclass):
            # the failing request's 503 still carries its correlation id.
            self._send_json(503, {"error": str(e), "request_id": rid})
            return

        self._finish_predict(rid, results, versions)

    def _handle_swap(self, body: bytes, rid: str) -> None:
        """POST /swap — the fleet-orchestration admin endpoint (ROADMAP item
        4 remainder): ``{"checkpoint": <path>, "version"?: <str>,
        "expected_identity"?: <hex>}`` loads the named v2 checkpoint from
        THIS replica's filesystem (shared storage in a fleet) and hot-swaps
        it through ``engine.swap_weights`` — zero recompiles, per-request
        version consistency, the ``X-HydraGNN-Model-Version`` header flips
        on the next response. Gated behind ``--admin`` (serving replicas
        must opt in to being driven): 403 otherwise. Refusals keep serving:
        409 on identity/fingerprint/tolerance-gate mismatches, 400 on a
        missing/corrupt file, 503 on a dead engine."""
        if not getattr(self.server, "allow_admin", False):  # type: ignore[attr-defined]
            self._send_json(
                403,
                {
                    "error": "/swap is disabled — start the replica with "
                    "--admin to allow lifecycle orchestration",
                    "request_id": rid,
                },
            )
            return
        from ..checkpoint.format import CheckpointError
        from .engine import (
            PrecisionToleranceError,
            SwapFingerprintError,
            SwapIdentityError,
            swap_from_checkpoint,
        )

        try:
            doc = json.loads(body or b"{}")
            path = doc.get("checkpoint")
            if not isinstance(path, str) or not path:
                raise ValueError(
                    'body must be {"checkpoint": "<path>", "version"?: ..., '
                    '"expected_identity"?: ...}'
                )
        except (ValueError, TypeError, json.JSONDecodeError) as e:
            self._send_json(400, {"error": str(e), "request_id": rid})
            return
        try:
            report = swap_from_checkpoint(
                self.engine,
                path,
                version=doc.get("version"),
                expected_identity=doc.get("expected_identity"),
            )
        except (
            SwapIdentityError,
            SwapFingerprintError,
            PrecisionToleranceError,
        ) as e:
            self._send_json(409, {"error": str(e), "request_id": rid})
            return
        except CheckpointError as e:
            # Corrupt/unreadable/wrong-format file: the candidate is bad, the
            # replica keeps serving.
            self._send_json(400, {"error": str(e), "request_id": rid})
            return
        except OSError as e:
            self._send_json(400, {"error": str(e), "request_id": rid})
            return
        except (EngineFailedError, RuntimeError) as e:
            self._send_json(503, {"error": str(e), "request_id": rid})
            return
        self._mv_override = report["version"]
        self._send_json(200, {"request_id": rid, "swapped": True, **report})

    def _finish_predict(self, rid: str, results, versions) -> None:
        engine = self.engine
        # The header (and body field) report the version that actually
        # answered: the newest version any of the call's graphs executed
        # against — for single-graph requests (the swap drill's shape) this
        # is exact; a multi-graph call legitimately spanning a swap reports
        # the newer version and carries the per-graph tags in the body.
        call_versions = [v for v in versions if v]
        if call_versions:
            self._mv_override = call_versions[-1]
        self._send_json(
            200,
            {
                "request_id": rid,
                "model_version": call_versions[-1] if call_versions else None,
                "model_versions": versions,
                # A token family's class head replies one log-probability a
                # token, not its `dim` logits (docs/SERVING.md).
                "heads": [
                    {"name": name, "type": htype, "dim": int(dim)}
                    if ihead not in engine.model.scored_heads
                    else {"name": name, "type": htype, "dim": 1,
                          "classes": int(dim), "reply": "next_token_logprob"}
                    for ihead, (name, htype, dim) in enumerate(zip(
                        engine.head_names,
                        engine.model.output_type,
                        engine.model.output_dim,
                    ))
                ],
                "predictions": [
                    [np.asarray(h).tolist() for h in per_graph]
                    for per_graph in results
                ],
            },
        )


class InferenceServer:
    """ThreadingHTTPServer wrapper owning one engine.

    ``port=0`` binds an ephemeral port (tests); ``.port`` reports the bound
    one. ``serve_forever`` blocks; ``start_background`` runs it on a daemon
    thread and returns immediately.
    """

    def __init__(
        self,
        engine: InferenceEngine,
        host: str = "127.0.0.1",
        port: int = 8000,
        request_timeout_s: float = 60.0,
        verbose: bool = False,
        replica_id: Optional[str] = None,
        enable_admin: bool = False,
    ):
        self.engine = engine
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.engine = engine  # type: ignore[attr-defined]
        # /swap fleet orchestration (docs/SERVING.md "Live model
        # lifecycle"): replicas must OPT IN to being driven — the endpoint
        # loads checkpoints from this process's filesystem.
        self._httpd.allow_admin = bool(enable_admin)  # type: ignore[attr-defined]
        # Every response path names the serving model version (the
        # lifecycle echo contract — see RequestPlumbing._model_version).
        self._httpd.model_version_fn = lambda: engine.model_version  # type: ignore[attr-defined]
        self._httpd.verbose = verbose  # type: ignore[attr-defined]
        self._httpd.request_timeout_s = request_timeout_s  # type: ignore[attr-defined]
        self._httpd.replica_id = replica_id  # type: ignore[attr-defined]
        self._thread: Optional[threading.Thread] = None

    @property
    def host(self) -> str:
        return self._httpd.server_address[0]

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    def serve_forever(self) -> None:
        self._httpd.serve_forever()

    def start_background(self) -> "InferenceServer":
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="hydragnn-serve-http",
            daemon=True,
        )
        self._thread.start()
        return self

    def shutdown(self, close_engine: bool = True) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(5.0)
        if close_engine:
            self.engine.close()
