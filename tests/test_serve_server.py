"""HTTP front end (hydragnn_tpu/serve/server.py) — localhost end-to-end smoke
of /predict, /healthz, and /metrics, plus the error paths (400 malformed,
404 unknown route, 429 backpressure with Retry-After). Tier-1, CPU."""

import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as ge
from hydragnn_tpu.graphs import collate_graphs
from hydragnn_tpu.models import init_model_variables
from hydragnn_tpu.serve import InferenceEngine, InferenceServer


def _engine(**options):
    rng = np.random.default_rng(3)
    graphs = ge._make_graphs(6, rng)
    model = ge._build_model(hidden=8, layers=2)
    batch = collate_graphs(graphs[:2], ge.TYPES, ge.DIMS, edge_dim=1)
    variables = init_model_variables(model, batch)
    options.setdefault("max_batch_graphs", 4)
    options.setdefault("max_delay_ms", 10.0)
    return InferenceEngine(model, variables, **options), graphs


def _graph_doc(g):
    return {
        "x": np.asarray(g.x).tolist(),
        "edge_index": np.asarray(g.edge_index).tolist(),
        "edge_attr": np.asarray(g.edge_attr).tolist(),
    }


def _post(url, doc):
    body = json.dumps(doc).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, json.loads(resp.read())


@pytest.mark.mpi_skip
def pytest_serve_http_predict_healthz_metrics_end_to_end():
    engine, graphs = _engine()
    server = InferenceServer(engine, port=0).start_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        status, doc = _post(
            base + "/predict", {"graphs": [_graph_doc(g) for g in graphs[:2]]}
        )
        assert status == 200
        assert [h["type"] for h in doc["heads"]] == ["graph", "node"]
        assert len(doc["predictions"]) == 2
        # Per-head shapes: graph head [1], node head [n, 1].
        for g, per_head in zip(graphs[:2], doc["predictions"]):
            assert np.asarray(per_head[0]).shape == (1,)
            assert np.asarray(per_head[1]).shape == (g.num_nodes, 1)

        with urllib.request.urlopen(base + "/healthz", timeout=10) as resp:
            health = json.loads(resp.read())
        assert health["ok"] is True and health["compiled_buckets"] >= 1
        # Where the weights live, as JAX reports it (never a hidden device).
        assert health["device"]["platform"] == "cpu"
        assert health["device"]["visible"] >= 1
        # The fault-tolerance surface: healthy AND un-degraded, with the
        # restart/bad-batch counters exposed (docs/FAULT_TOLERANCE.md).
        assert health["degraded"] is False
        assert health["bad_batches"] == 0 and health["restarts"] == 0

        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        assert "hydragnn_serve_requests_total 2" in text
        assert 'hydragnn_serve_latency_seconds_bucket{stage="e2e"' in text
        assert "hydragnn_serve_bucket_cache_misses_total 1" in text
        assert "hydragnn_serve_bad_batches_total 0" in text
        assert "hydragnn_serve_engine_restarts_total 0" in text

        # Serving seconds surface in the shared Timer registry too.
        from hydragnn_tpu.utils.time_utils import Timer

        assert Timer.snapshot().get("serve_e2e", 0.0) > 0.0
    finally:
        server.shutdown()


@pytest.mark.mpi_skip
def pytest_serve_http_error_paths():
    engine, graphs = _engine()
    server = InferenceServer(engine, port=0).start_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base + "/predict", {"graphs": [{"nope": 1}]})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base + "/predict", {"graphs": []})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/nothing", timeout=10)
        assert e.value.code == 404
    finally:
        server.shutdown()


@pytest.mark.mpi_skip
def pytest_serve_http_backpressure_returns_429_with_retry_after():
    # No worker (autostart=False) + a tiny queue: the HTTP layer must shed
    # load as 429 + Retry-After, not block.
    engine, graphs = _engine(queue_limit=1, autostart=False)
    engine.submit(graphs[0])  # occupy the single queue slot
    server = InferenceServer(engine, port=0).start_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base + "/predict", {"graphs": [_graph_doc(graphs[1])]})
        assert e.value.code == 429
        assert int(e.value.headers["Retry-After"]) >= 1
        assert json.loads(e.value.read())["retry_after_s"] > 0

        # healthz reports not-running for a stopped engine.
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/healthz", timeout=10)
        assert e.value.code == 503
    finally:
        server.shutdown()
