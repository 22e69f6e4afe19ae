"""Online inference engine (hydragnn_tpu/serve/engine.py) — tier-1, CPU.

Covers the serving subsystem's contracts:
  * numerical parity with the offline ``run_prediction`` path — BIT-exact on
    CPU when the engine is driven at the offline loader's bucket shapes;
  * micro-batch flush semantics (deadline flush vs max-batch flush);
  * backpressure rejection on a full bounded queue (retry-after hint);
  * worker-exception propagation to callers + engine poisoning;
  * compiled-executable (bucket) cache reuse and ladder warmup — the
    "zero recompiles after warmup" steady-state property.
"""

import dataclasses
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as ge
import hydragnn_tpu as hydragnn
from hydragnn_tpu.graphs import collate_graphs
from hydragnn_tpu.models import init_model_variables
from hydragnn_tpu.serve import (
    BackpressureError,
    EngineClosedError,
    EngineFailedError,
    InferenceEngine,
    NonFiniteOutputError,
)


def _tiny_engine(**options):
    """Small PNA (graph+node heads, edge features) with random init — the
    engine's behavior under test is orchestration, not accuracy."""
    rng = np.random.default_rng(0)
    graphs = ge._make_graphs(12, rng)
    model = ge._build_model(hidden=8, layers=2)
    batch = collate_graphs(graphs[:2], ge.TYPES, ge.DIMS, edge_dim=1)
    variables = init_model_variables(model, batch)
    options.setdefault("max_batch_graphs", 8)
    options.setdefault("max_delay_ms", 30.0)
    return InferenceEngine(model, variables, **options), graphs


# --------------------------------------------------------------------- parity
@pytest.mark.mpi_skip
def pytest_engine_matches_run_prediction_bit_exact():
    """Same checkpoint, same graphs, same bucket shapes → engine outputs are
    bit-identical to run_prediction's predicted_values on CPU. (Bit-exactness
    REQUIRES matching padded shapes — XLA:CPU matmul tiling varies with
    N_pad — which is exactly what the bucket ladder provides.)"""
    from tests.test_graphs import load_ci_config, unittest_train_model
    from hydragnn_tpu.preprocess.load_data import dataset_loading_and_splitting
    from hydragnn_tpu.utils.config_utils import update_config

    os.environ["SERIALIZED_DATA_PATH"] = os.getcwd()
    model_type = "PNA"
    config = load_ci_config("ci.json", model_type)

    # Reuse the committed/previously-trained checkpoint when present (the
    # test_model_loadpred convention), else train the cell now.
    log_name = hydragnn.utils.get_log_name_config(config)
    modelfile = os.path.join("./logs/", log_name, log_name + ".pk")
    snapshot = os.path.join("./logs/", log_name, "config.json")
    case_exist = os.path.isfile(modelfile) and os.path.isfile(snapshot)
    if case_exist:
        with open(snapshot) as f:
            config = json.load(f)
        case_exist = all(
            os.path.isfile(p) or os.path.isdir(p)
            for p in config["Dataset"]["path"].values()
        )
    if not case_exist:
        unittest_train_model(model_type, "ci.json", False)
        with open(snapshot) as f:
            config = json.load(f)

    from hydragnn_tpu.checkpoint.format import CheckpointError

    # ./logs is shared with the other test files, and tests/test_graphs.py
    # trains this same log name with ANOTHER parameter tree (edge lengths on)
    # in another worker: a checkpoint overwritten between the snapshot's read
    # and the engine's load is a fingerprint mismatch. Then wait for the
    # writer to finish and start again from the snapshot it left.
    for attempt in range(3):
        try:
            _, _, _, predicted_values = hydragnn.run_prediction(config)

            train_loader, val_loader, test_loader, _ = dataset_loading_and_splitting(
                config=config
            )
            config = update_config(config, train_loader, val_loader, test_loader)
            batch_size = config["NeuralNetwork"]["Training"]["batch_size"]
            n_pad, e_pad, _ = test_loader.pad_sizes

            engine = InferenceEngine.from_config(
                config,
                max_batch_graphs=batch_size,  # G_pad = batch_size + 1, like the loader
                max_delay_ms=500.0,
                bucket_ladder=[(n_pad, e_pad)],
                warmup=True,
            )
            break
        except CheckpointError:
            if attempt == 2:
                raise
            time.sleep(20.0)
            with open(snapshot) as f:
                config = json.load(f)
    try:
        compiles_after_warmup = engine.metrics.snapshot()["bucket_cache"][
            "misses"
        ]
        # Same batch membership as the eval loader: dataset order, chunks of
        # batch_size (shuffle=False, single bucket).
        dataset = list(test_loader.dataset)
        results = []
        for start in range(0, len(dataset), batch_size):
            results.extend(
                engine.predict(dataset[start : start + batch_size])
            )
        snap = engine.metrics.snapshot()
        assert snap["bucket_cache"]["misses"] == compiles_after_warmup, (
            "steady-state traffic recompiled despite warmup",
            snap["bucket_cache"],
        )
        assert snap["bucket_cache"]["ladder_fallbacks"] == 0
        for ihead, htype in enumerate(engine.model.output_type):
            offline = np.asarray(predicted_values[ihead])
            online = np.concatenate(
                [np.atleast_2d(r[ihead]) for r in results]
            ).reshape(offline.shape)
            np.testing.assert_array_equal(
                online,
                offline,
                err_msg=f"head {ihead} ({htype}): engine diverges from "
                "run_prediction",
            )
    finally:
        engine.close()


# ------------------------------------------------------------ flush semantics
@pytest.mark.mpi_skip
def pytest_deadline_flush_resolves_partial_batch():
    engine, graphs = _tiny_engine(max_batch_graphs=64, max_delay_ms=150.0)
    try:
        t0 = time.perf_counter()
        futures = [engine.submit(g) for g in graphs[:3]]
        outs = [f.result(timeout=30.0) for f in futures]
        elapsed = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        # One partial batch, flushed by the deadline — never by size.
        assert snap["batches_total"] == 1 and snap["graphs_total"] == 3
        assert snap["batch_occupancy_mean"] < 0.5
        # The flush waited for batch-mates: resolution cannot beat the
        # deadline (compile time only ADDS to it).
        assert elapsed >= 0.10, elapsed
        assert all(len(o) == len(engine.model.output_type) for o in outs)
    finally:
        engine.close()


@pytest.mark.mpi_skip
def pytest_max_batch_flush_preempts_deadline():
    engine, graphs = _tiny_engine(max_batch_graphs=4, max_delay_ms=60_000.0)
    try:
        futures = [engine.submit(g) for g in graphs[:4]]
        [f.result(timeout=30.0) for f in futures]  # << the 60 s deadline
        snap = engine.metrics.snapshot()
        assert snap["batches_total"] == 1 and snap["graphs_total"] == 4
        assert snap["batch_occupancy_mean"] == 1.0
    finally:
        engine.close()


# --------------------------------------------------------------- backpressure
@pytest.mark.mpi_skip
def pytest_backpressure_rejects_when_queue_full():
    # autostart=False: no consumer, so the bounded queue actually fills.
    engine, graphs = _tiny_engine(queue_limit=3, autostart=False)
    accepted = [engine.submit(g) for g in graphs[:3]]
    with pytest.raises(BackpressureError) as exc_info:
        engine.submit(graphs[3])
    assert exc_info.value.retry_after_s > 0
    snap = engine.metrics.snapshot()
    assert snap["rejected_total"] == 1 and snap["requests_total"] == 3
    # Shutdown fails the queued (never-batched) requests loudly.
    engine.close()
    for fut in accepted:
        with pytest.raises(EngineClosedError):
            fut.result(timeout=5.0)
    with pytest.raises(EngineClosedError):
        engine.submit(graphs[0])


@pytest.mark.mpi_skip
def pytest_invalid_request_rejected_at_submit():
    engine, graphs = _tiny_engine()
    try:
        from hydragnn_tpu.graphs.sample import GraphSample

        bad = GraphSample(x=np.zeros((3, 99), np.float32))
        with pytest.raises(ValueError, match="input_dim"):
            engine.submit(bad)
        # Edge-feature contract: the model consumes edge_attr (edge_dim=1);
        # a missing or wrong-width attr must reject at admission, not
        # zero-fill silently or blow up collation mid-batch.
        g = graphs[0]
        no_attr = GraphSample(x=g.x, pos=g.pos, edge_index=g.edge_index)
        with pytest.raises(ValueError, match="edge_attr"):
            engine.submit(no_attr)
        wide = GraphSample(
            x=g.x,
            pos=g.pos,
            edge_index=g.edge_index,
            edge_attr=np.zeros((g.num_edges, 3), np.float32),
        )
        with pytest.raises(ValueError, match="edge_attr"):
            engine.submit(wide)
        # Bad requests must not poison the engine for everyone else.
        assert engine.predict(graphs[:1])[0] is not None
    finally:
        engine.close()


@pytest.mark.mpi_skip
def pytest_collation_failure_fails_batch_not_engine():
    """A batch that fails on the collation (host) stage rejects ITS requests
    with the original error but leaves the engine serving — only
    transfer/dispatch-stage failures poison it."""
    engine, graphs = _tiny_engine(max_delay_ms=10.0)
    real_collate = engine._collate
    calls = {"n": 0}

    def flaky(entries, ladder=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ValueError("injected collation failure")
        return real_collate(entries, ladder)

    engine._collate = flaky
    try:
        fut = engine.submit(graphs[0])
        with pytest.raises(ValueError, match="injected collation failure"):
            fut.result(timeout=30.0)
        assert engine.metrics.snapshot()["errors_total"] == 1
        # Engine still alive and serving.
        assert engine.predict(graphs[:2])[0] is not None
        assert engine._error is None
    finally:
        engine.close()


# --------------------------------------------------- prepared once, at submit
def _shuffled_edges(sample, seed=1):
    """The sample with its edge list in no order (attrs along)."""
    order = np.random.default_rng(seed).permutation(sample.num_edges)
    out = sample.clone()
    out.edge_index = np.ascontiguousarray(out.edge_index[:, order])
    out.edge_attr = np.ascontiguousarray(out.edge_attr[order])
    return out


@pytest.mark.mpi_skip
def pytest_submit_prepares_every_request_and_leaves_the_sample_alone(monkeypatch):
    """A request is made ready in ``submit()``, every time (the same object
    sent twice is prepared twice: no memo), on a copy: the caller's sample
    keeps its very arrays. The ``prepare`` clock counts each admitted request
    and the stages still add up to ``e2e``."""
    from hydragnn_tpu.serve import engine as engine_module

    calls = []
    real_prepare = engine_module.prepare_graph

    def counted(sample, **kw):
        calls.append(id(sample))
        return real_prepare(sample, **kw)

    monkeypatch.setattr(engine_module, "prepare_graph", counted)
    engine, graphs = _tiny_engine(max_batch_graphs=4, max_delay_ms=10.0)
    try:
        g = _shuffled_edges(graphs[0])
        receivers = g.edge_index[1]
        assert not (receivers[1:] >= receivers[:-1]).all()
        held = {f.name: getattr(g, f.name) for f in dataclasses.fields(g)}
        kept = g.clone()
        first = engine.submit(g).result(timeout=30.0)
        second = engine.submit(g).result(timeout=30.0)
        for name, then in held.items():
            now = getattr(g, name)
            assert now is then, name
            assert now is None or np.array_equal(now, getattr(kept, name)), name
        assert calls == [id(g), id(g)]
        for a, b in zip(first, second):
            assert np.array_equal(a, b)
        # The order the edges arrive in is no part of the answer.
        for a, b in zip(first, engine.predict([graphs[0]])[0]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

        order = np.argsort(receivers, kind="stable")
        tidy = g.clone()
        tidy.edge_index, tidy.edge_attr = g.edge_index[:, order], g.edge_attr[order]
        engine.submit(tidy).result(timeout=30.0)

        snap = engine.metrics.snapshot()
        stages = snap["latency_ms"]
        assert snap["requests_total"] == stages["prepare"]["count"] == len(calls)
        assert snap["presorted_total"] >= 1  # ``tidy``; graphs[0] if built sorted
        assert snap["presorted_total"] <= 2
        # One request a flush here, so a request's stages are its flush's:
        # they add to no more than submit -> resolution.
        parts = ("prepare", "queue_wait", "collate", "h2d", "device")
        assert all(stages[p]["count"] == 4 for p in parts + ("e2e",))
        assert sum(stages[p]["sum_s"] for p in parts) <= stages["e2e"]["sum_s"] + 1e-3
        text = engine.metrics.render_prometheus()
        assert 'hydragnn_serve_latency_seconds_count{stage="prepare"} 4' in text
        assert f"hydragnn_serve_presorted_total {snap['presorted_total']}" in text
    finally:
        engine.close()


@pytest.mark.mpi_skip
def pytest_concurrent_submits_get_the_serial_replies():
    """64 callers preparing and submitting at once (more threads than cores,
    a shortened switch interval) each get the reply a serial ``predict`` of
    their graph gives, and every request was prepared exactly once."""
    engine, graphs = _tiny_engine(
        max_batch_graphs=16, max_delay_ms=20.0, queue_limit=128,
        bucket_ladder=[(512, 8192)],
    )
    try:
        graphs = [_shuffled_edges(g, seed=i) for i, g in enumerate(graphs)]
        serial = [engine.predict([g])[0] for g in graphs]
        picks = [slot % len(graphs) for slot in range(64)]
        replies, errors, gate = [None] * len(picks), [], threading.Event()

        def call(slot):
            gate.wait(10.0)
            try:
                fut = engine.submit(graphs[picks[slot]])
                replies[slot] = fut.result(timeout=60.0)
            except Exception as e:  # noqa: BLE001 -- reported below
                errors.append(repr(e))

        threads = [threading.Thread(target=call, args=(s,)) for s in range(len(picks))]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            gate.set()
            for t in threads:
                t.join(timeout=120.0)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads) and not errors, errors
        for slot, reply in enumerate(replies):
            for a, b in zip(reply, serial[picks[slot]]):
                assert np.array_equal(a, b), slot
        snap = engine.metrics.snapshot()
        assert snap["requests_total"] == len(graphs) + len(picks)
        assert snap["latency_ms"]["prepare"]["count"] == snap["requests_total"]
        assert snap["bucket_cache"]["ladder_fallbacks"] == 0
    finally:
        engine.close()


# ------------------------------------------------------ exception propagation
@pytest.mark.mpi_skip
def pytest_worker_exception_reraises_at_caller_and_poisons_engine():
    engine, graphs = _tiny_engine(max_delay_ms=10.0)

    def boom(dev_batch):
        raise RuntimeError("injected device failure")

    engine._execute = boom  # the dispatch-stage seam
    fut = engine.submit(graphs[0])
    with pytest.raises(RuntimeError, match="injected device failure"):
        fut.result(timeout=30.0)
    # The engine is poisoned: subsequent submits re-raise the original
    # error as the cause instead of silently queueing into a dead worker.
    with pytest.raises(EngineFailedError) as exc_info:
        engine.submit(graphs[1])
    assert "injected device failure" in str(exc_info.value.__cause__)
    assert engine.metrics.snapshot()["errors_total"] == 1
    engine.close()


# ------------------------------------------------- fault tolerance (serving)
@pytest.mark.mpi_skip
def pytest_nonfinite_output_fails_request_not_engine():
    """The serving reuse of the non-finite guard: a NaN model output fails
    THAT request with NonFiniteOutputError; the engine stays running (marked
    degraded, counters incremented) and later requests serve normally."""
    engine, graphs = _tiny_engine(max_delay_ms=10.0)
    real_execute = engine._execute
    state = {"poison": True}

    def nan_once(dev_batch):
        outputs, version = real_execute(dev_batch)
        if state.pop("poison", False):
            outputs = [np.full_like(o, np.nan) for o in outputs]
        return outputs, version

    engine._execute = nan_once
    try:
        fut = engine.submit(graphs[0])
        with pytest.raises(NonFiniteOutputError):
            fut.result(timeout=30.0)
        assert engine.running and engine._error is None
        assert engine.degraded is True
        snap = engine.metrics.snapshot()
        assert snap["nonfinite_total"] == 1
        assert snap["bad_batches_total"] == 1
        # Subsequent traffic is unaffected.
        out = engine.predict(graphs[1:3])
        assert all(np.isfinite(np.asarray(h)).all() for r in out for h in r)
    finally:
        engine.close()


@pytest.mark.mpi_skip
def pytest_resolution_failure_is_batch_scoped_not_fatal():
    """A failure in per-request post-processing (the resolve stage) fails the
    batch's futures with the original error but keeps the engine serving —
    only device/compile failures are engine-fatal."""
    engine, graphs = _tiny_engine(max_delay_ms=10.0)
    real_denorm = engine._denormalize
    calls = {"n": 0}

    def flaky(ihead, value):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ValueError("injected postprocess failure")
        return real_denorm(ihead, value)

    engine._denormalize = flaky
    try:
        fut = engine.submit(graphs[0])
        with pytest.raises(ValueError, match="injected postprocess failure"):
            fut.result(timeout=30.0)
        assert engine.running and engine._error is None
        assert engine.degraded is True
        assert engine.metrics.snapshot()["bad_batches_total"] == 1
        assert engine.predict(graphs[1:2])[0] is not None
    finally:
        engine.close()


@pytest.mark.mpi_skip
def pytest_worker_restart_budget_recovers_then_poisons():
    """max_worker_restarts=1: the first fatal worker error fails the
    in-flight futures but RESTARTS the pipeline (degraded, counter bumped,
    traffic continues); the second exhausts the budget and poisons the
    engine exactly like the historical behavior."""
    engine, graphs = _tiny_engine(max_delay_ms=10.0, max_worker_restarts=1)
    real_execute = engine._execute
    state = {"fail": True}

    def fail_once(dev_batch):
        if state.pop("fail", False):
            raise RuntimeError("injected device failure")
        return real_execute(dev_batch)

    engine._execute = fail_once
    try:
        fut = engine.submit(graphs[0])
        with pytest.raises(RuntimeError, match="injected device failure"):
            fut.result(timeout=30.0)
        # Restarted, not poisoned: still accepting and serving.
        deadline = time.perf_counter() + 10.0
        while not engine.running and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert engine.running and engine._error is None
        assert engine.degraded is True
        assert engine.metrics.snapshot()["engine_restarts_total"] == 1
        assert engine.predict(graphs[1:3])[0] is not None

        # Budget exhausted: next fatal error poisons.
        state["fail"] = True
        fut = engine.submit(graphs[0])
        with pytest.raises(RuntimeError, match="injected device failure"):
            fut.result(timeout=30.0)
        with pytest.raises(EngineFailedError):
            engine.submit(graphs[1])
    finally:
        engine.close()


# ----------------------------------------------------------- executable cache
@pytest.mark.mpi_skip
def pytest_bucket_cache_reuses_compiled_executable():
    engine, graphs = _tiny_engine(max_batch_graphs=2, max_delay_ms=10.0)
    try:
        engine.predict(graphs[:1])
        engine.predict(graphs[:1])  # same graph → same pow2 bucket
        snap = engine.metrics.snapshot()
        assert snap["bucket_cache"]["misses"] == 1, snap["bucket_cache"]
        assert snap["bucket_cache"]["hits"] == 1, snap["bucket_cache"]

        # A much larger graph lands in a different bucket → second compile.
        rng = np.random.default_rng(7)
        big = ge._make_graphs(1, rng, n_lo=200, n_hi=201)[0]
        engine.predict([big])
        snap = engine.metrics.snapshot()
        assert snap["bucket_cache"]["misses"] == 2, snap["bucket_cache"]
    finally:
        engine.close()


@pytest.mark.mpi_skip
def pytest_warmup_precompiles_ladder_no_steady_state_compiles():
    engine, graphs = _tiny_engine(
        max_batch_graphs=4,
        max_delay_ms=10.0,
        bucket_ladder=[(256, 2048)],
        warmup=True,
    )
    try:
        snap = engine.metrics.snapshot()
        assert snap["bucket_cache"]["misses"] == 1  # compiled at construction
        for start in (0, 4, 8):
            engine.predict(graphs[start : start + 4])
        snap = engine.metrics.snapshot()
        assert snap["bucket_cache"]["misses"] == 1, (
            "traffic recompiled despite warmup",
            snap["bucket_cache"],
        )
        assert snap["bucket_cache"]["hits"] == 3
        assert snap["bucket_cache"]["ladder_fallbacks"] == 0
        assert snap["padding_waste_nodes_mean"] is not None
    finally:
        engine.close()
