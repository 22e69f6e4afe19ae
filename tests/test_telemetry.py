"""graftel (hydragnn_tpu/telemetry/) — unified tracing, flight recorder,
and cross-layer telemetry (docs/OBSERVABILITY.md). Tier-1, CPU.

Covers the acceptance criteria of the graftel PR: a serve request's
correlation id traceable HTTP ingress → pack bin → device batch → demux →
response header; a deliberately injected ``nan_grad@K`` drill producing a
flight-recorder dump whose span timeline includes the offending step's
collate/h2d/device spans; dump triggers for engine poisoning, checkpoint
fallback, and supervisor restarts (each schema-validated); and the JSONL +
Chrome-trace (Perfetto) exporters of a short traced train run loading back.
"""

import glob
import json
import os
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as ge
from hydragnn_tpu import telemetry
from hydragnn_tpu.faults import FaultCounters, FaultPlan
from hydragnn_tpu.graphs import collate_graphs
from hydragnn_tpu.graphs.sample import GraphSample
from hydragnn_tpu.models import create_model, init_model_variables
from hydragnn_tpu.preprocess.dataloader import GraphDataLoader
from hydragnn_tpu.serve import InferenceEngine, InferenceServer
from hydragnn_tpu.train.train_validate_test import TrainingDriver
from hydragnn_tpu.train.trainer import create_train_state
from hydragnn_tpu.utils.optimizer import select_optimizer
from hydragnn_tpu.utils.time_utils import Timer


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Process-global tracer: every test starts from module defaults and
    leaves no run_dir/collect state behind for unrelated suites."""
    telemetry.reset()
    yield
    telemetry.reset()


HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 4,
        "num_headlayers": 1,
        "dim_headlayers": [4],
    },
}


def _dataset(rng, count=12, lo=4, hi=10):
    graphs = []
    for _ in range(count):
        n = int(rng.integers(lo, hi))
        x = rng.normal(size=(n, 1)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        graphs.append(
            GraphSample(
                x=x, pos=np.zeros((n, 3), np.float32),
                y=np.array([x.sum()], np.float32),
                y_loc=np.array([[0, 1]], np.int64), edge_index=ei,
            )
        )
    return graphs


def _loader(graphs, **kw):
    kw.setdefault("batch_size", 4)
    kw.setdefault("shuffle", False)
    loader = GraphDataLoader(graphs, **kw)
    loader.set_head_spec(("graph",), (1,))
    return loader


def _driver_for(loader, ft=None, plan=None):
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    variables = init_model_variables(model, next(iter(loader)))
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    return TrainingDriver(model, opt, state, fault_tolerance=ft, fault_plan=plan)


def _serve_engine(**options):
    rng = np.random.default_rng(3)
    graphs = ge._make_graphs(6, rng)
    model = ge._build_model(hidden=8, layers=2)
    batch = collate_graphs(graphs[:2], ge.TYPES, ge.DIMS, edge_dim=1)
    variables = init_model_variables(model, batch)
    options.setdefault("max_batch_graphs", 4)
    options.setdefault("max_delay_ms", 10.0)
    return InferenceEngine(model, variables, **options), graphs


# ----------------------------------------------------------- span primitives
def pytest_span_nesting_and_cross_thread_handoff():
    """Same-thread nesting parents via the thread-local stack; cross-thread
    propagation requires the EXPLICIT handoff (capture ctx, attach on the
    receiving thread) — a bare thread sees no parent."""
    telemetry.configure(collect=True)
    with telemetry.span("outer") as outer:
        with telemetry.span("inner"):
            pass
        captured = outer.ctx

        seen = {}

        def bare():
            seen["bare"] = telemetry.current()
            with telemetry.span("on-thread-bare"):
                pass

        def handed():
            telemetry.attach(captured)
            seen["handed"] = telemetry.current()
            with telemetry.span("on-thread-handed"):
                pass

        for fn in (bare, handed):
            t = threading.Thread(target=fn)
            t.start()
            t.join(10)

    recs = {r["name"]: r for r in telemetry.collected_records()}
    assert recs["inner"]["parent_id"] == recs["outer"]["span_id"]
    assert seen["bare"] is None
    assert recs["on-thread-bare"]["parent_id"] is None
    assert seen["handed"] is captured
    assert recs["on-thread-handed"]["parent_id"] == captured.span_id
    # Request ids inherit down the context chain.
    with telemetry.span("req-root", request_id="r-abc"):
        with telemetry.span("req-child"):
            pass
    recs = {r["name"]: r for r in telemetry.collected_records()}
    assert recs["req-child"]["request_id"] == "r-abc"


def pytest_ring_bounded_and_flight_dump_schema(tmp_path):
    """The flight recorder is a bounded window: flooding it never grows
    memory, and a dump is schema-valid with the trigger + registry
    snapshot."""
    telemetry.configure(run_dir=str(tmp_path))
    for i in range(5000):
        telemetry.event("flood", i=i)
    assert len(telemetry.snapshot_records()) <= 4096
    telemetry.counter("drill/things", 3)
    telemetry.gauge("drill/level", 0.5)
    path = telemetry.flight_dump("unit_drill", extra={"k": "v"})
    assert path is not None and os.path.exists(path)
    assert telemetry.validate_flight_file(path) == []
    with open(path) as f:
        doc = json.load(f)
    assert doc["trigger"] == "unit_drill"
    assert doc["extra"] == {"k": "v"}
    assert doc["counters"]["drill/things"] == 3
    assert doc["gauges"]["drill/level"] == 0.5
    # No configured/explicit run dir -> silent no-op, not an exception.
    telemetry.configure(run_dir=None)
    telemetry.reset()
    assert telemetry.flight_dump("nowhere") is None


def pytest_one_registry_for_timer_faultcounters_prometheus():
    """The retrofit claim: Timer and FaultCounters STORE into the graftel
    registry, and render_prometheus exposes the same numbers (training
    gauges included)."""
    Timer.reset()
    FaultCounters.reset()
    Timer.credit("unit_phase", 1.5)
    FaultCounters.inc("unit_faults", 2)
    telemetry.gauge("train/step_s_per_epoch", 0.25)
    assert telemetry.counters_snapshot("timer/")["timer/unit_phase"] == 1.5
    assert telemetry.counters_snapshot("fault/")["fault/unit_faults"] == 2
    assert Timer.snapshot()["unit_phase"] == 1.5
    assert FaultCounters.get("unit_faults") == 2
    text = telemetry.render_prometheus()
    assert "hydragnn_timer_unit_phase_total 1.5" in text
    assert "hydragnn_fault_unit_faults_total 2" in text
    assert "hydragnn_train_step_s_per_epoch 0.25" in text
    # FaultCounters increments also land in the event stream (the flight
    # recorder shows WHICH survival mechanism fired).
    names = [r["name"] for r in telemetry.snapshot_records()]
    assert "fault/unit_faults" in names
    Timer.reset()
    FaultCounters.reset()
    assert Timer.snapshot() == {}
    assert FaultCounters.snapshot() == {}


def pytest_disabled_tracer_keeps_registry_but_drops_records():
    telemetry.configure(enabled=False, collect=True)
    with telemetry.span("dropped"):
        pass
    telemetry.event("dropped-too")
    Timer.credit("still_counted", 1.0)
    assert telemetry.collected_records() == []
    assert telemetry.snapshot_records() == []
    assert Timer.snapshot()["still_counted"] == 1.0


# ------------------------------------------------- flight-recorder triggers
def pytest_nan_grad_drill_dump_has_offending_step_spans(tmp_path):
    """ACCEPTANCE: a deliberately injected ``nan_grad@2`` drill trips the
    non-finite guard, and the flight-recorder dump's span timeline includes
    the offending step's collate/h2d/device spans."""
    telemetry.configure(run_dir=str(tmp_path))
    rng = np.random.default_rng(0)
    loader = _loader(_dataset(rng))
    d = _driver_for(
        loader,
        ft={"enabled": True, "max_bad_steps": 99},
        plan=FaultPlan("nan_grad@2"),
    )
    d.scan_chunk = 1  # per-batch dispatch: span indices == fed batch indices
    d.train_epoch(loader)
    dumps = glob.glob(str(tmp_path / "flightrec_*_guard_trip.json"))
    assert len(dumps) == 1, "one dump per bad streak"
    assert telemetry.validate_flight_file(dumps[0]) == []
    with open(dumps[0]) as f:
        doc = json.load(f)
    assert doc["extra"]["bad_steps_this_update"] == 1
    spans = [r for r in doc["records"] if r["kind"] == "span"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # The offending step (fed batch 2) end to end: its collation span, its
    # H2D transfer, and its device dispatch are all in the timeline.
    assert any(s["attrs"]["index"] == 2 for s in by_name["collate"])
    assert any(s["attrs"]["index"] == 2 for s in by_name["device_step"])
    assert len(by_name["h2d"]) >= 3  # batches 0..2 all transferred
    # The guard's own counter event made it into the same timeline.
    assert any(r["name"] == "fault/bad_steps" for r in doc["records"])
    # All three pipeline stages hang off ONE (still-open at dump time) epoch
    # span: the collate/h2d spans were emitted on the feed-host and
    # feed-transfer threads yet share the consumer-thread device_step
    # spans' parent via the explicit context handoff.
    epoch_parent = {s.get("parent_id") for s in by_name["device_step"]}
    assert len(epoch_parent) == 1 and None not in epoch_parent
    assert {s.get("parent_id") for s in by_name["collate"]} == epoch_parent
    assert {s.get("parent_id") for s in by_name["h2d"]} == epoch_parent


def pytest_feed_wait_and_h2d_are_spans_of_the_epoch():
    """The consumer's blocking ``next()`` is a ``feed_wait`` span beside
    ``FeedStats.feed_wait_s`` (same region, same seconds), in the per-step
    train loop and in ``evaluate``; the transfer is ONE live ``h2d`` span a
    batch (its number and bytes as attributes) that ``FeedStats.h2d_s`` agrees
    with. All hang off the epoch's span."""

    class _PerStep:  # routes train_epoch onto the per-step path
        active = True

        def step(self):
            pass

    telemetry.configure(collect=True)
    loader = _loader(_dataset(np.random.default_rng(0)))
    d = _driver_for(loader)
    d.train_epoch(loader, _PerStep())
    train_stats = d.feed_stats.as_dict()
    spans = [r for r in telemetry.collected_records() if r["kind"] == "span"]
    by_name = {}
    for r in spans:
        by_name.setdefault(r["name"], []).append(r)
    (epoch,) = by_name["train_epoch"]
    batches = len(by_name["device_step"])
    assert batches == 3
    # One wait a batch and the one that finds the feed exhausted.
    assert len(by_name["feed_wait"]) == batches + 1
    assert {r["parent_id"] for r in by_name["feed_wait"]} == {epoch["span_id"]}
    assert sum(r["dur_s"] for r in by_name["feed_wait"]) == pytest.approx(
        train_stats["feed_wait_s"], abs=2e-3
    )
    assert [r["attrs"]["index"] for r in by_name["h2d"]] == [1, 2, 3]
    assert all(r["attrs"]["bytes"] > 0 for r in by_name["h2d"])
    assert {r["parent_id"] for r in by_name["h2d"]} == {epoch["span_id"]}
    assert sum(r["dur_s"] for r in by_name["h2d"]) == pytest.approx(
        train_stats["h2d_s"], abs=2e-3
    )

    telemetry.reset(keep_config=True)
    d.evaluate(loader)
    spans = [r for r in telemetry.collected_records() if r["kind"] == "span"]
    (evaluate,) = [r for r in spans if r["name"] == "evaluate"]
    waits = [r for r in spans if r["name"] == "feed_wait"]
    assert len(waits) == len([r for r in spans if r["name"] == "eval_step"]) + 1
    assert {r["parent_id"] for r in waits} == {evaluate["span_id"]}


def pytest_engine_poison_dumps_flight_recorder(tmp_path):
    telemetry.configure(run_dir=str(tmp_path))
    engine, graphs = _serve_engine()

    def boom(dev_batch):
        raise RuntimeError("injected device failure")

    engine._execute = boom
    fut = engine.submit(graphs[0])
    with pytest.raises(RuntimeError, match="injected device failure"):
        fut.result(timeout=30.0)
    engine.close()
    dumps = glob.glob(str(tmp_path / "flightrec_*_engine_poison.json"))
    assert len(dumps) == 1
    assert telemetry.validate_flight_file(dumps[0]) == []
    with open(dumps[0]) as f:
        doc = json.load(f)
    assert "injected device failure" in doc["extra"]["error"]
    # The poisoned request's submit event is in the timeline, correlated.
    rid = fut.request_id
    assert any(
        r["name"] == "serve/submit" and r.get("request_id") == rid
        for r in doc["records"]
    )


def pytest_checkpoint_fallback_dumps_flight_recorder(tmp_path):
    from hydragnn_tpu.utils.model import load_existing_model, save_model

    telemetry.configure(run_dir=str(tmp_path))  # NOT used: dump goes to run_dir arg
    params = {"dense": {"kernel": np.arange(12, dtype=np.float32).reshape(4, 3)}}
    variables = {"params": params, "batch_stats": {}}
    opt = select_optimizer("AdamW", 1e-3)
    opt_state = opt.init(params)
    for epoch in (1, 2, 3):
        save_model(
            variables, opt_state, "fb", path=str(tmp_path) + "/",
            meta={"epoch": epoch}, keep_last_k=3,
        )
    ckpt = str(tmp_path / "fb" / "fb.pk")
    with open(ckpt, "r+b") as f:
        f.seek(120)
        b = f.read(1)
        f.seek(120)
        f.write(bytes([b[0] ^ 0xFF]))
    template = {
        "params": {"dense": {"kernel": np.zeros((4, 3), np.float32)}},
        "batch_stats": {},
    }
    _, _, meta = load_existing_model(
        template, "fb", path=str(tmp_path) + "/", return_meta=True
    )
    assert meta["epoch"] == 2
    dumps = glob.glob(
        str(tmp_path / "fb" / "flightrec_*_checkpoint_fallback.json")
    )
    assert len(dumps) == 1
    assert telemetry.validate_flight_file(dumps[0]) == []
    with open(dumps[0]) as f:
        doc = json.load(f)
    assert doc["extra"]["fallback_file"] == "fb.e000002.pk"
    assert doc["extra"]["epochs_lost"] == 1


def pytest_supervisor_restart_dumps_flight_recorder(tmp_path, monkeypatch):
    """The restart trigger without real child processes: fake the child
    subprocess (rc=1 then rc=0) and assert the parent dumped its timeline
    into the run dir on the restart."""
    from hydragnn_tpu.faults import supervisor

    rcs = iter([1, 0])

    class _Proc:
        def __init__(self, rc):
            self.returncode = rc

    monkeypatch.setattr(
        supervisor.subprocess,
        "run",
        lambda *a, **kw: _Proc(next(rcs)),
    )
    config = {
        "NeuralNetwork": {
            "Architecture": {
                "model_type": "SAGE",
                "radius": 2,
                "max_neighbours": 10,
                "num_conv_layers": 2,
                "hidden_dim": 8,
                "task_weights": [1.0],
            },
            "Training": {
                "num_epoch": 1,
                "learning_rate": 0.001,
                "batch_size": 4,
            },
            "Variables_of_interest": {"input_node_features": [0]},
        },
        "Dataset": {"name": "sup_tele"},
    }
    meta = supervisor.run_supervised(
        config, max_restarts=2, logs_path=str(tmp_path) + "/"
    )
    assert meta["completed"] and meta["restarts"] == 1
    run_dir = os.path.join(str(tmp_path), meta["log_name"])
    dumps = glob.glob(
        os.path.join(run_dir, "flightrec_*_supervisor_restart.json")
    )
    assert len(dumps) == 1
    assert telemetry.validate_flight_file(dumps[0]) == []
    with open(dumps[0]) as f:
        doc = json.load(f)
    assert doc["extra"]["attempt"] == 1 and doc["extra"]["returncode"] == 1
    assert any(
        r["name"] == "fault/supervisor_restart" for r in doc["records"]
    )


# ----------------------------------------------- serve correlation, HTTP e2e
def pytest_serve_correlation_id_traceable_end_to_end():
    """ACCEPTANCE: the correlation id flows HTTP ingress → submit → pack bin
    (collate span) → device batch (device span) → demux (response event) →
    X-HydraGNN-Request-Id response header; the 429 path echoes it too."""
    telemetry.configure(collect=True)
    engine, graphs = _serve_engine()
    server = InferenceServer(engine, port=0).start_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        body = json.dumps(
            {
                "graphs": [
                    {
                        "x": np.asarray(graphs[0].x).tolist(),
                        "edge_index": np.asarray(graphs[0].edge_index).tolist(),
                        "edge_attr": np.asarray(graphs[0].edge_attr).tolist(),
                    }
                ]
            }
        ).encode()
        req = urllib.request.Request(
            base + "/predict",
            data=body,
            headers={
                "Content-Type": "application/json",
                "X-HydraGNN-Request-Id": "r-e2e-test",
            },
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            assert resp.headers["X-HydraGNN-Request-Id"] == "r-e2e-test"
            doc = json.loads(resp.read())
        assert doc["request_id"] == "r-e2e-test"

        # The per-graph id is <call id>/<index>; every stage of the trail
        # carries it.
        rid = "r-e2e-test/0"
        recs = telemetry.collected_records()
        submit = [r for r in recs if r["name"] == "serve/submit"]
        assert any(r["request_id"] == rid for r in submit)
        for stage in ("serve/collate", "serve/h2d", "serve/device"):
            stage_recs = [r for r in recs if r["name"] == stage]
            assert any(
                rid in r["attrs"]["request_ids"] for r in stage_recs
            ), f"{stage} lost the correlation id"
        response = [r for r in recs if r["name"] == "serve/response"]
        assert any(r["request_id"] == rid for r in response)

        # Header present on GET paths too.
        with urllib.request.urlopen(base + "/healthz", timeout=10) as resp:
            assert resp.headers["X-HydraGNN-Request-Id"]
            health = json.loads(resp.read())
        assert health["degraded_events"] == []
        # /metrics carries the graftel registry next to the engine metrics.
        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        assert "hydragnn_serve_requests_total" in text
        assert "hydragnn_timer_serve_e2e_total" in text
    finally:
        server.shutdown()


def pytest_serve_429_echoes_request_id_and_healthz_logs_degraded():
    engine, graphs = _serve_engine(queue_limit=1, autostart=False)
    engine.submit(graphs[0])  # occupy the single queue slot
    server = InferenceServer(engine, port=0, request_timeout_s=5.0).start_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        body = json.dumps(
            {"graphs": [{"x": np.asarray(graphs[1].x).tolist()}]}
        ).encode()
        req = urllib.request.Request(
            base + "/predict",
            data=body,
            headers={
                "Content-Type": "application/json",
                "X-HydraGNN-Request-Id": "r-shed-me",
            },
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 429
        assert e.value.headers["X-HydraGNN-Request-Id"] == "r-shed-me"
        assert json.loads(e.value.read())["request_id"] == "r-shed-me"
    finally:
        server.shutdown()
    # Degraded transitions carry the correlation ids that tripped them.
    engine2, graphs2 = _serve_engine(max_delay_ms=5.0)
    try:
        real_collate = engine2._collate
        calls = {"n": 0}

        def flaky(entries, ladder=None):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ValueError("injected collation failure")
            return real_collate(entries, ladder)

        engine2._collate = flaky
        fut = engine2.submit(graphs2[0], request_id="r-degrader")
        with pytest.raises(ValueError):
            fut.result(timeout=30.0)
        events = engine2.degraded_events
        assert events and events[-1]["reason"] == "collation_failure"
        assert "r-degrader" in events[-1]["request_ids"]
    finally:
        engine2.close()


# -------------------------------------------------------------- exporters
def pytest_traced_train_exports_valid_jsonl_and_perfetto(tmp_path):
    """A short traced train run exports a non-empty schema-valid JSONL event
    log, and the Chrome-trace (Perfetto) export loads back."""
    from hydragnn_tpu.telemetry.__main__ import _smoke_train

    telemetry.configure(run_dir=str(tmp_path), collect=True)
    _smoke_train(epochs=2)

    jsonl = str(tmp_path / "trace_events.jsonl")
    n = telemetry.export_events_jsonl(jsonl)
    assert n > 0
    count, errors = telemetry.validate_events_jsonl(jsonl)
    assert count == n and errors == []

    chrome = str(tmp_path / "trace_chrome.json")
    n_events = telemetry.export_chrome_trace(chrome)
    assert n_events == n
    assert telemetry.validate_chrome_trace(chrome) == []
    with open(chrome) as f:
        doc = json.load(f)  # loads back as plain JSON
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"train_epoch", "collate", "device_step"} <= names
    # thread_name metadata present for the pipeline threads.
    threads = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert any(t.startswith("hydragnn-prefetch") for t in threads)

    counts = telemetry.span_counts()
    assert counts["train_epoch"] == 2
    assert counts["device_step"] >= 2


# ------------------------------------------------- running totals, the account
def pytest_span_totals_equal_collected_durations_from_two_threads():
    """A span is also a running total: ``span_s/<name>`` / ``span_n/<name>``
    equal the collected spans' ``dur_s`` summed by name, from two threads at
    once, live with collection OFF, beyond the ring's 4,096 records, and
    rendered in Prometheus. ``dur_s`` is the span's one clock reading."""
    telemetry.configure(collect=True)

    def work(name, n):
        for _ in range(n):
            with telemetry.span(name) as outer:
                with telemetry.span("shared"):
                    pass
            assert outer.dur_s is not None and outer.dur_s >= 0.0

    threads = [
        threading.Thread(target=work, args=(name, 200)) for name in ("left", "right")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    by_name = {}
    for r in telemetry.collected_records():
        # The spans this test opened: a garbage collection of 1 ms or more is
        # a ``gc`` record too once any earlier test of this process installed
        # the hook (a loaded host makes them that long), marked ``retro``.
        if r["kind"] == "span" and not r.get("retro"):
            by_name.setdefault(r["name"], []).append(r["dur_s"])
    assert {k: len(v) for k, v in by_name.items()} == {
        "left": 200, "right": 200, "shared": 400,
    }
    totals = telemetry.span_totals()
    counts = telemetry.counters_snapshot("span_n/")
    for name, durs in by_name.items():
        assert totals[name] == pytest.approx(sum(durs), rel=1e-9)
        assert counts["span_n/" + name] == len(durs)
    assert "hydragnn_span_s_shared_total" in telemetry.render_prometheus()
    # Collection off, and more spans than the ring holds: the totals go on.
    telemetry.configure(collect=False)
    before = telemetry.counter_value("span_n/flood")
    for _ in range(5000):
        with telemetry.span("flood"):
            pass
    assert telemetry.collected_records() == []
    assert len(telemetry.snapshot_records()) <= 4096
    assert telemetry.counter_value("span_n/flood") - before == 5000


def pytest_disabled_spans_keep_their_clock_for_feedstats():
    """``enabled=False`` drops records and totals, but a span still reads
    its clock pair: ``FeedStats`` is credited from ``dur_s`` either way."""
    telemetry.configure(enabled=False)
    loader = _loader(_dataset(np.random.default_rng(0)))
    d = _driver_for(loader)
    d.train_epoch(loader)
    stats = d.feed_stats.as_dict()
    assert stats["step_s"] > 0.0 and stats["feed_wait_s"] > 0.0
    assert telemetry.snapshot_records() == []
    assert telemetry.span_totals() == {}


def pytest_record_span_is_retroactive_and_totalled():
    telemetry.configure(collect=True)
    with telemetry.span("holder") as holder:
        telemetry.record_span(
            "late", 0.25, end_ts=1000.0, thread="elsewhere", generation=2
        )
    (late,) = [r for r in telemetry.collected_records() if r["name"] == "late"]
    assert late["retro"] is True and late["thread"] == "elsewhere"
    assert late["ts"] == pytest.approx(999.75) and late["dur_s"] == 0.25
    assert late["parent_id"] == holder.ctx.span_id
    assert late["attrs"] == {"generation": 2}
    assert telemetry.span_totals()["late"] == 0.25
    assert telemetry.validate_flight(
        {"schema": telemetry.SCHEMA_FLIGHT, "trigger": "t", "ts_utc": "x",
         "pid": 1, "seq": 1, "records": [late], "counters": {}, "gauges": {}}
    ) == []


def pytest_gc_pause_is_a_record_only_from_a_millisecond():
    """Every collection adds to ``host/gc_pause_s``; one of a millisecond or
    more is a retroactive ``gc`` record (generation and collected count as
    attributes), a generation-0 collection of a few objects is not."""
    import gc

    from hydragnn_tpu.telemetry import graftel

    telemetry.configure(collect=True)
    telemetry.install_gc_hook()
    telemetry.install_gc_hook()  # idempotent
    assert gc.callbacks.count(graftel._on_gc) == 1

    class Node:
        pass

    ring = []
    for _ in range(200_000):
        a, b = Node(), Node()
        a.other, b.other = b, a
        ring.append(a)
    del ring, a, b
    with telemetry.span("around"):
        gc.collect()
    records = [r for r in telemetry.collected_records() if r["name"] == "gc"]
    full = [r for r in records if r["attrs"]["generation"] == 2]
    assert full and all(r["retro"] for r in records)
    assert max(r["attrs"]["collected"] for r in full) >= 400_000
    assert all(r["dur_s"] >= graftel.GC_RECORD_S for r in records)
    assert telemetry.counter_value("host/gc_pause_s") >= sum(
        r["dur_s"] for r in records
    ) - 1e-9
    # Young collections of next to nothing: counted, never recorded.
    n_records = len(records)
    n_counted = telemetry.counter_value("host/gc_collections")
    gc.disable()
    try:
        for _ in range(5):
            gc.collect(0)
    finally:
        gc.enable()
    assert telemetry.counter_value("host/gc_collections") >= n_counted + 5
    records = [r for r in telemetry.collected_records() if r["name"] == "gc"]
    assert len(records) < n_records + 5
    assert all(r["dur_s"] >= graftel.GC_RECORD_S for r in records)


def pytest_setup_account_and_jax_durations():
    """The functions every entry point calls before its first step open
    ``setup.*`` spans themselves, and the first one installs the hooks: with
    no caller configuring anything, the totals hold the initializer and JAX's
    own trace / lower / compile seconds. (The initializer's seconds are no
    longer held above the driver's: it is one program, and an equal model an
    earlier test initialised costs it no compile at all.)"""
    loader = _loader(_dataset(np.random.default_rng(0)))
    before = telemetry.jax_seconds()
    d = _driver_for(loader)
    totals = telemetry.span_totals()
    for name in ("setup.init_variables", "setup.create_state", "setup.driver"):
        assert totals[name] > 0.0, name
    # A program this process has not compiled yet (the models above may be
    # in its caches from an earlier test).
    import jax

    jax.block_until_ready(
        jax.jit(lambda x: (x * 35.0 + 0.35).sum())(np.arange(35, dtype=np.float32))
    )
    after = telemetry.jax_seconds()
    assert set(after) == {
        "jax_trace_s", "jax_lower_s", "jax_compile_s", "jax_cache_load_s",
    }
    for key in ("jax_trace_s", "jax_lower_s", "jax_compile_s"):
        assert after[key] > before[key], key
    # The persistent cache is off in the tests: nothing was loaded.
    assert after["jax_cache_load_s"] == before["jax_cache_load_s"]
    assert telemetry.counter_value("jax/compiles") >= 1
    assert isinstance(d, TrainingDriver)
    # Config completion and the loaders, through their own entry points.
    from hydragnn_tpu.utils.config_utils import update_config

    assert update_config.__wrapped__.__name__ == "update_config"


def _epochs_one_call_each(driver, loaders, epochs):
    """``train_validate_test`` one epoch a call, as the benchmark's drivers
    run it: the account of the last epochs lives on the driver."""
    from hydragnn_tpu.train.train_validate_test import train_validate_test

    history = None
    for epoch in range(epochs):
        history = train_validate_test(
            driver, *loaders, epoch + 1, start_epoch=epoch, history=history
        )
    return history


@pytest.mark.parametrize("stalled", [True, False])
def pytest_injected_stall_in_sixth_epoch_names_its_phase(tmp_path, caplog, stalled):
    """A collation stalled in the sixth epoch yields exactly ONE
    ``train/epoch_stall`` whose phase on the dispatching thread is
    ``feed_wait`` (the feed thread's ``collate`` beside it), a flight dump
    with trigger ``epoch_stall`` and one warning line, with no profiler and
    no collection; the same run without the stall yields none of them."""
    telemetry.configure(run_dir=str(tmp_path))
    graphs = _dataset(np.random.default_rng(0), count=24)
    loaders = (
        _loader(graphs[:16], shuffle=True), _loader(graphs[16:20]),
        _loader(graphs[20:]),
    )
    # 4 train batches an epoch: fed batch 21 is the sixth epoch's second.
    plan = FaultPlan("slow_collate@21:ms=400") if stalled else None
    d = _driver_for(loaders[0], plan=plan)
    with caplog.at_level("WARNING", logger="hydragnn_tpu.train.train_validate_test"):
        _epochs_one_call_each(d, loaders, 8)
    stalls = [
        r for r in telemetry.snapshot_records() if r["name"] == "train/epoch_stall"
    ]
    dumps = glob.glob(str(tmp_path / "flightrec_*_epoch_stall.json"))
    warnings = [r for r in caplog.records if "against a median" in r.getMessage()]
    assert telemetry.counter_value("train/epoch_stalls") == len(stalls)
    if not stalled:
        assert stalls == [] and dumps == [] and warnings == []
        return
    (stall,) = stalls
    attrs = stall["attrs"]
    assert attrs["epoch"] == 5 and attrs["phase"] == "feed_wait"
    assert attrs["wall_s"] > 0.4 > 1.5 * attrs["median_s"]
    assert attrs["seconds"]["feed_wait"] >= 0.39
    assert attrs["seconds"]["collate"] >= 0.39  # all threads: the feed's too
    assert {name for name, _, _ in attrs["excess"][:2]} == {"feed_wait", "collate"}
    assert attrs["no_leaf_s"] < 0.05
    (dump,) = dumps
    assert telemetry.validate_flight_file(dump) == []
    with open(dump) as f:
        doc = json.load(f)
    assert doc["trigger"] == "epoch_stall" and doc["extra"]["phase"] == "feed_wait"
    (warning,) = warnings
    assert "feed_wait" in warning.getMessage() and "epoch 5" in warning.getMessage()


def _sleep_on_call(monkeypatch, owner, name, seconds):
    """``owner.name`` sleeps ``seconds(n)`` before its ``n``-th call (from
    1), where that is more than nothing."""
    import time

    plain, calls = getattr(owner, name), [0]

    def slow(*args, **kwargs):
        calls[0] += 1
        if seconds(calls[0]) > 0.0:
            time.sleep(seconds(calls[0]))
        return plain(*args, **kwargs)

    monkeypatch.setattr(owner, name, slow)


@pytest.mark.parametrize("stalled", ["dispatch", "wait"])
def pytest_a_stalled_step_is_told_as_its_dispatch_or_its_wait(
    tmp_path, caplog, monkeypatch, stalled
):
    """The same eight epochs with a sleep planted in the sixth epoch's chunk:
    inside ``_dispatch`` it is reported as the dispatch, between its return
    and the readback as the wait. Exactly ONE ``train/epoch_stall``, one dump
    and one line; its phase is ``device_step``, whose longest step carries
    the seconds; the verdict names the part and says what the platform's
    count of the thread's run delay was, or that there is none. (Every
    epoch's head sleeps 0.3 s, so that a stall is 0.2 s or more over the
    median and a loaded host's own jitter raises none; the planted one is
    0.8 s.)"""
    from hydragnn_tpu.train.train_validate_test import EpochMetrics

    telemetry.configure(run_dir=str(tmp_path))
    graphs = _dataset(np.random.default_rng(0), count=24)
    loaders = (
        _loader(graphs[:16], shuffle=True), _loader(graphs[16:20]),
        _loader(graphs[20:]),
    )
    d = _driver_for(loaders[0])
    # ``set_epoch`` is called thrice in an epoch's head (one a loader); one
    # chunk and two evaluation steps an epoch: the sixth epoch's chunk is the
    # 16th program dispatched, and the 16th readback.
    _sleep_on_call(monkeypatch, GraphDataLoader, "set_epoch", lambda n: 0.1)
    owner, name = {
        "dispatch": (d, "_dispatch"), "wait": (EpochMetrics, "update"),
    }[stalled]
    _sleep_on_call(monkeypatch, owner, name, lambda n: 0.8 if n == 16 else 0.0)
    with caplog.at_level("WARNING", logger="hydragnn_tpu.train.train_validate_test"):
        _epochs_one_call_each(d, loaders, 8)
    records = telemetry.snapshot_records()
    (attrs,) = [r["attrs"] for r in records if r["name"] == "train/epoch_stall"]
    (dump,) = glob.glob(str(tmp_path / "flightrec_*_epoch_stall.json"))
    (said,) = [
        r.getMessage() for r in caplog.records if "against a median" in r.getMessage()
    ]
    assert telemetry.counter_value("train/epoch_stalls") == 1
    counted = telemetry.thread_sched()[0] is not None
    epochs = [r["attrs"] for r in records if r["name"] == "epoch"]
    assert len(epochs) == 8 and all(
        (a["run_delay_s"] is not None) == counted and a["nivcsw"] >= 0
        for a in epochs
    )
    part = {"dispatch": "dispatch_s", "wait": "wait_s"}[stalled]
    assert attrs["epoch"] == 5 and attrs["phase"] == "device_step"
    assert attrs["wall_s"] > 0.8 > 1.5 * attrs["median_s"]
    assert attrs["held_by"] == stalled and attrs["held_s"] >= 0.7
    assert attrs[part] >= 0.79 and attrs["no_leaf_s"] < 0.05
    assert attrs["dispatch_s"] + attrs["wait_s"] == pytest.approx(
        attrs["seconds"]["device_step"] + attrs["seconds"]["eval_step"], abs=5e-3
    )
    assert (attrs["run_delay_s"] is not None) == counted
    longest = attrs["longest_step"]
    assert longest["span"] == "device_step" and longest[part] >= 0.79
    assert telemetry.validate_flight_file(dump) == []
    with open(dump) as f:
        doc = json.load(f)
    assert doc["trigger"] == "epoch_stall" and doc["extra"]["held_by"] == stalled
    assert said.startswith("epoch 5 ") and "device_step" in said
    assert {"dispatch": "the dispatch was slow", "wait": "its wake-up was slow"}[
        stalled
    ] in said
    assert ("the thread's run delay 0." in said) == counted
    assert ("run delay is not counted on this host" in said) != counted


def pytest_a_step_record_tells_its_dispatch_from_its_wait(monkeypatch, tmp_path):
    """Every ``device_step`` and ``eval_step`` record carries ``dispatch_s``
    and ``wait_s``, which add to its ``dur_s``, and the thread's turn
    (``run_delay_s``, ``nivcsw``); nothing opens under it; the running
    counters move with collection off. What the platform does not count
    reads None, never 0: without ``/proc/thread-self/schedstat`` (the
    sandboxed kernel of the machine with the chips) the run delay alone,
    without ``resource`` too ``thread_sched`` itself; the counter
    ``host/run_delay_s`` then stays where it was and nothing else changes."""
    from hydragnn_tpu.telemetry import graftel

    assert len(telemetry.thread_sched()) == 2
    loader = _loader(_dataset(np.random.default_rng(0)))
    d = _driver_for(loader)
    d.train_epoch(loader)  # the scan path, collection off
    assert telemetry.collected_records() == []
    assert telemetry.counter_value("train/dispatch_s") > 0.0
    waited = telemetry.counter_value("train/readback_wait_s")
    assert waited > 0.0 and "host/run_delay_s" in telemetry.counters_snapshot("host/")
    assert "hydragnn_train_readback_wait_s_total" in telemetry.render_prometheus()

    # A kernel's count is read where it is; a file once found missing is
    # not asked for again (on a sandboxed kernel the failing call is slow
    # enough to cost the dispatching thread the GIL).
    there, absent = tmp_path / "schedstat", tmp_path / "no-such-file"
    there.write_text("7 2500000000 3\n")
    monkeypatch.setattr(graftel, "_SCHEDSTAT", str(there))
    assert telemetry.thread_sched()[0] == 2.5
    monkeypatch.setattr(graftel, "_SCHEDSTAT", str(absent))
    delay, switches = telemetry.thread_sched()
    assert delay is None and switches >= 0
    absent.write_text("7 2500000000 3\n")
    assert telemetry.thread_sched()[0] is None
    since = telemetry.sched_since((None, 0))
    assert since[0] is None and since[1] >= switches
    delayed = telemetry.counter_value("host/run_delay_s")
    telemetry.configure(collect=True)
    d.train_epoch(loader)
    with monkeypatch.context() as neither:
        neither.setattr(graftel, "resource", None)
        assert telemetry.thread_sched() is None
        assert telemetry.sched_since(None) == (None, None)
        assert telemetry.sched_since((0.0, 0)) == (None, None)
        d.evaluate(loader)
    assert telemetry.counter_value("host/run_delay_s") == delayed
    spans = [r for r in telemetry.collected_records() if r["kind"] == "span"]
    steps = [r for r in spans if r["name"] in ("device_step", "eval_step")]
    assert {r["name"] for r in steps} == {"device_step", "eval_step"}
    # (A collection's retroactive ``gc`` record was never open there.)
    parents = {r["parent_id"] for r in spans if not r.get("retro")}
    apart = []
    for r in steps:
        a = r["attrs"]
        assert a["dispatch_s"] > 0.0 and a["wait_s"] >= 0.0
        apart.append(r["dur_s"] - (a["dispatch_s"] + a["wait_s"]))
        assert a["run_delay_s"] is None
        assert (a["nivcsw"] is None) == (r["name"] == "eval_step")
        assert r["span_id"] not in parents, "a span opened under a step"
    # The two add to the span to the clock's grain: four attributes are set
    # between the last reading and the span's own (on a loaded host the
    # interpreter may hand the thread's turn away just there, once).
    assert min(apart) >= 0.0 and sorted(apart)[len(apart) // 2] < 1e-3, apart
    assert max(apart) < 2e-2, apart
    assert telemetry.counter_value("train/readback_wait_s") > waited


def pytest_the_stall_rule_alone_on_a_wall_and_named_seconds(tmp_path, caplog):
    """``StallAccount`` with no loop round it: history of three before it
    judges, 1.5 x the median AND 0.1 s over it, by key, each name's excess
    over its own median, the verdict's three answers, and what it says of a
    cycle whose keeper books no run delay (a platform that counts none)."""
    import logging

    telemetry.configure(run_dir=str(tmp_path))
    account = telemetry.StallAccount(
        "cycle", "test/stall", "test/stalls", "test_stall",
        logging.getLogger("test.stall"), dispatch=("launch",), wait=("wait",),
    )
    quiet = {"launch": 0.01, "wait": 0.2, "other": 0.05, "run_delay_s": 0.0}
    # Two cycles of history are not enough; a third key has none of its own.
    assert account.book(0, 9.0, quiet) is None and account.book(1, 0.3, quiet) is None
    assert all(account.book(k, 0.3, quiet) is None for k in range(2, 12))
    assert account.book(0, 9.0, quiet, key="other rung") is None
    # 0.42 is 1.4 x the median; 0.04 against tiny cycles is not 0.1 s over.
    assert account.book(4, 0.42, quiet) is None
    tiny = telemetry.StallAccount("c", "t/s", "t/n", "t", logging.getLogger("test.stall"))
    assert [tiny.book(k, w, {}) for k, w in enumerate((0.01, 0.01, 0.01, 0.05))] == [None] * 4
    with caplog.at_level("WARNING", logger="test.stall"):
        slow_wait = account.book(5, 0.8, dict(quiet, wait=0.7), note="kept")
        slow_launch = account.book(6, 0.8, dict(quiet, launch=0.51))
        not_run = account.book(7, 0.8, dict(quiet, wait=0.7, run_delay_s=0.4))
        elsewhere = account.book(8, 0.8, dict(quiet, other=0.55))
    assert [s["held_by"] for s in (slow_wait, slow_launch, not_run, elsewhere)] == [
        "wait", "dispatch", "host_thread", None,
    ]
    assert slow_wait["note"] == "kept" and slow_wait["median_s"] == 0.3
    assert slow_wait["excess"][0] == ["wait", 0.5, 0.2] and slow_wait["held_s"] == 0.5
    assert not_run["run_delay_s"] == 0.4 and not_run["held_s"] == 0.4
    assert elsewhere["excess"][0][0] == "other"
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 4 and "cycle 5 took 0.800 s against a median of 0.300 s" in said[0]
    assert "its wake-up was slow" in said[0] and "the dispatch was slow" in said[1]
    assert "runnable and not run" in said[2] and "neither the thread's turn" in said[3]
    assert "the thread's run delay 0.400 s against 0.000" in said[2]
    # A platform that counts no run delay: the keeper books none, the event
    # says None and the line "not counted", never a measured zero.
    uncounted = {k: v for k, v in quiet.items() if k != "run_delay_s"}
    blind = telemetry.StallAccount(
        "cycle", "test/stall", "test/stalls", "test_stall",
        logging.getLogger("test.stall"), dispatch=("launch",), wait=("wait",),
    )
    assert all(blind.book(k, 0.3, uncounted) is None for k in range(3))
    caplog.clear()
    with caplog.at_level("WARNING", logger="test.stall"):
        unseen = blind.book(3, 0.8, dict(uncounted, wait=0.7))
    assert unseen["run_delay_s"] is None and unseen["held_by"] == "wait"
    assert "run_delay_s" not in unseen["seconds"]
    (line,) = [r.getMessage() for r in caplog.records]
    assert "run delay is not counted on this host" in line and "run delay 0." not in line
    assert telemetry.counter_value("test/stalls") == 5
    dumps = glob.glob(str(tmp_path / "flightrec_*_test_stall.json"))
    assert len(dumps) == 5 and telemetry.validate_flight_file(dumps[0]) == []


# ------------------------------------------------- the engine's flush account
def _flush_records(engine, graphs, flushes, before_each=None):
    """``flushes`` full flushes of two requests through ``engine``, one
    after the other (a closed loop of one caller; the engines here flush by
    size, their deadline is 2 s); the records collected."""
    for k in range(flushes):
        if before_each is not None:
            before_each(k)
        for fut in [engine.submit(g) for g in graphs[:2]]:
            fut.result(timeout=60.0)
    return telemetry.collected_records()


def pytest_every_flush_has_one_account_on_one_clock():
    """One retroactive ``serve/flush`` a flush, whose ``flush_id`` its
    ``serve/collate`` / ``serve/h2d`` / ``serve/device`` / ``serve/resolve``
    spans carry (``serve/d2h`` is ``serve/device``'s child); its marks are
    monotone on one clock and lie where the real spans do; the launch and the
    wait are told apart ONCE, in the marks (``serve/device`` carries the
    dispatcher's turn); and the stage clocks add up: ``prepare + queue_wait``
    of a flush's requests and its ``collate + handoff + h2d + device + d2h``
    are the requests' ``e2e``."""
    from hydragnn_tpu.serve.engine import FLUSH_MARKS, flush_parts

    telemetry.configure(collect=True)
    engine, graphs = _serve_engine(max_batch_graphs=2, max_delay_ms=2000.0)
    try:
        recs = _flush_records(engine, graphs, 5)
        snap = engine.metrics.snapshot()["latency_ms"]
    finally:
        engine.close()
    flushes = [r for r in recs if r["name"] == "serve/flush"]
    assert [r["attrs"]["flush_id"] for r in flushes] == [1, 2, 3, 4, 5]
    assert all(r.get("retro") and r["attrs"]["requests"] == 2 for r in flushes)
    for stage in ("collate", "h2d", "device", "resolve"):
        ids = [r["attrs"]["flush_id"] for r in recs if r["name"] == "serve/" + stage]
        assert ids == [1, 2, 3, 4, 5], stage
    devices = [r for r in recs if r["name"] == "serve/device"]
    copies = [r for r in recs if r["name"] == "serve/d2h"]
    assert [r["parent_id"] for r in copies] == [r["span_id"] for r in devices]
    every_part = 0.0
    for k, r in enumerate(flushes):
        a = r["attrs"]
        offsets = [a["marks"][name] for name in FLUSH_MARKS]
        assert offsets[0] == 0.0 and offsets == sorted(offsets), offsets
        assert r["dur_s"] == pytest.approx(offsets[-1], abs=1e-5)
        assert (a["turnaround_s"] is None) == (a["await_s"] is None) == (k == 0)
        parts = flush_parts(a["marks"])
        every_part += 2 * sum(
            parts[name] for name in
            ("collate", "handoff", "lookup", "h2d", "launch", "device_wait", "d2h")
        )
        if k:
            before = flushes[k - 1]["attrs"]
            gap = a["t0"] - before["t0"]
            assert a["turnaround_s"] == pytest.approx(
                gap + a["marks"]["launch_end"] - before["marks"]["ready"], abs=1e-5
            )
            assert a["await_s"] == pytest.approx(gap - before["marks"]["resolved"], abs=1e-5)
    counted = telemetry.thread_sched()[0] is not None
    for r, flush in zip(devices, flushes):
        a, marks = r["attrs"], flush["attrs"]["marks"]
        assert set(a) == {"flush_id", "request_ids", "run_delay_s", "nivcsw"}
        assert (a["run_delay_s"] is not None) == counted and a["nivcsw"] >= 0
        # The record's ``ts`` is its start on the spans' clock: the marks the
        # dispatcher read inside ``serve/device`` lie inside that span.
        opened = r["ts"] - flush["ts"]
        assert opened - 1e-3 <= marks["exec_start"] <= marks["launch_start"]
        assert marks["d2h_end"] <= opened + r["dur_s"] + 1e-3
    # The stage identity, summed over the window's ten requests: the two
    # per-request clocks, and each flush's clocks once a request of it.
    assert snap["e2e"]["count"] == 10 and snap["turnaround"]["count"] == 4
    per_request = snap["prepare"]["sum_s"] + snap["queue_wait"]["sum_s"]
    per_flush = sum(
        snap[s]["sum_s"] for s in ("collate", "handoff", "h2d", "device", "d2h")
    )
    assert per_request + 2 * per_flush == pytest.approx(snap["e2e"]["sum_s"], abs=1e-3)
    assert 2 * per_flush == pytest.approx(every_part, abs=1e-3)


def pytest_await_is_one_span_across_empty_polls_and_closes_with_the_engine():
    telemetry.configure(collect=True)
    engine, graphs = _serve_engine(max_batch_graphs=2, max_delay_ms=2000.0)
    try:
        import time

        time.sleep(0.25)  # five empty polls of 50 ms
        _flush_records(engine, graphs, 1)
    finally:
        engine.close()
    recs = telemetry.collected_records()
    waits = [r for r in recs if r["name"] == "serve/await"]
    (fill,) = [r for r in recs if r["name"] == "serve/fill"]
    # One from the start to the first request, one open when the engine closed.
    assert [r["attrs"]["flush_id"] for r in waits] == [1, 2]
    assert waits[0]["dur_s"] >= 0.25
    assert fill["attrs"] == {"flush_id": 1, "requests": 2, "reason": "size"}
    assert fill["ts"] >= waits[0]["ts"] + waits[0]["dur_s"] - 1e-3


class _Lazy:
    """An output the host has to wait for: what a slow program looks like
    to ``jax.block_until_ready``."""

    def __init__(self, value, seconds):
        self.value, self.seconds = value, seconds

    def block_until_ready(self):
        import time

        time.sleep(self.seconds)
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.value, dtype=dtype)


def _slow_engine(seconds):
    """An engine of two-request flushes whose every forward makes the host
    wait ``seconds()`` for its outputs (0.4 s where the tests below plant
    nothing: a stall is then 0.2 s or more over the median, and a loaded
    host's own jitter raises none)."""
    engine, graphs = _serve_engine(max_batch_graphs=2, max_delay_ms=2000.0)
    plain = engine._executable_for

    def planted(dev_batch, params, bstats):
        exe = plain(dev_batch, params, bstats)
        return lambda *args: [
            _Lazy(out, 0.0 if head else seconds())
            for head, out in enumerate(exe(*args))
        ]

    engine._executable_for = planted
    return engine, graphs


def pytest_a_slow_program_raises_one_flush_stall_held_by_the_wait(tmp_path, caplog):
    """After four flushes of its rung, one whose program takes 0.6 s longer
    raises ONE ``serve/flush_stall`` (counter, flight dump, one warning line)
    through the rule the epochs use: it names ``device_wait``, is held by the
    wait, and says what the dispatcher's run delay was, or that the platform
    counts none."""
    telemetry.configure(run_dir=str(tmp_path))
    wait_s = {"now": 0.4}
    engine, graphs = _slow_engine(lambda: wait_s["now"])
    try:
        with caplog.at_level("WARNING", logger="hydragnn_tpu.serve.engine"):
            telemetry.configure(collect=True)
            recs = _flush_records(
                engine, graphs, 6,
                lambda k: wait_s.update(now=1.0 if k == 5 else 0.4),
            )
    finally:
        engine.close()
    (a,) = [r["attrs"] for r in recs if r["name"] == "serve/flush_stall"]
    assert a["flush_id"] == 6 and a["rung"] in engine.metrics.snapshot()["per_bucket"]
    assert a["excess"][0][0] == "device_wait" and a["excess"][0][1] >= 0.59
    assert a["wall_s"] >= 1.0 > 1.5 * a["median_s"]
    assert a["held_by"] == "wait" and a["held_s"] >= 0.59
    counted = telemetry.thread_sched()[0] is not None
    assert (a["run_delay_s"] is not None) == counted
    assert set(a["seconds"]) - {"run_delay_s"} == {
        "d2h", "resolve", "collate", "handoff", "h2d", "lookup", "launch",
        "device_wait",
    }
    assert telemetry.counter_value("serve/flush_stalls") == 1
    (dump,) = glob.glob(str(tmp_path / "flightrec_*_flush_stall.json"))
    assert telemetry.validate_flight_file(dump) == []
    (said,) = [r.getMessage() for r in caplog.records]
    assert said.startswith("flush 6 took") and "against a median" in said
    assert "device_wait" in said and "its wake-up was slow" in said
    assert ("run delay is not counted on this host" in said) != counted


def pytest_an_idle_engine_is_not_a_stalled_one(tmp_path, caplog):
    """Open-loop traffic with pauses: a client 0.5 s late (``await``) and a
    flush that waits 0.3 s for its second request (``fill``) are the
    callers' seconds, not the engine's. The records say how long each was;
    no ``serve/flush_stall`` is raised, counted, dumped or logged."""
    import time

    telemetry.configure(run_dir=str(tmp_path), collect=True)
    engine, graphs = _slow_engine(lambda: 0.4)
    try:
        with caplog.at_level("WARNING", logger="hydragnn_tpu.serve.engine"):
            _flush_records(
                engine, graphs, 5, lambda k: time.sleep(0.5 if k == 4 else 0.0)
            )
            first = engine.submit(graphs[0])
            time.sleep(0.3)
            for fut in (first, engine.submit(graphs[1])):
                fut.result(timeout=60.0)
            recs = _flush_records(engine, graphs, 1)
    finally:
        engine.close()
    flushes = {
        r["attrs"]["flush_id"]: r["attrs"] for r in recs if r["name"] == "serve/flush"
    }
    assert sorted(flushes) == [1, 2, 3, 4, 5, 6, 7]
    assert flushes[5]["await_s"] >= 0.49 and flushes[5]["turnaround_s"] >= 0.49
    assert flushes[6]["marks"]["taken"] >= 0.29 and flushes[6]["turnaround_s"] >= 0.29
    assert all(flushes[k]["turnaround_s"] < 0.2 for k in (2, 3, 4, 7))
    assert [r for r in recs if r["name"] == "serve/flush_stall"] == []
    assert telemetry.counter_value("serve/flush_stalls") == 0
    assert glob.glob(str(tmp_path / "flightrec_*_flush_stall.json")) == []
    assert [r.getMessage() for r in caplog.records] == []
