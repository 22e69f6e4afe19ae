"""graftroute (hydragnn_tpu/route/) — the multi-replica serving tier.

Covers the ISSUE-12 contract: hash-ring stability under join/leave (bounded
key movement), admission/shedding by deadline class, Retry-After propagation
with jitter, degraded-replica drain + readmit + ejection, correlation-id
hop-log e2e through two in-process replicas, warm spin-up admitting only
after hydration (compile-spy: zero XLA compiles on a shared graftcache
store), router bit-exactness vs a direct engine at matched buckets, and the
HTTP front end (RouterServer + HttpReplica). Tier-1, CPU.
"""

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
from hydragnn_tpu.graphs import collate_graphs
from hydragnn_tpu.graphs.collate import compute_pad_sizes
from hydragnn_tpu.models import init_model_variables
from hydragnn_tpu.route import (
    HashRing,
    HttpReplica,
    InProcessReplica,
    NoReplicaAvailableError,
    ReplicaBackpressureError,
    Router,
    RouterBusyError,
    RouterServer,
)
from hydragnn_tpu.serve import InferenceEngine, InferenceServer


# ---------------------------------------------------------------- helpers
def _fleet_parts():
    """Shared model + variables + graph pool: every engine built from these
    is bit-identical to every other (the replica fleet contract)."""
    rng = np.random.default_rng(3)
    graphs = ge._make_graphs(6, rng)
    model = ge._build_model(hidden=4, layers=1)
    batch = collate_graphs(graphs[:2], ge.TYPES, ge.DIMS, edge_dim=1)
    variables = init_model_variables(model, batch)
    n_pad, e_pad, _ = compute_pad_sizes(graphs, 4)
    ladder = [(n_pad, e_pad)]
    return model, variables, graphs, ladder


def _engine(model, variables, ladder, **options):
    options.setdefault("max_batch_graphs", 4)
    options.setdefault("max_delay_ms", 5.0)
    options.setdefault("bucket_ladder", ladder)
    return InferenceEngine(model, variables, **options)


def _rid_with_primary(names, want, vnodes=64):
    """A request id whose consistent-hash primary is ``want`` (the probe
    ring mirrors the router's default construction: weight 1, vnodes 64)."""
    ring = HashRing(vnodes)
    for n in names:
        ring.add(n)
    for i in range(10000):
        rid = f"probe-{i}"
        if ring.owners(rid)[0] == want:
            return rid
    raise AssertionError(f"no key with primary {want!r} in 10000 probes")


class _StubReplica:
    """Scriptable replica for router-logic tests (no engine, no jax)."""

    def __init__(self, name, health=None, predict_exc=None, block=None):
        self.name = name
        self.health_doc = dict(
            health or {"ok": True, "compiled_buckets": 1}
        )
        self.health_exc = None
        self.predict_exc = predict_exc
        self.block = block
        self.calls = []

    def predict(self, samples, timeout=60.0, request_id=None):
        self.calls.append(request_id)
        if self.block is not None:
            self.block.wait(10)
        if self.predict_exc is not None:
            raise self.predict_exc
        return [[np.zeros(1, np.float32)] for _ in samples]

    def health(self):
        if self.health_exc is not None:
            raise self.health_exc
        return dict(self.health_doc)

    def close(self):
        pass


# ------------------------------------------------------------ 1. hash ring
def pytest_hash_ring_bounded_key_movement_on_join_leave():
    ring = HashRing(vnodes=64)
    for name in ("a", "b", "c", "d"):
        ring.add(name)
    keys = [f"key-{i}" for i in range(2000)]
    before = {k: ring.owners(k)[0] for k in keys}

    ring.add("e")
    after = {k: ring.owners(k)[0] for k in keys}
    moved = sum(1 for k in keys if before[k] != after[k])
    # Ideal movement is 1/5 of the keyspace; allow vnode-variance slack but
    # nothing like a rehash-everything (which would move ~4/5).
    assert 0 < moved / len(keys) < 0.32, moved / len(keys)
    # Every moved key moved TO the new member, never between old members.
    assert all(
        after[k] == "e" for k in keys if before[k] != after[k]
    )

    # Leave restores the exact original assignment (same points, same walk).
    ring.remove("e")
    assert {k: ring.owners(k)[0] for k in keys} == before

    # Weighted member owns proportionally more of the keyspace.
    ring.add("w", weight=2.0)
    share = sum(1 for k in keys if ring.owners(k)[0] == "w") / len(keys)
    assert 0.2 < share < 0.5, share  # ~2/6 of the keyspace, wide tolerance

    # owners() walks distinct members in preference order.
    owners = ring.owners("some-key")
    assert sorted(owners) == sorted(ring.members)
    assert len(set(owners)) == len(owners)


# ----------------------------------------------------------- 2. admission
def pytest_admission_sheds_by_deadline_class():
    block = threading.Event()
    stub = _StubReplica("only", block=block)
    router = Router(
        [stub],
        classes={
            "fast": {"deadline_s": 0.5},
            "ensemble": {"deadline_s": 60.0},
        },
        autostart_health=False,
        jitter_seed=0,
    )
    try:
        errors = []

        def worker():
            try:
                router.predict([object()], klass="fast")
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=worker, daemon=True) for _ in range(4)
        ]
        for t in threads:
            t.start()
        for _ in range(200):
            if router.queue_depth() == 4:
                break
            threading.Event().wait(0.01)
        assert router.queue_depth() == 4
        # Teach the router its per-request cost (1 s) now that 4 requests
        # hold the fleet: estimated wait = 4 in-flight x 1 s = 4 s.
        router.metrics.observe("fast", 1.0)

        # 4 s estimated wait: 'fast' (0.5 s deadline) is shed with a
        # jittered hint + the router queue depth...
        with pytest.raises(RouterBusyError) as e:
            router.predict([object()], klass="fast")
        assert e.value.retry_after_s > 0
        assert e.value.queue_depth == 4
        # ...while 'ensemble' (60 s deadline) is still admitted at the very
        # same queue depth — the per-class SLO differentiation.
        router._admit(router.classes["ensemble"], "rid-ensemble")

        block.set()
        for t in threads:
            t.join(10)
        assert not errors, errors
        snap = router.metrics.snapshot()
        assert snap["per_class"]["fast"]["shed"] == 1
        assert snap["per_class"]["fast"]["requests"] == 5
        assert snap["shed_total"] == 1

        # Unknown class is a caller error, not a shed.
        with pytest.raises(ValueError):
            router.predict([object()], klass="nope")
    finally:
        block.set()
        router.close()

    # No replicas at all: explicit retryable 503, never a hang.
    empty = Router([], autostart_health=False)
    with pytest.raises(NoReplicaAvailableError) as e:
        empty.predict([object()])
    assert e.value.retryable and e.value.retry_after_s > 0
    empty.close()

    # A class-less request against a custom-class fleet takes the fleet's
    # default (tightest deadline), not a hard-coded "fast".
    custom = Router(
        [_StubReplica("only")],
        classes={"batch": {"deadline_s": 30.0}, "slow": {"deadline_s": 60.0}},
        autostart_health=False,
    )
    assert custom.default_class == "batch"
    res = custom.predict([object()])
    assert res.klass == "batch"
    custom.close()


# ----------------------------------------------- 3. Retry-After propagation
def pytest_replica_backpressure_propagates_jittered_retry_after():
    bp = ReplicaBackpressureError("queue full", retry_after_s=3.0)
    stubs = [
        _StubReplica("a", predict_exc=bp),
        _StubReplica("b", predict_exc=bp),
    ]
    router = Router(stubs, autostart_health=False, jitter_seed=7)
    try:
        hints = []
        for _ in range(2):
            with pytest.raises(RouterBusyError) as e:
                router.predict([object()], klass="fast")
            err = e.value
            # The replica's own hint is surfaced verbatim, the caller-facing
            # hint is jittered around it (0.5x-1.5x), and the hop log shows
            # both replicas were tried before shedding fleet-wide.
            assert err.replica_retry_after_s == 3.0
            assert 1.5 <= err.retry_after_s <= 4.5
            assert [h["outcome"] for h in err.hops] == (
                ["backpressure", "backpressure"]
            )
            hints.append(err.retry_after_s)
        assert hints[0] != hints[1]  # jitter desynchronizes retries
    finally:
        router.close()

    # One replica sheds, the other absorbs: retry within the deadline wins.
    shed = _StubReplica("a", predict_exc=bp)
    ok = _StubReplica("b")
    router = Router([shed, ok], autostart_health=False, jitter_seed=1)
    try:
        rid = _rid_with_primary(("a", "b"), "a")
        res = router.predict([object()], klass="fast", request_id=rid)
        assert res.replica == "b"
        assert [h["replica"] for h in res.hops] == ["a", "b"]
        assert [h["outcome"] for h in res.hops] == ["backpressure", "ok"]
        assert router.metrics.read_counters("retries_total")[
            "retries_total"
        ] == 1
    finally:
        router.close()


# ------------------------------------------------- 4. drain/readmit/eject
def pytest_degraded_replica_drains_and_readmits():
    a = _StubReplica(
        "a", health={"ok": True, "compiled_buckets": 1, "bad_batches": 0}
    )
    b = _StubReplica(
        "b", health={"ok": True, "compiled_buckets": 1, "bad_batches": 0}
    )
    router = Router(
        [a, b],
        autostart_health=False,
        readmit_polls=2,
        eject_after=2,
        jitter_seed=0,
    )
    try:
        router.poll_health()  # establishes each replica's fault baseline
        assert {
            n: s["state"] for n, s in router.states().items()
        } == {"a": "admitted", "b": "admitted"}

        # Sticky-degraded transition: a's fault counters MOVED since the
        # last poll -> drain (out of the ring, no new traffic).
        a.health_doc["bad_batches"] = 2
        a.health_doc["degraded"] = True
        router.poll_health()
        assert router.states()["a"]["state"] == "draining"
        rid = _rid_with_primary(("a", "b"), "a")
        res = router.predict([object()], request_id=rid)
        assert res.replica == "b"  # a's keyspace fails over to b
        assert a.calls == []

        # Counters quiet for readmit_polls polls -> readmitted (the sticky
        # degraded FLAG alone must not pin it out forever).
        router.poll_health()
        router.poll_health()
        assert router.states()["a"]["state"] == "admitted"
        counters = router.metrics.read_counters(
            "drains_total", "readmissions_total"
        )
        assert counters["drains_total"] == 1
        assert counters["readmissions_total"] == 1

        # Health endpoint dead for eject_after polls -> ejected; recovery
        # re-enters through warming (hydration re-verified) then admits.
        a.health_exc = ConnectionError("down")
        router.poll_health()
        router.poll_health()
        assert router.states()["a"]["state"] == "ejected"
        a.health_exc = None
        router.poll_health()
        assert router.states()["a"]["state"] == "warming"
        router.poll_health()
        assert router.states()["a"]["state"] == "admitted"
        assert (
            router.metrics.read_counters("ejections_total")[
                "ejections_total"
            ]
            == 1
        )

        # A WARMING replica whose health keeps failing ejects too (a dead
        # scale-up target must not be polled forever as "warming").
        dead = _StubReplica("c")
        dead.health_exc = ConnectionError("never came up")
        spawn = router.scale_up("c", lambda: dead)
        spawn.join(10)
        router.poll_health()
        router.poll_health()
        assert router.states()["c"]["state"] == "ejected"
    finally:
        router.close()


# ------------------------------------- 5. correlation-id hop log (engines)
@pytest.mark.mpi_skip
def pytest_correlation_id_hop_log_through_two_inprocess_replicas():
    model, variables, graphs, ladder = _fleet_parts()
    # Warm: a first request would pay the forward's compile inside the
    # router's 60 s hop wait, which six busy test workers can outlast.
    eng_a = _engine(model, variables, ladder, warmup=True)
    eng_b = _engine(model, variables, ladder, warmup=True)
    router = Router(
        [
            InProcessReplica("eng-a", eng_a),
            InProcessReplica("eng-b", eng_b),
        ],
        autostart_health=False,
        jitter_seed=0,
    )
    try:
        # Happy path: one hop, the caller's id preserved end to end.
        rid = _rid_with_primary(("eng-a", "eng-b"), "eng-a")
        res = router.predict([graphs[0]], request_id=rid)
        assert res.request_id == rid
        assert len(res.hops) == 1 and res.hops[0]["outcome"] == "ok"
        assert res.hops[0]["replica"] == res.replica == "eng-a"

        # Failover path: the primary dies mid-fleet; the SAME id rides the
        # retry hop and the hop log records the whole journey.
        eng_a.close()
        res2 = router.predict([graphs[1]], request_id=rid)
        assert res2.request_id == rid
        assert [h["replica"] for h in res2.hops] == ["eng-a", "eng-b"]
        assert [h["outcome"] for h in res2.hops] == ["down", "ok"]
        # Dispatch-observed failure drains the dead replica immediately.
        assert router.states()["eng-a"]["state"] == "draining"
    finally:
        router.close()
        eng_a.close()
        eng_b.close()


# ---------------------------------------------------- 6. bit-exactness
@pytest.mark.mpi_skip
def pytest_router_bitexact_vs_direct_engine_at_matched_buckets():
    model, variables, graphs, ladder = _fleet_parts()
    # Warm, as the hop log's replicas above: no compile inside a timed wait.
    direct = _engine(model, variables, ladder, warmup=True)
    eng_a = _engine(model, variables, ladder, warmup=True)
    eng_b = _engine(model, variables, ladder, warmup=True)
    router = Router(
        [
            InProcessReplica("eng-a", eng_a),
            InProcessReplica("eng-b", eng_b),
        ],
        autostart_health=False,
    )
    try:
        used = set()
        for i, g in enumerate(graphs):
            want = [np.asarray(h) for h in direct.predict([g])[0]]
            res = router.predict([g], request_id=f"bitexact-{i}")
            used.add(res.replica)
            got = [np.asarray(h) for h in res.results[0]]
            assert len(got) == len(want)
            for w, o in zip(want, got):
                assert w.dtype == o.dtype and np.array_equal(w, o)
        # The comparison exercised the fleet, not one lucky replica.
        assert used == {"eng-a", "eng-b"}
    finally:
        router.close()
        direct.close()
        eng_a.close()
        eng_b.close()


# ------------------------------------------------------- 7. warm spin-up
@pytest.mark.mpi_skip
def pytest_warm_spinup_admits_only_after_hydration_with_zero_compiles(
    tmp_path,
):
    from hydragnn_tpu.analysis.sentinel import compile_count

    store = str(tmp_path / "graftcache")
    model, variables, graphs, ladder = _fleet_parts()
    # Replica A compiles the ladder cold and persists it to the shared store.
    eng_a = _engine(model, variables, ladder, compile_cache=store, warmup=True)
    router = Router(
        [InProcessReplica("eng-a", eng_a)],
        autostart_health=False,
        expected_rungs=len(ladder),
        jitter_seed=0,
    )
    spawned = {}
    release = threading.Event()

    def factory():
        eng_b = _engine(
            model, variables, ladder, compile_cache=store, warmup=False
        )
        c0 = compile_count()
        eng_b.warmup()
        spawned["warmup_xla_compiles"] = compile_count() - c0
        spawned["engine"] = eng_b
        release.wait(10)  # hold the spawn open so WARMING is observable
        return InProcessReplica("eng-b", eng_b)

    try:
        thread = router.scale_up("eng-b", factory)
        # While spawning/warming the new replica takes NO traffic.
        assert router.states()["eng-b"]["state"] == "warming"
        rid_b = _rid_with_primary(("eng-a", "eng-b"), "eng-b")
        res = router.predict([graphs[0]], request_id=rid_b)
        assert res.replica == "eng-a"
        release.set()
        thread.join(30)
        assert thread.is_alive() is False
        router.poll_health()
        assert router.states()["eng-b"]["state"] == "admitted"

        # The whole ladder came from the shared store: hydration, not
        # compilation (the 27x-warm-spin-up property this tier exists for).
        assert spawned["warmup_xla_compiles"] == 0
        hydrated = spawned["engine"].metrics.read_counters(
            "exec_cache_hydrated_total", "cache_misses_total"
        )
        assert hydrated["exec_cache_hydrated_total"] == len(ladder)
        assert hydrated["cache_misses_total"] == 0
        assert (
            router.metrics.read_counters("warm_admissions_total")[
                "warm_admissions_total"
            ]
            == 1
        )

        # Admitted replica serves its keyspace, bit-exact with replica A.
        res_b = router.predict([graphs[0]], request_id=rid_b)
        assert res_b.replica == "eng-b"
        res_a = router.predict(
            [graphs[0]],
            request_id=_rid_with_primary(("eng-a", "eng-b"), "eng-a"),
        )
        for ha, hb in zip(res_a.results[0], res_b.results[0]):
            assert np.array_equal(np.asarray(ha), np.asarray(hb))
    finally:
        release.set()
        router.close()
        eng_a.close()
        if "engine" in spawned:
            spawned["engine"].close()


# ------------------------------------------ 8. HTTP front end + HttpReplica
@pytest.mark.mpi_skip
def pytest_router_http_end_to_end_with_http_replica():
    model, variables, graphs, ladder = _fleet_parts()
    # Warm, as the in-process replicas above: the router's hop wait answers
    # 503 after 60 s, which a first compile under six busy workers outlasts.
    engine = _engine(model, variables, ladder, warmup=True)
    serve = InferenceServer(engine, port=0, replica_id="r0").start_background()
    replica = HttpReplica("r0", f"http://127.0.0.1:{serve.port}")
    router = Router([replica], autostart_health=False)
    front = RouterServer(router, port=0).start_background()
    base = f"http://127.0.0.1:{front.port}"
    try:
        # Replica-mode plumbing: /healthz names the replica and carries the
        # warmup-provenance counters the warm-spin-up gate consumes.
        h = replica.health()
        assert h["replica"] == "r0"
        assert "hydrated_buckets" in h and "compiled_fresh_buckets" in h

        doc = {
            "graphs": [
                {
                    "x": np.asarray(g.x).tolist(),
                    "edge_index": np.asarray(g.edge_index).tolist(),
                    "edge_attr": np.asarray(g.edge_attr).tolist(),
                }
                for g in graphs[:2]
            ]
        }
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps(doc).encode(),
            headers={
                "Content-Type": "application/json",
                "X-HydraGNN-Request-Id": "route-e2e-1",
            },
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            assert resp.headers["X-HydraGNN-Request-Id"] == "route-e2e-1"
            payload = json.loads(resp.read())
        assert payload["request_id"] == "route-e2e-1"
        assert payload["replica"] == "r0"
        assert [h["outcome"] for h in payload["hops"]] == ["ok"]
        # Bit-exact through TWO HTTP layers (router front + replica hop):
        # float32 repr round-trips exactly.
        want = engine.predict(graphs[:2], request_id="direct")
        for per_graph, ref in zip(payload["predictions"], want):
            for h_doc, r in zip(per_graph, ref):
                assert np.array_equal(
                    np.asarray(h_doc, np.float32), np.asarray(r)
                )

        with urllib.request.urlopen(base + "/healthz", timeout=10) as resp:
            health = json.loads(resp.read())
        assert health["ok"] is True and health["admitted"] == 1
        assert health["replicas"]["r0"]["state"] == "admitted"

        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        assert "hydragnn_route_requests_total 1\n" in text  # anchored: not 1x
        assert 'hydragnn_route_replica_state{replica="r0",state="admitted"}' in text
        assert 'hydragnn_route_latency_seconds_bucket{class="fast"' in text

        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/nothing", timeout=10)
        assert e.value.code == 404
    finally:
        front.shutdown(close_router=True)
        serve.shutdown()  # closes the engine
