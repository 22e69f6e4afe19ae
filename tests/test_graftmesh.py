"""graftmesh — the tier-1-runnable distributed harness + mesh training arms
(docs/DISTRIBUTED.md): loopback rendezvous/worker semantics, DP and
graph-partitioned steps under a REAL >1-size virtual mesh with numerics gated
against single-device, overlapped gradient-sync arms allclose vs the
single-psum step, mesh graftcache hydration with a zero-compile spy,
loss-scale backoff lockstep across shards, StepGuard rollback under mesh,
and the bad-mesh config contract."""

import os
import sys

import numpy as np
import pytest

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hydragnn_tpu.faults import FaultCounters, FaultPlan
from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.models import create_model, init_model_variables
from hydragnn_tpu.parallel import (
    LoopbackError,
    LoopbackRendezvous,
    ProxyRendezvous,
    make_mesh,
    run_workers,
)
from hydragnn_tpu.preprocess.dataloader import GraphDataLoader
from hydragnn_tpu.train.train_validate_test import TrainingDriver
from hydragnn_tpu.train.trainer import (
    create_train_state,
    make_train_step,
    make_train_step_dp,
    stack_batches,
)
from hydragnn_tpu.utils.optimizer import select_optimizer

HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 4,
        "num_headlayers": 1,
        "dim_headlayers": [4],
    },
}


@pytest.fixture(autouse=True)
def _reset_fault_counters():
    FaultCounters.reset()
    yield
    FaultCounters.reset()


def _dataset(rng, count=24, lo=4, hi=12):
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


def _model_and_state(loader, optimizer="AdamW", lr=5e-3):
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    variables = init_model_variables(model, next(iter(loader)))
    opt = select_optimizer(optimizer, lr)
    return model, opt, create_train_state(model, variables, opt)


def _finite_params(driver_or_state):
    state = getattr(driver_or_state, "state", driver_or_state)
    return all(
        np.isfinite(np.asarray(l)).all()
        for l in jax.tree_util.tree_leaves(state.params)
    )


# -------------------------------------------------------- loopback rendezvous
def pytest_loopback_exchange_broadcast_barrier():
    """N workers allgather rank payloads in rank order; broadcast picks the
    source's; barriers verify lockstep tags."""
    def fn(w):
        got = w.exchange(w.rank * 10, tag="t1")
        assert got == [0, 10, 20, 30]
        assert w.broadcast("x" if w.rank == 2 else None, src=2) == "x"
        w.barrier("done")
        return w.rank

    assert run_workers(4, fn) == [0, 1, 2, 3]


def pytest_loopback_worker_death_aborts_peers():
    """A dying worker must abort the rendezvous so peers raise instead of
    hanging to the barrier timeout; the ROOT error is surfaced."""
    def fn(w):
        if w.rank == 1:
            raise RuntimeError("injected worker death")
        w.exchange(w.rank)  # peers block here until the abort
        return w.rank

    with pytest.raises(LoopbackError, match="injected worker death"):
        run_workers(3, fn)


def pytest_loopback_lockstep_divergence_detected():
    """Workers calling DIFFERENT collectives (the classic distributed
    deadlock) fail loudly with both tags named."""
    def fn(w):
        if w.rank == 0:
            w.exchange(1, tag="step")
        else:
            w.exchange(1, tag="eval")

    with pytest.raises(LoopbackError, match="divergence|broken"):
        run_workers(2, fn)


def pytest_proxy_rendezvous_barrier_and_allgather():
    """The spawn-path rendezvous: same barrier-with-data protocol over a real
    localhost TCP socket (clients here are threads — the wire protocol is
    what's under test; process-spawn cost belongs to the slow suite)."""
    rdv = ProxyRendezvous(world_size=3, timeout_s=30.0)
    port = rdv.serve()
    addr = f"127.0.0.1:{port}"
    try:
        def fn(w):
            # Tag REUSE across rounds (a heartbeat loop barriers on one
            # name): each round must return fresh payloads, never round-1
            # leftovers — the coordinator evicts served generations.
            for rnd in range(3):
                out = ProxyRendezvous.allgather(
                    addr, "meta", w.rank,
                    {"rank": w.rank, "round": rnd}, timeout_s=30.0,
                )
                assert [o["rank"] for o in out] == [0, 1, 2]
                assert [o["round"] for o in out] == [rnd] * 3, out
                ProxyRendezvous.barrier(addr, "done", w.rank, timeout_s=30.0)
            return True

        assert run_workers(3, fn) == [True, True, True]
    finally:
        rdv.close()


# --------------------------------------------- DP numerics vs single device
def pytest_dp_mesh_convergence_parity_vs_single_device():
    """Same-seed convergence-parity gate (documented): per-graph RMSE losses
    are not additive across shards (sqrt is nonlinear), so DP-vs-single is
    gated at trajectory level — identical data, identical init, 12 steps;
    both finite and decreasing, final losses within a 1.5x band + 0.02
    absolute allowance (observed ratio on this workload ~1.0; the band
    absorbs fp32 reduction order + the per-shard loss decomposition)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs a 4-device (virtual) mesh")
    graphs = _dataset(np.random.default_rng(0), count=16)
    loader = _loader(graphs, batch_size=16)  # one full batch
    model, opt, state_s = _model_and_state(loader)
    batch_full = next(iter(loader))
    step_s = make_train_step(model, opt, donate=False)
    rng = jax.random.PRNGKey(0)
    losses_s = []
    for _ in range(12):
        state_s, m = step_s(state_s, batch_full, rng)
        losses_s.append(float(m["loss"]) / float(m["count"]))

    mesh = make_mesh(data_axis=4, graph_axis=1)
    _, _, state_d = _model_and_state(loader)
    per_dev = [
        collate_graphs(
            graphs[i::4], ("graph",), (1,),
            num_nodes_pad=64, num_edges_pad=128, num_graphs_pad=5,
        )
        for i in range(4)
    ]
    stacked = stack_batches(per_dev, 4)
    step_d = make_train_step_dp(model, opt, mesh, donate=False)
    losses_d = []
    for _ in range(12):
        state_d, m = step_d(state_d, stacked, rng)
        losses_d.append(float(m["loss"]) / float(m["count"]))

    assert all(np.isfinite(losses_s)) and all(np.isfinite(losses_d))
    assert losses_s[-1] < losses_s[0] and losses_d[-1] < losses_d[0]
    band = 1.5 * losses_s[-1] + 0.02
    assert losses_d[-1] <= band, (losses_d[-1], losses_s[-1])
    assert losses_s[-1] <= 1.5 * losses_d[-1] + 0.02, (losses_s, losses_d)


@pytest.mark.parametrize("model_type", ["PNA", "GAT"])
def pytest_graph_partitioned_csr_zero_searchsorted(monkeypatch, model_type):
    """Graph-partitioned steps consume the CSR contract per edge shard
    (localized row_ptr — the halo/edge-cut exchange): ZERO searchsorted
    traced under the sorted path, numerics matching single-device within
    fp32 reduction noise. PNA covers the stats family, GAT the softmax
    denominator."""
    if len(jax.devices()) < 4:
        pytest.skip("needs a 4-device (virtual) mesh")
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    import hydragnn_tpu.ops.segment_sorted as srt
    from tests.test_distributed import _setup

    edge_dim = 1 if model_type == "PNA" else None
    model_s, opt, state_s, batch, *_ = _setup(model_type, None, edge_dim, "SGD")
    rng = jax.random.PRNGKey(0)
    step_s = make_train_step(model_s, opt)
    new_s, m_s = step_s(state_s, batch, rng)

    mesh = make_mesh(data_axis=1, graph_axis=4)
    model_g, opt_g, state_g, batch_g, *_ = _setup(
        model_type, "graph", edge_dim, "SGD"
    )
    step_g = make_train_step_dp(model_g, opt_g, mesh)
    before = srt.searchsorted_calls()
    new_g, m_g = step_g(state_g, stack_batches([batch_g], 1), rng)
    assert srt.searchsorted_calls() == before, (
        "graph-partitioned trace derived boundaries via searchsorted — the "
        "CSR localization contract broke"
    )
    np.testing.assert_allclose(
        float(m_s["loss"]), float(m_g["loss"]), rtol=1e-5, atol=1e-6
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(new_s.params),
        jax.tree_util.tree_leaves(new_g.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


# ------------------------------------------------------- overlapped grad sync
def pytest_overlap_arms_grads_allclose_vs_single_psum():
    """The bucketed (psum-in-backward) and ring (ppermute) arms must produce
    the same updated parameters as the single-psum step from identical state
    — the weighted-loss construction makes them equal up to fp32 reduction
    order. Tiny bucket target forces MANY buckets (every leaf its own
    collective), the harshest composition."""
    if len(jax.devices()) < 4:
        pytest.skip("needs a 4-device (virtual) mesh")
    graphs = _dataset(np.random.default_rng(1), count=16)
    loader = _loader(graphs)
    model, _, _ = _model_and_state(loader)
    opt = select_optimizer("SGD", 1e-2)
    per_dev = [
        collate_graphs(
            graphs[i::4], ("graph",), (1,),
            num_nodes_pad=64, num_edges_pad=128, num_graphs_pad=5,
        )
        for i in range(4)
    ]
    stacked = stack_batches(per_dev, 4)
    mesh = make_mesh(data_axis=4, graph_axis=1)
    rng = jax.random.PRNGKey(0)
    results = {}
    for arm in ("single", "bucketed", "ring"):
        variables = init_model_variables(model, per_dev[0])
        state = create_train_state(model, variables, opt)
        step = make_train_step_dp(
            model, opt, mesh, donate=False, grad_sync=arm,
            grad_bucket_mb=1e-5,  # ~10 bytes: one bucket per leaf
        )
        results[arm] = step(state, stacked, rng)
    ref_params = jax.tree_util.tree_leaves(results["single"][0].params)
    ref_loss = float(results["single"][1]["loss"])
    for arm in ("bucketed", "ring"):
        assert float(results[arm][1]["loss"]) == pytest.approx(
            ref_loss, rel=1e-6
        )
        for a, b in zip(
            ref_params, jax.tree_util.tree_leaves(results[arm][0].params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
            )


def pytest_bucket_plan_reverse_order_and_size_targets():
    from hydragnn_tpu.parallel import plan_buckets

    params = {
        "a": np.zeros((256,), np.float32),   # 1 KiB
        "b": np.zeros((256,), np.float32),
        "c": np.zeros((2048,), np.float32),  # 8 KiB — exceeds target alone
    }
    plan = plan_buckets(params, bucket_bytes=2048)
    leaves = jax.tree_util.tree_leaves(params)
    # Reverse flatten order: the LAST leaf (backward-first) leads the plan.
    assert plan[0][0] == len(leaves) - 1
    covered = sorted(i for b in plan for i in b)
    assert covered == list(range(len(leaves)))  # exact partition
    # The oversized leaf sits alone in its bucket.
    sizes = [
        sum(leaves[i].size * 4 for i in bucket) for bucket in plan
    ]
    assert any(s > 2048 for s in sizes)  # the 8 KiB leaf
    assert all(len(b) == 1 for b, s in zip(plan, sizes) if s > 2048)


# ------------------------------------------------------------- mesh graftcache
def pytest_mesh_graftcache_hydrates_zero_compiles(tmp_path):
    """Warm-restart property for MESH programs: a second driver over the same
    config/mesh/store hydrates its shard_map step from disk — the
    no_recompile spy proves ZERO XLA compiles — and the hydrated executable
    is bit-exact against the fresh compile."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device (virtual) mesh")
    from hydragnn_tpu.analysis import no_recompile

    store = str(tmp_path / "store")
    graphs = _dataset(np.random.default_rng(2), count=8)
    mesh = make_mesh(data_axis=2, graph_axis=1)

    def build():
        # Model init COMPILES (and legitimately so) — keep driver/model
        # construction OUTSIDE the spy; only the epoch must be compile-free.
        loader = _loader(graphs)
        model, opt, state = _model_and_state(loader)
        driver = TrainingDriver(
            model, opt, state, mesh=mesh, compile_cache=store,
            compile_cache_fingerprint="graftmesh-test",
        )
        loader.set_epoch(0)
        return driver, loader

    driver, loader = build()
    loss_cold, _ = driver.train_epoch(loader)
    assert len(list((tmp_path / "store").glob("*.hexe"))) >= 1
    driver2, loader2 = build()
    with no_recompile(action="raise", label="mesh warm restart"):
        loss_warm, _ = driver2.train_epoch(loader2)
    assert loss_warm == loss_cold


def pytest_cache_key_mesh_component():
    """The mesh axis layout is a CacheKey component: a data:4 program never
    hydrates a data:2 entry."""
    from hydragnn_tpu.cache import CacheKey

    env = {
        "jax_version": "j", "jaxlib_version": "jl",
        "backend": "cpu", "topology": "t",
    }
    base = CacheKey.for_environment("p", "cfg", env=env)
    m2 = CacheKey.for_environment("p", "cfg", env=env, mesh="data:2xgraph:1")
    m4 = CacheKey.for_environment("p", "cfg", env=env, mesh="data:4xgraph:1")
    assert len({base.digest(), m2.digest(), m4.digest()}) == 3
    # Round-trip preserves the component.
    assert CacheKey.from_json(m4.to_json()) == m4
    assert CacheKey.from_json(base.to_json()) == base


# ------------------------------------------------ loss-scale lockstep on mesh
def pytest_loss_scale_backoff_lockstep_across_shards():
    """bf16 + mesh (the PR-11 explicit rejection, now closed): a NaN batch on
    ONE shard overflows the reduced gradient, so EVERY shard skips the update
    and the shared scale backs off exactly once — lockstep post-psum. Params
    stay finite, training continues, the backoff counter reads 1."""
    if len(jax.devices()) < 4:
        pytest.skip("needs a 4-device (virtual) mesh")
    from hydragnn_tpu.telemetry import graftel as telemetry

    telemetry.clear_counters("prec/")
    graphs = _dataset(np.random.default_rng(3), count=32)
    loader = _loader(graphs, batch_size=4)
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    variables = init_model_variables(model, next(iter(loader)))
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    mesh = make_mesh(data_axis=4, graph_axis=1)
    init_scale = 2.0**12
    driver = TrainingDriver(
        model, opt, state, mesh=mesh,
        precision="bf16",
        loss_scale={"init": init_scale, "growth_interval": 1000},
        fault_plan=FaultPlan("nan_grad@1"),
    )
    loader.set_epoch(0)
    loss, _ = driver.train_epoch(loader)
    assert np.isfinite(loss)
    assert _finite_params(driver)
    assert FaultCounters.get("loss_scale_backoff") == 1
    assert float(driver.state.loss_scale.scale) == init_scale / 2


def pytest_step_guard_rollback_under_mesh():
    """StepGuard's consecutive-bad-step rollback fires on the mesh path too:
    a NaN streak longer than max_bad_steps restores the epoch-start snapshot
    (finite, replicated) and training survives."""
    if len(jax.devices()) < 4:
        pytest.skip("needs a 4-device (virtual) mesh")
    graphs = _dataset(np.random.default_rng(4), count=32)
    loader = _loader(graphs, batch_size=4)
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    variables = init_model_variables(model, next(iter(loader)))
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    mesh = make_mesh(data_axis=4, graph_axis=1)
    driver = TrainingDriver(
        model, opt, state, mesh=mesh,
        fault_tolerance={"enabled": True, "max_bad_steps": 2},
        fault_plan=FaultPlan("nan_grad@1-8"),
    )
    loss = None
    for epoch in range(2):
        loader.set_epoch(epoch)
        loss, _ = driver.train_epoch(loader)
    assert np.isfinite(loss)
    assert driver.guard.rollbacks >= 1
    assert FaultCounters.get("rollbacks") >= 1
    assert _finite_params(driver)


# --------------------------------------------------------- bad-mesh contract
def pytest_bad_mesh_config_findings(monkeypatch):
    from hydragnn_tpu.analysis.contracts import check_config

    def findings(training_extra, env_sorted=None, deep=False):
        if env_sorted is None:
            monkeypatch.delenv("HYDRAGNN_SEGMENT_SORTED", raising=False)
        else:
            monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", env_sorted)
        config = {
            "NeuralNetwork": {"Training": dict(training_extra)},
            "Dataset": {},
        }
        report = check_config(config, strict=False, deep=deep)
        return [
            e["message"]
            for e in report["errors"]
            if e["code"] == "bad-mesh"
        ]

    assert findings({"grad_sync": "overlapped"})  # unknown arm
    assert not findings({"grad_sync": "bucketed"})
    assert not findings({"grad_sync": "ring"})
    assert findings({"grad_bucket_mb": 0})
    assert findings({"grad_bucket_mb": "big"})
    assert not findings({"grad_bucket_mb": 4.0})
    # graph_axis with the CSR/sorted contract explicitly disabled.
    assert findings({"graph_axis": 2}, env_sorted="0")
    assert not findings({"graph_axis": 2}, env_sorted="1")
    assert not findings({"graph_axis": 1}, env_sorted="0")
    # elastic knobs nonsense
    assert findings({"elastic": {"min_workers": 4, "max_workers": 2}})
    assert findings({"elastic": {"min_workers": 0}})
    assert findings({"elastic": {"heartbeat_s": -1}})
    assert findings({"elastic": {"workers": 3}})  # unknown knob
    assert findings({"elastic": "auto"})  # not a dict
    assert not findings(
        {"elastic": {"min_workers": 1, "max_workers": 4, "heartbeat_s": 5}}
    )
    # device-count check (deep only — must not fire structurally)
    assert not findings({"graph_axis": 10_000}, deep=False)
    msgs = findings({"graph_axis": 10_000}, deep=True)
    assert msgs and "device" in msgs[0]


def pytest_supervisor_meta_records_mesh_topology(tmp_path, monkeypatch):
    """run_supervised persists the world/mesh topology (elastic restart
    metadata) BEFORE and WITH the attempt log — a restart post-mortem reads
    the launch shape from supervisor.json, not from env archaeology."""
    import json
    import os
    import subprocess
    from types import SimpleNamespace

    from hydragnn_tpu.faults.supervisor import run_supervised

    monkeypatch.setattr(
        subprocess, "run", lambda *a, **k: SimpleNamespace(returncode=0)
    )

    # Elastic configs take the MONITORED child path (Popen + heartbeat
    # drain, graftelastic) instead of subprocess.run — fake that too.
    class _FakeProc:
        pid = 12345

        def poll(self):
            return 0

        def kill(self):
            pass

        def wait(self, timeout=None):
            return 0

    monkeypatch.setattr(subprocess, "Popen", lambda *a, **k: _FakeProc())
    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(REPO, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    training = config["NeuralNetwork"]["Training"]
    training["graph_axis"] = 2
    training["grad_sync"] = "bucketed"
    training["elastic"] = {"min_workers": 1, "max_workers": 2}
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        meta = run_supervised(config, max_restarts=0)
    finally:
        os.chdir(cwd)
    assert meta["completed"]
    assert meta["mesh"]["graph_axis"] == 2
    assert meta["mesh"]["grad_sync"] == "bucketed"
    assert meta["mesh"]["elastic"] == {"min_workers": 1, "max_workers": 2}
    assert meta["mesh"]["world_size"] == 1
    # The elastic membership loop annotates each attempt (graftelastic).
    assert meta["attempts"][0]["world_size"] == 1
    assert meta["attempts"][0]["heartbeats"] == 0
    assert meta["attempts"][0]["stalled"] is False
    assert meta["elastic_transitions"] == []
    run_dir = next((tmp_path / "logs").iterdir())
    with open(run_dir / "supervisor.json") as f:
        assert json.load(f)["mesh"]["grad_sync"] == "bucketed"
