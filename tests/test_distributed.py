"""Distributed (virtual 8-device CPU mesh) tests — the analog of the reference's
2-rank MPI CI pass (SURVEY.md §4): data-parallel training via shard_map + psum,
and edge-sharded graph parallelism, which must reproduce single-device math
EXACTLY (same batch, same seed → same updated parameters)."""

import numpy as np
import jax
import pytest

from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.models import create_model, init_model_variables
from hydragnn_tpu.parallel import make_mesh
from hydragnn_tpu.train.trainer import (
    create_train_state,
    make_eval_step_dp,
    make_train_step,
    make_train_step_dp,
    stack_batches,
)
from hydragnn_tpu.utils.optimizer import select_optimizer

HEADS = {
    "graph": {
        "num_sharedlayers": 2,
        "dim_sharedlayers": 8,
        "num_headlayers": 2,
        "dim_headlayers": [12, 12],
    },
    "node": {"num_headlayers": 2, "dim_headlayers": [8, 8], "type": "mlp"},
}


def _graphs(rng, count, fdim=1):
    out = []
    for _ in range(count):
        n = int(rng.integers(4, 9))
        x = rng.normal(size=(n, fdim)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        ei = np.concatenate([ei, ei[::-1]], axis=1)
        ea = (rng.random((ei.shape[1], 1)) + 0.1).astype(np.float32)
        y = np.concatenate([[x.sum()], x[:, 0]])
        y_loc = np.array([[0, 1, 1 + n]], dtype=np.int64)
        out.append(GraphSample(x=x, pos=np.zeros((n, 3), np.float32), y=y,
                               y_loc=y_loc, edge_index=ei, edge_attr=ea))
    return out


def _setup(model_type="PNA", graph_axis=None, edge_dim=1, optimizer="AdamW"):
    types, dims = ("graph", "node"), (1, 1)
    model = create_model(
        model_type, 1, 8, dims, types, HEADS, [1.0, 1.0], 2,
        max_neighbours=8, edge_dim=edge_dim,
        pna_deg=[0, 0, 8, 8] if model_type == "PNA" else None,
    )
    # Dropout off: stochastic attention masks are sampled per edge-shard and can
    # never match across shardings; determinism is required for equivalence.
    model = model.clone(dropout=0.0)
    graphs = _graphs(np.random.default_rng(0), 8)
    batch = collate_graphs(graphs, types, dims, edge_dim=edge_dim)
    # Init outside shard_map (collective axes unbound there), then bind the axis.
    variables = init_model_variables(model, batch)
    if graph_axis:
        model = model.clone(graph_axis=graph_axis)
    opt = select_optimizer(optimizer, 1e-2)
    state = create_train_state(model, variables, opt)
    return model, opt, state, batch, types, dims, graphs


@pytest.mark.parametrize("model_type", ["PNA", "GAT", "SAGE", "MFC", "GIN", "CGCNN"])
def pytest_graph_parallel_matches_single_device(model_type):
    """Edge-sharded message passing over a 4-way 'graph' axis must produce
    bitwise-level-identical training math to one device."""
    edge_dim = 1 if model_type in ("PNA", "CGCNN") else None
    # SGD: parameter delta is linear in the gradient, so the comparison checks
    # gradient math itself (AdamW would amplify float32 noise near zero grads).
    model_s, opt, state_s, batch, *_ = _setup(model_type, None, edge_dim, "SGD")
    step_s = make_train_step(model_s, opt)
    rng = jax.random.PRNGKey(0)
    new_s, m_s = step_s(state_s, batch, rng)

    # Graph-parallel over mesh (1 data, 4 graph).
    mesh = make_mesh(data_axis=1, graph_axis=4)
    model_g, opt_g, state_g, batch_g, *_ = _setup(model_type, "graph", edge_dim, "SGD")
    step_g = make_train_step_dp(model_g, opt_g, mesh)
    stacked = stack_batches([batch_g], 1)
    new_g, m_g = step_g(state_g, stacked, rng)

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


def pytest_dp_training_runs_and_reduces():
    """8-way data parallelism: metrics are globally reduced and training makes
    progress; the last partial device group (empty padded batches) must not
    poison gradients (NaN guard)."""
    types, dims = ("graph", "node"), (1, 1)
    model = create_model("SAGE", 1, 8, dims, types, HEADS, [1.0, 1.0], 2)
    mesh = make_mesh(data_axis=8, graph_axis=1)
    graphs = _graphs(np.random.default_rng(1), 40)
    per_dev = [
        collate_graphs(graphs[i::8], types, dims, num_nodes_pad=64,
                       num_edges_pad=128, num_graphs_pad=6)
        for i in range(8)
    ]
    batch = stack_batches(per_dev, 8)
    variables = init_model_variables(model, per_dev[0])
    opt = select_optimizer("AdamW", 1e-2)
    state = create_train_state(model, variables, opt)
    step = make_train_step_dp(model, opt, mesh)
    rng = jax.random.PRNGKey(0)

    losses = []
    for i in range(20):
        state, m = step(state, batch, rng)
        losses.append(float(m["loss"]) / float(m["count"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert float(m["count"]) == 40.0  # all real graphs counted exactly once

    # Partial group: only 3 of 8 device slots have real data. NB: the train
    # step DONATES its input state — the old state object is consumed, all
    # later use must go through the returned state.
    partial = stack_batches(per_dev[:3], 8)
    state2, m2 = step(state, partial, rng)
    assert all(
        np.all(np.isfinite(np.asarray(l)))
        for l in jax.tree_util.tree_leaves(state2.params)
    )

    # Eval step reduces across devices too.
    eval_step = make_eval_step_dp(model, mesh)
    em, outputs = eval_step(state2, batch)
    assert float(em["count"]) == 40.0
    assert outputs[0].shape[0] == 8  # leading device axis restored


def pytest_slurm_nodelist_parser():
    """Scheduler-hostlist expansion parity (reference parse_slurm_nodelist,
    /root/reference/hydragnn/utils/distributed.py:43-74)."""
    from hydragnn_tpu.parallel import parse_slurm_nodelist

    assert parse_slurm_nodelist("or-condo-g04") == ["or-condo-g04"]
    assert parse_slurm_nodelist("or-condo-g[05,07-08,13]") == [
        "or-condo-g05", "or-condo-g07", "or-condo-g08", "or-condo-g13",
    ]
    assert parse_slurm_nodelist("or-condo-g[05,07-08,13],or-condo-h[01,12]") == [
        "or-condo-g05", "or-condo-g07", "or-condo-g08", "or-condo-g13",
        "or-condo-h01", "or-condo-h12",
    ]
    # zero-padded widths preserved
    assert parse_slurm_nodelist("n[008-011]") == ["n008", "n009", "n010", "n011"]


def pytest_coordinator_address_resolution(monkeypatch):
    """MASTER_ADDR > LSB_HOSTS > SLURM_NODELIST > localhost (reference
    distributed.py:120-132), port from MASTER_PORT (default 8889)."""
    from hydragnn_tpu.parallel import get_local_rank, resolve_coordinator_address

    for var in ("MASTER_ADDR", "MASTER_PORT", "LSB_HOSTS", "SLURM_NODELIST"):
        monkeypatch.delenv(var, raising=False)
    assert resolve_coordinator_address() == "127.0.0.1:8889"

    monkeypatch.setenv("SLURM_NODELIST", "cades-a[02-03]")
    assert resolve_coordinator_address() == "cades-a02:8889"

    # LSF: first entry is the batch node; rendezvous on the first compute host.
    monkeypatch.setenv("LSB_HOSTS", "batch01 h41n03 h41n04")
    assert resolve_coordinator_address() == "h41n03:8889"

    monkeypatch.setenv("MASTER_ADDR", "10.0.0.7")
    monkeypatch.setenv("MASTER_PORT", "7777")
    assert resolve_coordinator_address() == "10.0.0.7:7777"

    monkeypatch.delenv("OMPI_COMM_WORLD_LOCAL_RANK", raising=False)
    monkeypatch.setenv("SLURM_LOCALID", "3")
    assert get_local_rank() == 3
    monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_RANK", "1")
    assert get_local_rank() == 1


def pytest_local_size_detection(monkeypatch):
    from hydragnn_tpu.parallel import get_local_size

    for var in ("OMPI_COMM_WORLD_LOCAL_SIZE", "SLURM_NTASKS_PER_NODE"):
        monkeypatch.delenv(var, raising=False)
    assert get_local_size() == 1
    monkeypatch.setenv("SLURM_NTASKS_PER_NODE", "4(x2)")
    assert get_local_size() == 4
    monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_SIZE", "2")
    assert get_local_size() == 2


def pytest_local_device_slot_same_family(monkeypatch):
    """local_device_ids placement must derive rank+size from ONE launcher
    family; a partial env (rank without size, or vice versa) means default
    claim-all placement."""
    from hydragnn_tpu.parallel.distributed import _local_device_slot

    for var in (
        "OMPI_COMM_WORLD_LOCAL_RANK", "OMPI_COMM_WORLD_LOCAL_SIZE",
        "SLURM_LOCALID", "SLURM_NTASKS_PER_NODE",
    ):
        monkeypatch.delenv(var, raising=False)
    assert _local_device_slot() is None
    monkeypatch.setenv("SLURM_LOCALID", "2")  # rank without size: default
    assert _local_device_slot() is None
    monkeypatch.setenv("SLURM_NTASKS_PER_NODE", "4(x2)")
    assert _local_device_slot() == 2
    monkeypatch.setenv("SLURM_LOCALID", "0")
    monkeypatch.setenv("SLURM_NTASKS_PER_NODE", "1")  # 1 proc/host: default
    assert _local_device_slot() is None
    monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_RANK", "0")
    monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_SIZE", "2")
    assert _local_device_slot() == 0


def pytest_hostlist_and_tasks_grammar(monkeypatch):
    """Hard SLURM grammar: multi-bracket names, suffixes, heterogeneous
    tasks-per-node lists — must parse, never crash into sequential fallback."""
    from hydragnn_tpu.parallel import parse_slurm_nodelist
    from hydragnn_tpu.parallel.distributed import (
        _local_device_slot,
        _tasks_per_node_counts,
    )

    assert parse_slurm_nodelist("rack[1-2]n[1-2]") == [
        "rack1n1", "rack1n2", "rack2n1", "rack2n2",
    ]
    assert parse_slurm_nodelist("tux[1-2]-ib") == ["tux1-ib", "tux2-ib"]
    assert _tasks_per_node_counts("4(x2),3") == [4, 4, 3]
    assert _tasks_per_node_counts("4,2") == [4, 2]

    for var in (
        "OMPI_COMM_WORLD_LOCAL_RANK", "OMPI_COMM_WORLD_LOCAL_SIZE",
        "SLURM_LOCALID", "SLURM_NTASKS_PER_NODE",
    ):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("SLURM_LOCALID", "1")
    monkeypatch.setenv("SLURM_NTASKS_PER_NODE", "4(x2),3")
    assert _local_device_slot() == 1
    monkeypatch.setenv("SLURM_NTASKS_PER_NODE", "garbled")
    assert _local_device_slot() is None  # unparseable → default placement


def pytest_tpu_expected_reads_the_platform_list_without_a_backend(monkeypatch):
    """Decided from JAX_PLATFORMS / jax_platforms alone when either names
    platforms — the checks that call it run before anything may touch (and so
    claim) a chip."""
    from hydragnn_tpu.parallel import distributed as dist

    saved = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", "tpu,cpu")
        assert dist.tpu_expected()
        jax.config.update("jax_platforms", "cpu")
        assert not dist.tpu_expected()
        jax.config.update("jax_platforms", None)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        assert dist.tpu_expected()
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert not dist.tpu_expected()
    finally:
        jax.config.update("jax_platforms", saved)


def pytest_several_ranks_per_tpu_host_fail_fast(monkeypatch):
    """local_device_ids hides CUDA/ROCm devices only: on a TPU host every
    rank would claim every chip. setup_ddp refuses before it initialises
    anything, unless the launcher already hid the chips."""
    from hydragnn_tpu.parallel import distributed as dist

    for var, val in (
        ("OMPI_COMM_WORLD_SIZE", "2"), ("OMPI_COMM_WORLD_RANK", "1"),
        ("OMPI_COMM_WORLD_LOCAL_RANK", "1"), ("OMPI_COMM_WORLD_LOCAL_SIZE", "2"),
    ):
        monkeypatch.setenv(var, val)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.setattr(dist, "tpu_expected", lambda: True)
    monkeypatch.setattr(dist, "_distributed_active", lambda: False)

    def never(**kw):
        raise AssertionError("jax.distributed.initialize must not be reached")

    monkeypatch.setattr(jax.distributed, "initialize", never)
    with pytest.raises(RuntimeError, match="TPU_VISIBLE_CHIPS"):
        dist.setup_ddp()


def pytest_router_spawn_refused_on_a_tpu_host(monkeypatch, capsys):
    from hydragnn_tpu.parallel import distributed as dist
    from hydragnn_tpu.route.__main__ import main as router_main

    monkeypatch.setattr(dist, "tpu_expected", lambda: True)
    rc = router_main(["--config", "unused.json", "--spawn", "2"])
    assert rc == 2
    assert "--replica-url" in capsys.readouterr().err
