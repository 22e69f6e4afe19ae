"""Model-layer unit tests: every conv family runs forward+grad, and padding must
not change results on real rows (hard part #1 in SURVEY.md §7: padding-correct
statistics in BatchNorm, PNA std/scalers, mean-pool)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.models import create_model, init_model_variables, multihead_rmse_loss

HEADS = {
    "graph": {
        "num_sharedlayers": 2,
        "dim_sharedlayers": 4,
        "num_headlayers": 2,
        "dim_headlayers": [10, 10],
    },
    "node": {"num_headlayers": 2, "dim_headlayers": [4, 4], "type": "mlp"},
}
ALL_MODELS = ["SAGE", "GIN", "MFC", "GAT", "CGCNN", "PNA"]


def _graphs(rng, count=3, fdim=1):
    out = []
    for i in range(count):
        n = int(rng.integers(3, 7))
        x = rng.normal(size=(n, fdim)).astype(np.float32)
        # ring + random chords, symmetric enough for a connected graph
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        ei = np.concatenate([ei, ei[::-1]], axis=1)
        ea = rng.random((ei.shape[1], 1)).astype(np.float32) + 0.1
        y = np.concatenate([[x.sum()], x[:, 0], x[:, 0] ** 2])
        y_loc = np.array([[0, 1, 1 + n, 1 + 2 * n]], dtype=np.int64)
        out.append(
            GraphSample(x=x, pos=np.zeros((n, 3), np.float32), y=y, y_loc=y_loc,
                        edge_index=ei, edge_attr=ea)
        )
    return out


def _build(model_type, edge_dim=None):
    types = ("graph", "node", "node")
    dims = (1, 1, 1)
    model = create_model(
        model_type, 1, 8, dims, types, HEADS, [1.0, 1.0, 1.0], 2,
        max_neighbours=8, edge_dim=edge_dim,
        pna_deg=[0, 0, 4, 4] if model_type == "PNA" else None,
    )
    return model, types, dims


@pytest.mark.parametrize("model_type", ALL_MODELS)
def pytest_forward_and_grad(model_type):
    edge_dim = 1 if model_type in ("PNA", "CGCNN") else None
    model, types, dims = _build(model_type, edge_dim)
    graphs = _graphs(np.random.default_rng(0))
    batch = collate_graphs(graphs, types, dims, edge_dim=edge_dim)
    variables = init_model_variables(model, batch)

    def loss_fn(params):
        out, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            batch, train=True, mutable=["batch_stats"],
            rngs={"dropout": jax.random.PRNGKey(0)},
        )
        loss, _ = multihead_rmse_loss(out, batch, types, model.task_weights)
        return loss

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(variables["params"])
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)
    # At least some gradient signal somewhere.
    assert any(np.abs(np.asarray(g)).max() > 0 for g in flat)


@pytest.mark.parametrize("model_type", ALL_MODELS)
def pytest_padding_invariance(model_type):
    """Outputs on real rows must be identical whatever the pad sizes."""
    edge_dim = 1 if model_type in ("PNA", "CGCNN") else None
    model, types, dims = _build(model_type, edge_dim)
    graphs = _graphs(np.random.default_rng(1))
    small = collate_graphs(graphs, types, dims, edge_dim=edge_dim)
    big = collate_graphs(
        graphs, types, dims, edge_dim=edge_dim,
        num_nodes_pad=small.num_nodes_pad * 2,
        num_edges_pad=small.num_edges_pad * 2,
        num_graphs_pad=small.num_graphs_pad + 3,
    )
    variables = init_model_variables(model, small)
    # train=False: eval path, deterministic (no attention dropout).
    out_s = model.apply(variables, small, train=False)
    out_b = model.apply(variables, big, train=False)
    gm = np.asarray(small.graph_mask)
    nm = np.asarray(small.node_mask)
    for o_s, o_b, t in zip(out_s, out_b, types):
        if t == "graph":
            np.testing.assert_allclose(
                np.asarray(o_s)[gm], np.asarray(o_b)[: gm.sum()], rtol=2e-5, atol=2e-5
            )
        else:
            np.testing.assert_allclose(
                np.asarray(o_s)[nm], np.asarray(o_b)[: nm.sum()], rtol=2e-5, atol=2e-5
            )


def pytest_batchnorm_running_stats_update():
    model, types, dims = _build("SAGE")
    graphs = _graphs(np.random.default_rng(2))
    batch = collate_graphs(graphs, types, dims)
    variables = init_model_variables(model, batch)
    _, mut = model.apply(variables, batch, train=True, mutable=["batch_stats"])
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mut["batch_stats"])
    assert any(
        not np.allclose(np.asarray(a), np.asarray(b)) for a, b in zip(after, before)
    )


def pytest_mlp_per_node_head():
    """mlp_per_node: distinct per-slot MLPs on fixed-size graphs."""
    heads = {
        "graph": HEADS["graph"],
        "node": {"num_headlayers": 2, "dim_headlayers": [4, 4], "type": "mlp_per_node"},
    }
    types, dims = ("node",), (1,)
    n = 4
    model = create_model("SAGE", 1, 8, dims, types, heads, [1.0], 2, num_nodes=n)
    rng = np.random.default_rng(3)
    graphs = []
    for _ in range(3):
        x = rng.normal(size=(n, 1)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        y = x[:, 0].copy()
        y_loc = np.array([[0, n]], dtype=np.int64)
        graphs.append(GraphSample(x=x, pos=np.zeros((n, 3), np.float32), y=y,
                                  y_loc=y_loc, edge_index=ei,
                                  edge_attr=np.ones((n, 1), np.float32)))
    batch = collate_graphs(graphs, types, dims)
    variables = init_model_variables(model, batch)
    (out,) = model.apply(variables, batch, train=False)
    assert out.shape == (batch.num_nodes_pad, 1)
    assert np.all(np.isfinite(np.asarray(out)))


def pytest_initial_bias():
    model, types, dims = _build("SAGE")
    model2 = create_model(
        "SAGE", 1, 8, dims, types, HEADS, [1.0, 1.0, 1.0], 2, initial_bias=7.5
    )
    graphs = _graphs(np.random.default_rng(4))
    batch = collate_graphs(graphs, types, dims)
    v = init_model_variables(model2, batch)
    # Last dense of the graph head carries the UQ bias.
    bias = v["params"]["head_0"]["dense_2"]["bias"]
    assert np.allclose(np.asarray(bias), 7.5)


@pytest.mark.parametrize("model_type", ["SAGE", "GAT"])
def pytest_conv_node_head(model_type):
    """Node heads decoded by a conv chain (reference node_NN_type == 'conv')."""
    heads = {
        "graph": HEADS["graph"],
        "node": {"num_headlayers": 2, "dim_headlayers": [4, 4], "type": "conv"},
    }
    types, dims = ("graph", "node"), (1, 1)
    model = create_model(model_type, 1, 8, dims, types, heads, [1.0, 1.0], 2)
    graphs = _graphs(np.random.default_rng(5))
    for g in graphs:  # trim targets to two heads
        g.y = np.concatenate([[g.x.sum()], g.x[:, 0]])
        g.y_loc = np.array([[0, 1, 1 + g.num_nodes]], dtype=np.int64)
    batch = collate_graphs(graphs, types, dims)
    variables = init_model_variables(model, batch)
    outs = model.apply(variables, batch, train=False)
    assert outs[0].shape == (batch.num_graphs_pad, 1)
    assert outs[1].shape == (batch.num_nodes_pad, 1)
    assert all(np.all(np.isfinite(np.asarray(o))) for o in outs)


def pytest_cgcnn_conv_node_head_rejected():
    heads = {
        "graph": HEADS["graph"],
        "node": {"num_headlayers": 2, "dim_headlayers": [4, 4], "type": "conv"},
    }
    model = create_model("CGCNN", 1, 8, (1,), ("node",), heads, [1.0], 2, edge_dim=0)
    graphs = _graphs(np.random.default_rng(6))
    for g in graphs:
        g.y = g.x[:, 0].copy()
        g.y_loc = np.array([[0, g.num_nodes]], dtype=np.int64)
    batch = collate_graphs(graphs, ("node",), (1,), edge_dim=0)
    with pytest.raises(ValueError, match="conv"):
        init_model_variables(model, batch)


def pytest_nll_loss_raises():
    from hydragnn_tpu.models.loss import multihead_rmse_loss as loss_fn
    with pytest.raises(ValueError, match="not ready"):
        loss_fn([], None, (), (), ilossweights_nll=1)
