"""Model-layer unit tests: every conv family runs forward+grad, and padding must
not change results on real rows (hard part #1 in SURVEY.md §7: padding-correct
statistics in BatchNorm, PNA std/scalers, mean-pool)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.models import create_model, init_model_variables, multihead_rmse_loss
from tests.conftest import forward

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


def _build(model_type, edge_dim=None, hidden=8):
    types = ("graph", "node", "node")
    dims = (1, 1, 1)
    model = create_model(
        model_type, 1, hidden, dims, types, HEADS, [1.0, 1.0, 1.0], 2,
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


def _tiny(family, width=0):
    """(model, batch): PNA with its three heads, or LFM2's four token layers;
    ``width`` more columns than any other test's model has."""
    if family == "PNA":
        model, types, dims = _build("PNA", 1, hidden=8 + width)
        return model, collate_graphs(_graphs(np.random.default_rng(0)), types, dims, edge_dim=1)
    from tests import test_lfm2 as token

    model = token._model(intermediate_size=token.ARCH["intermediate_size"] + width)
    return model, token._collate(token._sequences((5, 9)))


@pytest.mark.parametrize("family", ["PNA", "LFM2"])
def pytest_the_initializer_is_one_program(family):
    """``init_model_variables`` asks the compiler for ONE program, whatever
    the family (op by op PNA asked 149 times; a second request is allowed
    for a constant the tracer folds ahead of the program), and for none the
    next time an equal model is initialised over the same shapes: fresh
    buffers of the same values."""
    from hydragnn_tpu.analysis.sentinel import compile_count

    # A model no other test of this process builds: an equal one initialised
    # earlier would have left its program behind.
    model, batch = _tiny(family, width=4)
    before = compile_count()
    variables = jax.block_until_ready(init_model_variables(model, batch, seed=7))
    assert 1 <= compile_count() - before <= 2
    # An equal model over equal shapes (a reload inside one process): none.
    before = compile_count()
    again = jax.block_until_ready(init_model_variables(_tiny(family, width=4)[0], batch, seed=7))
    assert compile_count() == before
    assert all(
        np.array_equal(a, b) and a.unsafe_buffer_pointer() != b.unsafe_buffer_pointer()
        for a, b in zip(jax.tree_util.tree_leaves(again), jax.tree_util.tree_leaves(variables))
    )
    # Another seed is another argument of the same program: other values, no
    # compile.
    other = jax.block_until_ready(init_model_variables(model, batch, seed=8))
    assert compile_count() == before
    assert not all(
        np.array_equal(a, b)
        for a, b in zip(jax.tree_util.tree_leaves(other), jax.tree_util.tree_leaves(variables))
    )
    # Asked for its shapes alone (a server sizing its template), it compiles
    # and allocates nothing.
    before = compile_count()
    shapes = jax.eval_shape(lambda: init_model_variables(model, batch, seed=7))
    assert compile_count() == before
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), variables) == (
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes)
    )
    assert all(
        isinstance(a, jax.ShapeDtypeStruct) for a in jax.tree_util.tree_leaves(shapes)
    )


@pytest.mark.parametrize("family", ["PNA", "LFM2"])
def pytest_the_compiled_initializer_draws_what_the_eager_one_draws(family):
    """Same tree, shapes and dtypes as ``model.init`` run op by op here, and
    the same values (the same keys into the same initializers; a fused
    program may round a scaled draw differently, so 1e-6 relative)."""
    model, batch = _tiny(family)
    got = init_model_variables(model, batch, seed=3)
    want = model.init(
        {"params": jax.random.PRNGKey(3), "dropout": jax.random.PRNGKey(4)}, batch, train=False,
    )
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for (path, g), w in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)
    ):
        name = jax.tree_util.keystr(path)
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6, atol=0, err_msg=name)


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
    out_s = forward(model, variables, small)
    out_b = forward(model, variables, big)
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
    _, mut = jax.jit(
        lambda v, b: model.apply(v, b, train=True, mutable=["batch_stats"])
    )(variables, batch)
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
    (out,) = forward(model, variables, batch)
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
    outs = forward(model, variables, batch)
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
