"""Rematerialization (jax.checkpoint over conv layers, Architecture.remat):
must be numerically transparent — identical forward outputs and gradients,
just recomputed activations in the backward pass. TPU-native addition (no
reference analog)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.models import create_model, init_model_variables, multihead_rmse_loss

HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 4,
        "num_headlayers": 1,
        "dim_headlayers": [4],
    },
    "node": {"num_headlayers": 1, "dim_headlayers": [4], "type": "mlp"},
}


def _batch(rng):
    graphs = []
    for _ in range(4):
        n = int(rng.integers(4, 8))
        x = rng.normal(size=(n, 1)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        ei = np.concatenate([ei, ei[::-1]], axis=1)
        ea = rng.random((ei.shape[1], 1)).astype(np.float32) + 0.1
        y = np.concatenate([[x.sum()], x[:, 0]]).astype(np.float32)
        y_loc = np.array([[0, 1, 1 + n]], dtype=np.int64)
        graphs.append(
            GraphSample(x=x, pos=np.zeros((n, 3), np.float32), y=y, y_loc=y_loc,
                        edge_index=ei, edge_attr=ea)
        )
    return collate_graphs(graphs, ("graph", "node"), (1, 1), edge_dim=1)


@pytest.mark.parametrize("conv", ["SAGE", "GIN", "MFC", "GAT", "CGCNN", "PNA"])
def pytest_remat_transparent(conv):
    rng = np.random.default_rng(0)
    batch = _batch(rng)
    kwargs = dict(edge_dim=1)
    if conv == "PNA":
        kwargs["pna_deg"] = [0, 1, 2, 4, 2, 1]
    if conv == "MFC":
        kwargs["max_neighbours"] = 8

    base = create_model(conv, 1, 8, (1, 1), ("graph", "node"), HEADS,
                        [1.0, 1.0], 2, **kwargs)
    rem = create_model(conv, 1, 8, (1, 1), ("graph", "node"), HEADS,
                       [1.0, 1.0], 2, remat=True, **kwargs)
    # Whole stacks under jit, each model one program (loss and gradients):
    # op by op every primitive of every shape compiles alone.
    v = init_model_variables(base, batch)

    def loss_fn(model, params):
        outs = model.apply({"params": params, "batch_stats": v.get("batch_stats", {})},
                           batch, train=False)
        loss, _ = multihead_rmse_loss(outs, batch, model.output_type,
                                      model.task_weights)
        return loss

    # remat model must accept the same params pytree
    l0, g0 = jax.jit(jax.value_and_grad(lambda p: loss_fn(base, p)))(v["params"])
    l1, g1 = jax.jit(jax.value_and_grad(lambda p: loss_fn(rem, p)))(v["params"])
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)

    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
