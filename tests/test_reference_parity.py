"""The program's forward against the benchmark's plain float32 reference
(``graftbench/reference.py``, imported as it is), on tiny models of the three
families that own a cell, on every aggregation route: the yardstick the chip
holds ``correct`` to, held here on the CPU, so that a change to
``models/convs.py``, ``models/painn.py`` or ``ops/aggregate.py`` meets it
before it meets the chip. Values, never a time."""

import numpy as np
import pytest

from graftbench import reference
from graftbench.drivers.train_epochs import shaken
from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.models import create_model, init_model_variables
from tests.conftest import forward

HEADS = {
    "graph": {
        "num_sharedlayers": 1, "dim_sharedlayers": 8,
        "num_headlayers": 2, "dim_headlayers": [8, 8],
    },
    "node": {"num_headlayers": 2, "dim_headlayers": [8, 8], "type": "mlp"},
}
RADIUS = 2.2
# family -> (extra create_model arguments, edge features a graph carries)
FAMILIES = {
    "PNA": (dict(pna_deg=[0, 2, 5, 9, 6, 3, 1], edge_dim=1), True),
    "GAT": ({}, False),
    "PAINN": (dict(radius=RADIUS, num_radial=6), False),
}
# route -> (HYDRAGNN_SEGMENT_SORTED, whether the batch keeps its CSR pointers)
ROUTES = {"xla": ("0", True), "sorted": ("1", False), "csr": ("1", True)}


def _graphs(family, count=5):
    rng = np.random.default_rng(28)
    graphs = []
    for _ in range(count):
        n = int(rng.integers(5, 10))
        pos = rng.uniform(0.0, 3.0, size=(n, 3)).astype(np.float32)
        d = np.linalg.norm(pos[:, None] - pos[None], axis=-1)
        send, recv = np.nonzero((d < RADIUS) & (d > 0))
        x = rng.uniform(size=(n, 1)).astype(np.float32)
        graphs.append(GraphSample(
            x=x, pos=pos,
            y=np.concatenate([[x.sum()], x[:, 0]]).astype(np.float32),
            y_loc=np.array([[0, 1, 1 + n]], dtype=np.int64),
            edge_index=np.stack([send, recv]).astype(np.int32),
            edge_attr=d[send, recv][:, None].astype(np.float32)
            if FAMILIES[family][1] else None,
        ))
    return graphs


@pytest.mark.parametrize("route", list(ROUTES))
@pytest.mark.parametrize("family", list(FAMILIES))
def pytest_program_forward_matches_plain_reference(family, route, monkeypatch):
    sorted_env, keep_csr = ROUTES[route]
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", sorted_env)
    extra, edge_features = FAMILIES[family]
    graphs = _graphs(family)
    model = create_model(
        family, 1, 8, (1, 1), ("graph", "node"), HEADS, [1.0, 1.0], 2, **extra
    )
    batch = collate_graphs(
        graphs, ("graph", "node"), (1, 1), edge_dim=1 if edge_features else None,
        with_positions=family == "PAINN",
    )
    if not keep_csr:
        batch = batch.replace(row_ptr=None, graph_ptr=None)
    # Noise on every vector leaf: the terms a fresh initialization leaves at
    # 0 or 1 (biases, BatchNorm scale, shift and running statistics).
    variables = shaken(init_model_variables(model, batch), 28)

    # The program's side is ONE program; the plain reference stays as it is.
    got = [np.asarray(o) for o in forward(model, variables, batch)]
    want = reference.forward(model, variables, graphs)
    atol, rtol = reference.tolerance(model.conv_type)
    starts = np.concatenate([[0], np.cumsum([g.num_nodes for g in graphs])])
    for g, want_g in enumerate(want):
        np.testing.assert_allclose(got[0][g], want_g[0], rtol=rtol, atol=atol)
        np.testing.assert_allclose(
            got[1][starts[g]:starts[g + 1]], want_g[1], rtol=rtol, atol=atol
        )
    # The comparison is not of zeros against zeros.
    assert max(float(np.abs(w[0]).max()) for w in want) > 10 * atol
