"""PaiNN (``model_type: "PAINN"``, hydragnn_tpu/models/painn.py) on the CPU at
tiny sizes (F 16, 2 blocks, graphs of 5-12 atoms): the program against the
plain float32 reference of ``graftbench/`` (forward for PNA and GAT too, the
yardstick the chip runs hold them to; forward and gradients for PaiNN),
padding independence with finite gradients (a padding edge has length 0),
equivariance, and the family through ``run_training`` / ``run_prediction``
on the scan path and on a mesh. Values and counts, never a time.

The loaders' two pads trained alike (three families, an epoch each) is a file
of its own, tests/test_painn_pads.py: ``--dist loadfile`` gives a file to ONE
worker, and this one was the longest of the suite."""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from graftbench import reference  # noqa: E402
from graftbench.drivers.train_epochs import shaken  # noqa: E402
from graftbench.families import painn as plain_painn  # noqa: E402
from hydragnn_tpu.graphs import GraphSample, collate_graphs  # noqa: E402
from hydragnn_tpu.models import create_model, init_model_variables  # noqa: E402
from hydragnn_tpu.models.painn import PaiNNBlock  # noqa: E402
from tests.conftest import forward  # noqa: E402

RADIUS, F = 2.5, 16
HEADS = {
    "graph": {"num_sharedlayers": 1, "dim_sharedlayers": 8,
              "num_headlayers": 1, "dim_headlayers": [8]},
    "node": {"num_headlayers": 1, "dim_headlayers": [8], "type": "mlp"},
}
TYPES, DIMS = ("graph", "node"), (1, 1)


def _graphs(seed=0, sizes=(5, 7, 9, 12), box=3.0):
    """Atoms in a box, every pair nearer than the cutoff an edge (both
    directions, unsorted: the collator sorts, the reference takes any order)."""
    rng = np.random.default_rng(seed)
    out = []
    for n in sizes:
        pos = rng.uniform(0.0, box, (n, 3)).astype(np.float32)
        d = np.linalg.norm(pos[:, None] - pos[None], axis=-1)
        send, recv = np.nonzero((d < RADIUS) & (d > 0))
        order = rng.permutation(len(send))
        x = rng.normal(size=(n, 1)).astype(np.float32)
        y = np.concatenate([[x.sum()], np.tanh(x[:, 0])]).astype(np.float32)
        out.append(GraphSample(
            x=x, pos=pos, y=y, y_loc=np.array([[0, 1, 1 + n]], dtype=np.int64),
            edge_index=np.stack([send[order], recv[order]]).astype(np.int32),
            edge_attr=d[send[order], recv[order], None].astype(np.float32),
        ))
    return out


def _model(kind="PAINN", hidden=F, **kw):
    if kind == "PNA":
        kw["pna_deg"] = [0, 1, 2, 4, 4, 3, 2, 1]
    if kind == "PAINN":
        kw.update(radius=RADIUS, num_radial=6)
    return create_model(kind, 1, hidden, DIMS, TYPES, HEADS, [1.0, 1.0], 2, **kw)


def _collate(model, graphs, **pads):
    return collate_graphs(
        graphs, TYPES, DIMS, with_positions=model.needs_positions, **pads
    )


def _shaken_variables(model, graphs, seed=3):
    return shaken(init_model_variables(model, _collate(model, graphs)), seed)


def _per_graph(outputs, graphs):
    """The program's padded per-head outputs as ``reference.forward`` gives
    them: per graph, [dim] of the graph head and [n, dim] of the node head."""
    starts = np.concatenate([[0], np.cumsum([g.num_nodes for g in graphs])])
    return [
        [np.asarray(outputs[0])[i], np.asarray(outputs[1])[starts[i]:starts[i + 1]]]
        for i in range(len(graphs))
    ]


# ------------------------------------------------- (i) forward vs the yardstick
# (family, hidden width): the tiny size, and each benchmark configuration's own
# width, so that the sums run on both sides of ``segment_sorted.WIDE_ROW``
# (128): PaiNN F 128 sums [E, 512] rows; GAT 64 a head x 6 sums [E, 384] rows
# and [E, 6] denominators; PNA 256 sums [E, 256] rows and its input layer's
# one column.
# (family, hidden) -> (widths summed by the scatter-add, by the prefix sums) on
# the chip's arm; the pool's mean is hidden wide (GAT: its shared layer's).
_ROUTES = {
    ("PNA", F): (set(), {1, F}), ("GAT", F): (set(), {6, F, 6 * F}),
    ("PAINN", F): (set(), {F, 4 * F}), ("PAINN", 128): ({128, 512}, set()),
    ("GAT", 64): ({384}, {6, 64}), ("PNA", 256): ({256}, {1}),
}


@pytest.mark.parametrize("arm", ["xla", "chip"])
@pytest.mark.parametrize("kind,hidden", list(_ROUTES))
def pytest_program_forward_matches_the_plain_reference(kind, hidden, arm, monkeypatch):
    """What ``correct`` holds a chip run to (graftbench/drivers/
    train_epochs.py), here on every tier-1 run: the program's forward on
    seeded, shaken weights against ``graftbench.reference``, on the CPU's arm
    (``xla``: the masked XLA segment ops) and on the chip's (``chip``: the
    sorted arm through ``HYDRAGNN_SEGMENT_SORTED=1`` with the batch's
    ``row_ptr``: wide sums one scatter-add told the ids are sorted, narrow
    ones prefix sums). Both sides are float32 on the CPU and differ by
    summation order alone; the limit is PaiNN's own on the chip (1e-4,
    ``graftbench/families/painn.py``). This is the check PR 29's streamed sum
    failed in ``painn_f128.train_b512`` (ledger, PR 29)."""
    from hydragnn_tpu.ops import segment_sorted as srt

    if arm == "chip":
        monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    else:
        monkeypatch.delenv("HYDRAGNN_SEGMENT_SORTED", raising=False)
    scattered, prefixed = [], []
    scatter, prefix = srt._sum_count_scatter, srt._sum_count_prefix
    monkeypatch.setattr(srt, "_sum_count_scatter", lambda d, *a: (
        scattered.append(d.shape[1]), scatter(d, *a))[1])
    monkeypatch.setattr(srt, "_sum_count_prefix", lambda d, *a: (
        prefixed.append(d.shape[1]), prefix(d, *a))[1])
    graphs = _graphs()
    model = _model(kind, hidden=hidden)
    variables = _shaken_variables(model, graphs)
    batch = _collate(model, graphs)
    assert batch.row_ptr is not None
    got = _per_graph(forward(model, variables, batch), graphs)
    want = reference.forward(model, variables, graphs)
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    # The arm that ran is the arm the case names: nothing of the sorted arm on
    # a CPU's default; on the chip's, every edge sum at least a lane tile wide
    # took the scatter-add and every narrower one the prefix sums.
    want_routes = (set(), set()) if arm == "xla" else _ROUTES[(kind, hidden)]
    assert (set(scattered), set(prefixed)) == want_routes
    assert all(w >= srt.WIDE_ROW for w in scattered)
    assert all(w < srt.WIDE_ROW for w in prefixed)


# ------------------------------------------ (ii) loss and gradients vs reference
def _plain_outputs(model, params, g):
    graph = {"x": g.x, "pos": g.pos, "send": g.edge_index[0], "recv": g.edge_index[1]}
    s = plain_painn.encode(model, params, {}, graph)
    shared = reference.mlp(params["graph_shared"], s.mean(axis=0), final_relu=True)
    return reference.mlp(params["head_0"], shared), reference.mlp(params["head_1"]["mlp"], s)


def _plain_loss(model, params, graphs):
    total = 0.0
    for g in graphs:
        out_g, out_n = _plain_outputs(model, params, g)
        total += jnp.sum((out_g - g.y[:1]) ** 2)
        total += jnp.sum((out_n[:, 0] - g.y[1:]) ** 2)
    return total


def _program_loss(model, params, batch, **collections):
    out_g, out_n = model.apply({**collections, "params": params}, batch, train=False)
    tgt_g, tgt_n = batch.targets
    return jnp.sum(jnp.where(batch.graph_mask[:, None], (out_g - tgt_g) ** 2, 0.0)) + jnp.sum(
        jnp.where(batch.node_mask[:, None], (out_n - tgt_n) ** 2, 0.0)
    )


def _assert_trees_close(got, want, rtol, atol_of_scale):
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all(), jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            a, b, rtol=rtol, atol=atol_of_scale * max(np.abs(b).max(), 1e-6),
            err_msg=jax.tree_util.keystr(path),
        )


def pytest_painn_loss_and_gradients_match_the_plain_reference():
    """A padded batch (half its edge rows padding) through the program
    against ``jax.grad`` through the plain reference summed over the same
    graphs. Tolerance: both are float32 on the CPU; the program sums a
    receiver's messages in sorted order and the reference in the edge list's,
    two blocks deep, so 1e-3 relative and 1e-4 of a leaf's largest entry;
    a wrong split, sign or missing term is off by O(1) of it."""
    graphs = _graphs(seed=1)
    model = _model()
    params = _shaken_variables(model, graphs)["params"]
    batch = _collate(model, graphs, num_nodes_pad=64, num_edges_pad=512)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(
            jax.value_and_grad(lambda p: _program_loss(model, p, batch))
        )(params)
        want_loss, want = jax.jit(
            jax.value_and_grad(lambda p: _plain_loss(model, p, graphs))
        )(params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-4)
    _assert_trees_close(grads, want, rtol=1e-3, atol_of_scale=1e-4)
    # Every parameter of the encoder takes part: no dead branch.
    for name, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert np.abs(np.asarray(leaf)).max() > 0, jax.tree_util.keystr(name)


# ---------------------------------------------------- (iii) padding independence
def pytest_painn_outputs_and_gradients_do_not_depend_on_the_padding():
    """The same graphs under two paddings, the second with more padding
    nodes, edges AND all-padding graph slots: outputs on the real rows equal,
    every gradient finite and equal. A padding edge joins the padding node to
    itself, so its length is 0 and an unguarded ``sin(n pi d / r_c) / d`` or
    ``r_ij / d`` is NaN there, and 0 x NaN is a NaN weight gradient."""
    graphs = _graphs(seed=2)
    model = _model()
    params = _shaken_variables(model, graphs)["params"]
    tight = _collate(model, graphs)
    loose = _collate(
        model, graphs, num_nodes_pad=128, num_edges_pad=1024, num_graphs_pad=9
    )
    assert loose.senders.shape[0] > 2 * int(tight.edge_mask.sum())
    results = []
    for batch in (tight, loose):
        outputs = forward(model, {"params": params}, batch)
        loss, grads = jax.jit(
            jax.value_and_grad(lambda p: _program_loss(model, p, batch))
        )(params)
        assert all(np.isfinite(np.asarray(o)).all() for o in outputs)
        results.append((_per_graph(outputs, graphs), loss, grads))
    for a, b in zip(results[0][0], results[1][0]):
        for x, y in zip(a, b):
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(results[0][1], results[1][1], rtol=1e-5)
    _assert_trees_close(results[0][2], results[1][2], rtol=1e-4, atol_of_scale=1e-5)


# ------------------------------------------------------------ (iv) equivariance
def pytest_painn_energy_is_invariant_and_v_rotates():
    """A random rotation and translation of every position leaves each head's
    output unchanged and rotates the vector state: ``v`` is flat
    ``[N, 3F]``, xyz-major, so component k is columns ``k F .. (k+1) F``."""
    graphs = _graphs(seed=4)
    model = _model()
    variables = _shaken_variables(model, graphs)
    rng = np.random.default_rng(5)
    q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
    q = (q * np.sign(np.linalg.det(q))).astype(np.float32)  # a proper rotation
    shift = rng.normal(size=3).astype(np.float32) * 4.0
    moved = [copy.copy(g) for g in graphs]
    for g in moved:
        g.pos = g.pos @ q.T + shift

    def run(gs):
        outputs, state = jax.jit(lambda v, b: model.apply(
            v, b, train=False,
            capture_intermediates=lambda m, _: isinstance(m, PaiNNBlock),
            mutable=["intermediates"],
        ))(variables, _collate(model, gs))
        _, v = state["intermediates"]["conv_1"]["__call__"][0]
        n = sum(g.num_nodes for g in gs)
        return outputs, np.asarray(v)[:n].reshape(n, 3, F)

    (out_a, v_a), (out_b, v_b) = run(graphs), run(moved)
    for a, b in zip(_per_graph(out_a, graphs), _per_graph(out_b, graphs)):
        for x, y in zip(a, b):
            np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)
    assert np.abs(v_a).max() > 1e-3  # there is a vector state to rotate
    np.testing.assert_allclose(
        v_b, np.einsum("ab,nbf->naf", q, v_a), rtol=1e-3,
        atol=1e-4 * np.abs(v_a).max(),
    )


def pytest_painn_under_remat_and_bf16_compute():
    """``Architecture.remat`` recomputes the blocks in the backward pass: the
    same gradients. ``compute_dtype: bfloat16`` casts parameters and node
    features and leaves positions and the geometry in float32: finite, and
    within bf16's reach of the float32 outputs."""
    from hydragnn_tpu.train.trainer import _apply_model

    graphs = _graphs(seed=6)
    model = _model()
    batch = _collate(model, graphs)
    params = _shaken_variables(model, graphs)["params"]
    grads = jax.jit(jax.grad(lambda p: _program_loss(model, p, batch)))(params)
    remat = _model(remat=True)
    again = jax.jit(jax.grad(lambda p: _program_loss(remat, p, batch)))(params)
    _assert_trees_close(again, grads, rtol=1e-5, atol_of_scale=1e-6)
    want = forward(model, {"params": params}, batch)
    half = _model(compute_dtype="bfloat16")
    got = jax.jit(lambda p, b: _apply_model(half, p, {}, b, train=False))(params, batch)
    assert batch.positions.dtype == np.float32
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32 and np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=0.1, atol=0.1)


def pytest_painn_refuses_what_it_cannot_run():
    graphs = _graphs()
    model = _model()
    without = collate_graphs(graphs, TYPES, DIMS)
    assert without.positions is None
    with pytest.raises(ValueError, match="positions"):
        init_model_variables(model, without)
    with pytest.raises(ValueError, match="num_radial"):
        create_model("PAINN", 1, F, DIMS, TYPES, HEADS, [1.0, 1.0], 2, radius=2.0)
    conv_heads = copy.deepcopy(HEADS)
    conv_heads["node"]["type"] = "conv"
    conv_model = create_model(
        "PAINN", 1, F, DIMS, TYPES, conv_heads, [1.0, 1.0], 2, radius=2.0, num_radial=4
    )
    with pytest.raises(ValueError, match='"conv" node decoder is not supported for PAINN'):
        init_model_variables(conv_model, _collate(conv_model, graphs))
    from hydragnn_tpu.analysis.contracts import ConfigContractError, check_config

    with open(os.path.join(REPO, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    arch = config["NeuralNetwork"]["Architecture"]
    arch.update(model_type="PAINN", periodic_boundary_conditions=True)
    with pytest.raises(ConfigContractError) as err:
        check_config(config, deep=False)
    found = " ".join(message for _, message in err.value.errors)
    assert "num_radial" in found and "periodic" in found


@pytest.mark.mpi_skip
def pytest_painn_serves_through_the_engine_and_asks_for_positions():
    """The serving engine collates positions for the family (from the model,
    as it takes ``edge_dim``): predictions equal the direct forward, and a
    request without ``pos`` is refused at admission."""
    from hydragnn_tpu.serve import InferenceEngine

    graphs = _graphs(seed=7)
    model = _model()
    variables = _shaken_variables(model, graphs)
    want = _per_graph(forward(model, variables, _collate(model, graphs)), graphs)
    engine = InferenceEngine(model, variables, max_batch_graphs=8, max_delay_ms=20.0)
    try:
        got = engine.predict([GraphSample(x=g.x, pos=g.pos, edge_index=g.edge_index)
                              for g in graphs])
        for g, w in zip(got, want):
            for a, b in zip(g, w):
                np.testing.assert_allclose(np.asarray(a).reshape(b.shape), b,
                                           rtol=1e-5, atol=1e-6)
        with pytest.raises(ValueError, match="positions"):
            engine.submit(GraphSample(x=graphs[0].x, edge_index=graphs[0].edge_index))
    finally:
        engine.close()


# --------------------------------- (v) run_training, run_prediction: the paths
def _tiny_config(tmp_path, **training):
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from deterministic_graph_data import deterministic_graph_data

    data = os.path.join(tmp_path, "dataset", "unit_test_painn")
    os.makedirs(data, exist_ok=True)
    deterministic_graph_data(data, number_configurations=96)
    with open(os.path.join(REPO, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    config["Dataset"]["name"] = "unit_test_painn"
    config["Dataset"]["path"] = {"total": data}
    arch = config["NeuralNetwork"]["Architecture"]
    arch.update(model_type="PAINN", hidden_dim=F, num_radial=6)
    config["NeuralNetwork"]["Training"].update(
        num_epoch=6, batch_size=16, learning_rate=0.005, **training
    )
    config["Visualization"]["create_plots"] = 0
    return config


@pytest.mark.mpi_skip
@pytest.mark.parametrize("path", ["scan", "data_mesh", "graph_axis"])
def pytest_painn_trains_and_predicts_through_the_normal_entry_points(
    path, tmp_path, monkeypatch
):
    """``run_training`` then ``run_prediction`` with ``model_type: "PAINN"``:
    the scanned epoch of one device, the per-step mesh step on a data mesh of
    two, and edge-sharded graph parallelism (``Training.graph_axis: 2``: the
    two sums take the mesh axis). The loss falls, nothing asks for a
    ``batch_stats`` collection, and the loaders carry positions because
    config completion told them to."""
    import hydragnn_tpu
    from hydragnn_tpu.parallel import make_mesh

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    config = _tiny_config(tmp_path, **({"graph_axis": 2} if path == "graph_axis" else {}))
    mesh = make_mesh(devices=jax.devices()[:2]) if path == "data_mesh" else None
    history = hydragnn_tpu.run_training(copy.deepcopy(config), mesh=mesh)
    losses = [float(v) for v in history["total_loss_train"]]
    assert np.isfinite(losses).all() and losses[-1] < 0.7 * losses[0], losses
    error, rmse_task, true_values, predicted = hydragnn_tpu.run_prediction(
        copy.deepcopy(config), mesh=mesh
    )
    assert np.isfinite(error) and error < 2.0 * losses[-1] + 0.1, (error, losses)
    assert np.asarray(predicted[0]).shape == np.asarray(true_values[0]).shape
