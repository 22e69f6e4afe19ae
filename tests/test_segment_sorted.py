"""Scatter-free sorted-segment path (ops/segment_sorted.py): f64-ground-truth
certification, gradients, wrapper routing, and end-to-end conv equivalence on
a REAL collated batch (whose receivers GraphArena now sorts per graph)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from hydragnn_tpu.graphs.collate import collate_graphs
from hydragnn_tpu.ops import aggregate as agg
from hydragnn_tpu.ops import segment as seg
from hydragnn_tpu.ops.segment_sorted import (
    segment_sum_count_sorted,
    segment_sum_sorted,
    sorted_enabled,
)


def _problem(rng, e=4096, f=32, n=1024, pad_rows=300):
    """Sorted ids with a masked tail targeting the top segment (the collation
    padding contract)."""
    ids = np.sort(rng.integers(0, n - 1, e)).astype(np.int32)
    ids[-pad_rows:] = n - 1
    data = (rng.normal(size=(e, f)) * 2 + 1).astype(np.float32)
    mask = np.ones(e, bool)
    mask[-pad_rows:] = False
    return data, ids, mask


def pytest_sorted_sum_count_matches_f64():
    rng = np.random.default_rng(0)
    data, ids, mask = _problem(rng)
    n = 1024
    dz = np.where(mask[:, None], data, 0.0)
    total, count = jax.jit(
        lambda d, i: segment_sum_count_sorted(d, i, n)
    )(jnp.asarray(dz), jnp.asarray(ids))

    t64 = np.zeros((n, data.shape[1]))
    np.add.at(t64, ids[mask], data[mask].astype(np.float64))
    c64 = np.bincount(ids[mask], minlength=n)
    # Real segments exact counts; sums within the kernel certification tol.
    np.testing.assert_array_equal(np.asarray(count)[: n - 1], c64[: n - 1])
    err = np.abs(np.asarray(total, np.float64)[: n - 1] - t64[: n - 1]).max()
    assert err < 5e-4, err


def pytest_sorted_empty_segments_zero():
    # Gaps in the id sequence must come back as exact zeros / zero counts.
    ids = np.asarray([0, 0, 3, 3, 3, 7], np.int32)
    data = np.ones((6, 2), np.float32)
    total, count = segment_sum_count_sorted(jnp.asarray(data), jnp.asarray(ids), 9)
    np.testing.assert_array_equal(
        np.asarray(count), [2, 0, 0, 3, 0, 0, 0, 1, 0]
    )
    np.testing.assert_array_equal(np.asarray(total)[1], [0.0, 0.0])
    np.testing.assert_array_equal(np.asarray(total)[3], [3.0, 3.0])


def pytest_sorted_gradient_is_masked_gather():
    rng = np.random.default_rng(1)
    data, ids, mask = _problem(rng, e=512, f=8, n=64, pad_rows=50)
    n = 64
    w = rng.normal(size=(n, 8)).astype(np.float32)

    def loss(d):
        out = segment_sum_sorted(d, jnp.asarray(ids), n, mask=jnp.asarray(mask))
        return jnp.sum(out * w)

    g = np.asarray(jax.grad(loss)(jnp.asarray(data)))
    g_ref = np.where(mask[:, None], w[ids], 0.0)
    np.testing.assert_allclose(g, g_ref, rtol=1e-6, atol=1e-6)


def pytest_sorted_routing_and_conv_equivalence(monkeypatch):
    """fused_* wrappers route to the sorted path only under BOTH the env gate
    and the caller's sorted_ids declaration — and a real PNA conv forward on a
    collated batch matches the default XLA path to fp32 tolerance."""
    from hydragnn_tpu.models.convs import PNAConv
    from hydragnn_tpu.graphs.sample import GraphSample

    rng = np.random.default_rng(2)
    graphs = []
    for _ in range(5):
        nn_ = int(rng.integers(4, 9))
        ne = int(rng.integers(6, 14))
        ei = np.stack([
            rng.integers(0, nn_, ne).astype(np.int64),
            rng.integers(0, nn_, ne).astype(np.int64),
        ])
        graphs.append(
            GraphSample(
                x=rng.normal(size=(nn_, 3)).astype(np.float32),
                pos=np.zeros((nn_, 3), np.float32),
                y=np.zeros(1, np.float32),
                y_loc=np.array([0, 1], np.int64),
                edge_index=ei,
                edge_attr=rng.normal(size=(ne, 2)).astype(np.float32),
            )
        )
    batch = collate_graphs(graphs, ["graph"], [1], edge_dim=2)
    recv = np.asarray(batch.receivers)
    # The arena guarantee the sorted path depends on:
    assert np.all(np.diff(recv) >= 0), "collated receivers must be sorted"

    conv = PNAConv(out_dim=8, deg_avg_log=1.0, deg_avg_lin=2.0, edge_dim=2)
    vars_ = conv.init(
        jax.random.PRNGKey(0), batch.node_features, batch.senders, batch.receivers,
        batch.edge_features, batch.edge_mask, batch.node_mask, train=False,
    )

    def run():
        return np.asarray(
            conv.apply(
                vars_, batch.node_features, batch.senders, batch.receivers,
                batch.edge_features, batch.edge_mask, batch.node_mask, train=False,
            )
        )

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "0")
    assert not sorted_enabled()
    base = run()
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    assert sorted_enabled()
    sorted_out = run()
    # Only REAL rows: padding-node outputs legitimately differ (the sorted
    # path's count at the padding segment includes masked edges, which is
    # exactly the contract — padding outputs are never consumed).
    real = np.asarray(batch.node_mask)
    np.testing.assert_allclose(
        sorted_out[real], base[real], rtol=2e-4, atol=2e-4
    )

    # Wrapper-level: the node->graph pooling contract (node_graph is sorted
    # by construction) agrees with the masked XLA op.
    x = np.asarray(batch.node_features)
    m_sorted = agg.fused_segment_mean(
        jnp.asarray(x), batch.node_graph, batch.num_graphs_pad,
        mask=batch.node_mask,
    )
    m_ref = seg.segment_mean(
        jnp.asarray(x), batch.node_graph, batch.num_graphs_pad,
        mask=batch.node_mask,
    )
    np.testing.assert_allclose(
        np.asarray(m_sorted), np.asarray(m_ref), rtol=1e-5, atol=1e-5
    )


def pytest_sorted_training_step_converges(monkeypatch):
    """A short end-to-end training run under HYDRAGNN_SEGMENT_SORTED=1 (the
    production-shaped sanity check: loss decreases, no NaNs)."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    import optax

    from hydragnn_tpu.graphs.sample import GraphSample
    from hydragnn_tpu.models.create import create_model, init_model_variables
    from hydragnn_tpu.train.trainer import create_train_state, make_train_step

    rng = np.random.default_rng(3)
    graphs = []
    for _ in range(16):
        nn_ = int(rng.integers(5, 10))
        ne = int(rng.integers(8, 16))
        ei = np.stack([
            rng.integers(0, nn_, ne).astype(np.int64),
            rng.integers(0, nn_, ne).astype(np.int64),
        ])
        x = rng.normal(size=(nn_, 3)).astype(np.float32)
        graphs.append(
            GraphSample(
                x=x,
                pos=np.zeros((nn_, 3), np.float32),
                y=np.asarray([x.sum()], np.float32),
                y_loc=np.array([0, 1], np.int64),
                edge_index=ei,
                edge_attr=None,
            )
        )
    batch = collate_graphs(graphs, ["graph"], [1])
    model = create_model(
        model_type="SAGE", input_dim=3, hidden_dim=8, output_dim=[1],
        output_type=["graph"],
        output_heads={"graph": {"num_sharedlayers": 1, "dim_sharedlayers": 8,
                                "num_headlayers": 1, "dim_headlayers": [8]}},
        task_weights=[1.0], num_conv_layers=2,
    )
    variables = init_model_variables(model, batch)
    opt = optax.adamw(1e-2)
    state = create_train_state(model, variables, opt)
    step = make_train_step(model, opt)
    key = jax.random.PRNGKey(0)
    losses = []
    for _ in range(80):
        state, metrics = step(state, batch, key)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.3, losses[:3] + losses[-3:]


def pytest_sorted_default_follows_execution_platform(monkeypatch):
    """The sorted path defaults ON exactly for TPU execution and OFF
    elsewhere; HYDRAGNN_SEGMENT_SORTED overrides both ways. The platform comes
    from ops.segment.execution_platform, which the trainer's mesh steps pin
    (platform_override), so a TPU-attached host tracing a CPU mesh keeps the
    CPU default."""
    from hydragnn_tpu.ops import segment as seg
    from hydragnn_tpu.ops import segment_sorted as srt

    monkeypatch.delenv("HYDRAGNN_SEGMENT_SORTED", raising=False)
    with seg.platform_override("tpu"):
        assert srt.sorted_enabled()
    with seg.platform_override("cpu"):
        assert not srt.sorted_enabled()
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "0")
    with seg.platform_override("tpu"):
        assert not srt.sorted_enabled()
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    with seg.platform_override("cpu"):
        assert srt.sorted_enabled()


def pytest_sorted_path_under_graph_shard_map(monkeypatch):
    """Edge-sharded (graph-parallel) aggregation through the sorted path —
    the composition the TPU-default flip makes production for distributed
    runs. A contiguous slice of a globally sorted edge array is still
    non-decreasing, so each shard satisfies the sorted contract; partial
    sums compose via psum. Values (not just finiteness) must match the
    single-device sorted result, and gradients must flow."""
    import jax
    from jax.sharding import PartitionSpec as P

    from hydragnn_tpu.ops import aggregate as agg

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    rng = np.random.default_rng(11)
    e, n, f = 64, 10, 5
    data = jnp.asarray(rng.normal(size=(e, f)).astype(np.float32))
    ids = jnp.asarray(np.sort(rng.integers(0, n, size=e)).astype(np.int32))

    ref = agg.fused_segment_stats(data, ids, n)

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("graph",))

    def local(d_, ids_):
        total, mean, std, count = agg.fused_segment_stats(
            d_, ids_, n, axis_name="graph"
        )
        return total, mean, std, count

    # Under jit, as every program path runs it (op by op the mapped body and
    # its backward were 60 s of this file's 100).
    sharded = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("graph"), P("graph")),
        out_specs=(P(), P(), P(), P()), check_vma=False,
    ))
    out = sharded(data, ids)
    for a, b in zip(ref, out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)

    def loss(d_):
        total, mean, std, _ = sharded(d_, ids)
        return jnp.sum(total * 0.3 + mean * 1.7 - std * 0.9)

    g = jax.jit(jax.grad(loss))(data)
    assert bool(jnp.all(jnp.isfinite(g)))
