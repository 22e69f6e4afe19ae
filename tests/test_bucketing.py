"""Graph-size bucketing (SURVEY.md §7 hard part #4 — recompilation control):
``GraphDataLoader(num_buckets=K)`` partitions mixed-size datasets into K
quantile buckets with per-bucket pad shapes, cutting padding waste while
keeping the number of XLA compiles bounded. No reference analog (the reference
pads nothing — PyG batches are ragged)."""

import numpy as np
import jax
import pytest

from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.models import create_model, init_model_variables
from hydragnn_tpu.preprocess.dataloader import GraphDataLoader
from hydragnn_tpu.train.train_validate_test import TrainingDriver
from hydragnn_tpu.train.trainer import create_train_state
from hydragnn_tpu.utils.optimizer import select_optimizer

HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 4,
        "num_headlayers": 1,
        "dim_headlayers": [4],
    },
}


def _mixed_dataset(rng, count=60, small=(3, 8), large=(40, 64)):
    graphs = []
    for i in range(count):
        lo, hi = small if i % 2 == 0 else large
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


def pytest_buckets_reduce_padding_waste():
    rng = np.random.default_rng(0)
    ds = _mixed_dataset(rng)
    flat = GraphDataLoader(ds, batch_size=8, shuffle=False, num_buckets=1)
    bucketed = GraphDataLoader(ds, batch_size=8, shuffle=False, num_buckets=4)

    def padded_rows(loader):
        return sum(b.node_features.shape[0] for b in loader)

    assert bucketed.num_buckets > 1
    assert padded_rows(bucketed) < 0.7 * padded_rows(flat), (
        padded_rows(bucketed), padded_rows(flat),
    )


def pytest_buckets_cover_every_sample_once():
    rng = np.random.default_rng(0)
    ds = _mixed_dataset(rng, count=37)
    loader = GraphDataLoader(ds, batch_size=5, shuffle=True, num_buckets=3)
    loader.set_head_spec(("graph",), (1,))
    for epoch in (0, 1):
        loader.set_epoch(epoch)
        total = sum(int(b.graph_mask.sum()) for b in loader)
        assert total == 37
        assert len(loader) == sum(1 for _ in loader)


def pytest_bucket_shapes_bounded():
    rng = np.random.default_rng(0)
    ds = _mixed_dataset(rng)
    loader = GraphDataLoader(ds, batch_size=8, shuffle=True, num_buckets=4)
    shapes = {b.node_features.shape for b in loader}
    assert len(shapes) <= 4


def pytest_unshuffled_single_bucket_keeps_dataset_order():
    """Eval-loader guarantee: shuffle=False + num_buckets=1 iterates in exact
    dataset order regardless of graph sizes (the Visualizer aligns dataset-
    order node features with eval-order predictions)."""
    rng = np.random.default_rng(3)
    ds = _mixed_dataset(rng, count=11)  # alternating small/large sizes
    loader = GraphDataLoader(ds, batch_size=3, shuffle=False, num_buckets=1)
    loader.set_head_spec(("graph",), (1,))
    seen = []
    for b in loader:
        seen.extend(np.asarray(b.targets[0])[np.asarray(b.graph_mask)].ravel())
    expected = [float(s.y[0]) for s in ds]
    np.testing.assert_allclose(seen, expected, rtol=1e-6)


def pytest_pad_sizes_covers_all_buckets():
    rng = np.random.default_rng(0)
    ds = _mixed_dataset(rng)
    loader = GraphDataLoader(ds, batch_size=8, num_buckets=4)
    n_pad, e_pad, g_pad = loader.pad_sizes
    for b in loader:
        assert b.node_features.shape[0] <= n_pad
        assert b.senders.shape[0] <= e_pad
        assert b.num_graphs_pad <= g_pad


@pytest.mark.parametrize("ladder_step", [None, "pow2"])
def pytest_evaluation_loaders_share_one_shape(ladder_step):
    """Validation and test run the same evaluation program, so with no ladder
    named (pads rounded up to the kernels' tile, which two samples of one
    dataset rarely share) ``create_dataloaders`` gives both the larger pad in
    each dimension: one shape, one compile. A named ladder is left as it was,
    and so are the train loader's buckets."""
    from hydragnn_tpu.preprocess.load_data import create_dataloaders

    train, val, test = (
        _mixed_dataset(np.random.default_rng(seed), count=160, small=(30, 50), large=large)
        for seed, large in ((0, (40, 64)), (1, (40, 52)), (2, (40, 64)))
    )
    made = create_dataloaders(
        train, val, test, batch_size=64, num_buckets=2, ladder_step=ladder_step
    )[:3]
    alone = [
        GraphDataLoader(
            ds, batch_size=64, shuffle=i == 0, num_buckets=2 if i == 0 else 1,
            ladder_step=ladder_step,
        )
        for i, ds in enumerate((train, val, test))
    ]
    assert made[0]._bucket_pads == alone[0]._bucket_pads and made[0].num_buckets == 2
    if ladder_step is None:
        assert alone[1].pad_sizes != alone[2].pad_sizes  # the case at hand
        shared = tuple(max(d) for d in zip(alone[1].pad_sizes, alone[2].pad_sizes))
        assert made[1].pad_sizes == made[2].pad_sizes == shared
        shapes = {
            (b.node_features.shape[0], b.senders.shape[0]) for ld in made[1:] for b in ld
        }
        assert shapes == {shared[:2]}
    else:
        assert [ld.pad_sizes for ld in made[1:]] == [ld.pad_sizes for ld in alone[1:]]


def pytest_uniform_dataset_collapses_buckets():
    rng = np.random.default_rng(0)
    graphs = []
    for _ in range(20):
        n = 5
        x = rng.normal(size=(n, 1)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        graphs.append(
            GraphSample(x=x, pos=np.zeros((n, 3), np.float32),
                        y=np.array([x.sum()], np.float32),
                        y_loc=np.array([[0, 1]], np.int64), edge_index=ei)
        )
    loader = GraphDataLoader(graphs, batch_size=4, num_buckets=4)
    assert loader.num_buckets == 1  # identical sizes merge


def pytest_bucketed_training_scan_path():
    rng = np.random.default_rng(0)
    ds = _mixed_dataset(rng, count=40)
    loader = GraphDataLoader(ds, batch_size=8, shuffle=True, num_buckets=3)
    loader.set_head_spec(("graph",), (1,))
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    example = next(iter(loader))
    variables = init_model_variables(model, example)
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    driver = TrainingDriver(model, opt, state)
    losses = []
    for epoch in range(4):
        loader.set_epoch(epoch)
        loss, _ = driver.train_epoch(loader)
        losses.append(loss)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def pytest_bucketed_training_dp_path():
    from hydragnn_tpu.parallel import make_mesh

    rng = np.random.default_rng(0)
    ds = _mixed_dataset(rng, count=40)
    loader = GraphDataLoader(ds, batch_size=4, shuffle=True, num_buckets=2)
    loader.set_head_spec(("graph",), (1,))
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    example = next(iter(loader))
    variables = init_model_variables(model, example)
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    mesh = make_mesh(data_axis=4, graph_axis=1)
    driver = TrainingDriver(model, opt, state, mesh=mesh)
    loss, _ = driver.train_epoch(loader)
    assert np.isfinite(loss)
    # eval path groups by shape too
    eloss, _ = driver.evaluate(loader)
    assert np.isfinite(eloss)
