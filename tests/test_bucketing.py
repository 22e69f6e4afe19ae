"""Graph-size bucketing (SURVEY.md §7 hard part #4 — recompilation control):
``GraphDataLoader(num_buckets=K)`` partitions mixed-size datasets into K
quantile buckets with per-bucket pad shapes, cutting padding waste while
keeping the number of XLA compiles bounded. No reference analog (the reference
pads nothing — PyG batches are ragged)."""

import numpy as np
import jax
import pytest

from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.graphs.collate import GraphArena
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
    # Shuffled: a batch of 8 can draw any 8, so each bucket runs at its
    # worst case (an unshuffled flat loader is sized to its own fixed batches
    # and has little left for buckets to take).
    flat = GraphDataLoader(ds, batch_size=8, shuffle=True, num_buckets=1)
    bucketed = GraphDataLoader(ds, batch_size=8, shuffle=True, num_buckets=4)

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
        for seed, large in ((0, (40, 64)), (1, (30, 36)), (2, (40, 64)))
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


def _lattice_like(count=1536, seed=0, sizes=(8, 12, 12, 16, 18, 24, 24, 36)):
    """Graphs of a few sizes in lattice-like proportions, 4 edges a node: big
    enough batches (256) that a bucket's shape is counted in kernel tiles."""
    rng = np.random.default_rng(seed)
    graphs = []
    for n in rng.choice(sizes, size=count):
        n = int(n)
        e = 4 * n - int(rng.integers(0, n))
        graphs.append(
            GraphSample(
                x=rng.normal(size=(n, 1)).astype(np.float32),
                pos=np.zeros((n, 3), np.float32),
                y=np.array([float(n)], np.float32),
                y_loc=np.array([[0, 1]], np.int64),
                edge_index=rng.integers(0, n, size=(2, e)).astype(np.int32),
            )
        )
    return graphs


_SPEC = dict(head_types=("graph",), head_dims=(1,))


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def pytest_shuffled_buckets_are_sized_to_the_batches_they_draw():
    """A shuffled two-bucket loader over a lattice-like mix: each bucket's
    shape is a rung of its worst case, under it in both dimensions, whole
    tiles, and every batch of 50 epochs fits it (the worst-case shape is for
    the batch that does not, and none comes)."""
    from hydragnn_tpu.graphs.collate import PAD_RUNGS, loader_pad_tile

    loader = GraphDataLoader(
        _lattice_like(), batch_size=256, shuffle=True, num_buckets=2, **_SPEC
    )
    tile = loader_pad_tile()
    assert loader.num_buckets == 2
    for fitted, worst in zip(loader._bucket_pads, loader._worst_pads):
        assert fitted[0] < worst[0] and fitted[1] < worst[1] and fitted[2] == worst[2]
        assert fitted[0] % tile == 0 and fitted[1] % tile == 0
        for rows, top in zip(fitted[:2], worst[:2]):
            assert any(
                0 <= rows - top * rung / PAD_RUNGS < tile for rung in range(1, PAD_RUNGS)
            )
    assert loader.pad_sizes == tuple(map(max, *loader._worst_pads))
    for epoch in range(50):
        loader.set_epoch(epoch)
        for _, bi, _, need in loader._batch_plan():
            assert need[0] < loader._bucket_pads[bi][0]
            assert need[1] < loader._bucket_pads[bi][1]
    shapes = {(b.node_features.shape[0], b.senders.shape[0]) for b in loader}
    assert shapes == {p[:2] for p in loader._bucket_pads}
    assert loader.padding_stats()["fallback_batches"] == 0


def _tight(monkeypatch):
    """No room over the mean, rungs of a few rows: about every other drawn
    batch overflows its bucket's shape."""
    from hydragnn_tpu.graphs import collate

    monkeypatch.setattr(collate, "PAD_SIGMAS", 0)
    monkeypatch.setattr(collate, "PAD_RUNGS", 4096)


def pytest_a_batch_that_does_not_fit_takes_the_worst_case_shape(monkeypatch):
    """With the bound cut to the mean, the batches over it come out at the
    bucket's worst-case shape, bit for bit the plain collation of the same
    members at that shape; the others hold the same real rows under the same
    masks in fewer padding rows; ``fallback_batches`` counts the first kind;
    and the two shards of a two-process run choose the same shape at every
    place of the plan, from the larger shard's totals."""
    _tight(monkeypatch)
    graphs = _lattice_like()
    loader = GraphDataLoader(graphs, batch_size=256, shuffle=True, num_buckets=2, **_SPEC)
    plan = loader._batch_plan()
    batches = list(loader)
    stats = loader.padding_stats()
    assert 0 < stats["fallback_batches"] < len(batches)
    fell = 0
    arena = GraphArena(graphs)  # the parent's collation: every batch at the worst case
    for (_, bi, members, _), batch in zip(plan, batches):
        worst = loader._worst_pads[bi]
        at_worst = arena.collate(
            members, num_nodes_pad=worst[0], num_edges_pad=worst[1],
            num_graphs_pad=worst[2], with_positions=True, **_SPEC,
        )
        shape = (batch.node_features.shape[0], batch.senders.shape[0])
        if shape == worst[:2]:
            fell += 1
            assert _leaves_equal(batch, at_worst)
        else:
            assert shape == loader._bucket_pads[bi][:2]
            for name in ("node_features", "senders", "receivers", "node_graph"):
                mask = "node_mask" if name.startswith("node") else "edge_mask"
                real = np.asarray(getattr(batch, mask))
                assert np.array_equal(
                    np.asarray(getattr(batch, name))[real],
                    np.asarray(getattr(at_worst, name))[np.asarray(getattr(at_worst, mask))],
                )
            assert np.array_equal(batch.targets[0], at_worst.targets[0])
    assert fell == stats["fallback_batches"]
    assert stats["pad_nodes"] == sum(b.node_features.shape[0] for b in batches)

    shards = [
        GraphDataLoader(
            graphs, batch_size=128, shuffle=True, num_buckets=2, num_shards=2,
            shard_rank=rank, **_SPEC,
        )
        for rank in (0, 1)
    ]
    assert shards[0]._bucket_pads == shards[1]._bucket_pads
    for epoch in (0, 1):
        keys = []
        for shard in shards:
            shard.set_epoch(epoch)
            keys.append([(b.node_features.shape[0], b.senders.shape[0]) for b in shard])
        assert keys[0] == keys[1] and len(set(keys[0])) > 2
    alone = [  # a shard's own totals would have chosen otherwise somewhere
        [
            int(s._ns[m].sum()) >= s._bucket_pads[bi][0]
            or int(s._es[m].sum()) >= s._bucket_pads[bi][1]
            for _, bi, m, _ in s._batch_plan()
        ]
        for s in shards
    ]
    assert alone[0] != alone[1]


@pytest.mark.parametrize(
    "knobs",
    [dict(shuffle=False), dict(shuffle=True, reshuffle="batch", num_buckets=2)],
    ids=["unshuffled", "frozen"],
)
def pytest_fixed_membership_gets_the_rung_of_its_largest_batch(knobs, monkeypatch):
    """A loader whose batches never change is sized to them and not to a
    bound: the lowest rung of the worst case that holds the plan's largest
    batch of each bucket (with rungs of a few rows: that batch, to the tile),
    whatever the epoch, and nothing ever falls back."""
    from hydragnn_tpu.graphs.collate import fit_pad_sizes, loader_pad_tile

    _tight(monkeypatch)
    loader = GraphDataLoader(_lattice_like(), batch_size=256, **knobs, **_SPEC)
    largest = [[0, 0] for _ in loader._bucket_pads]
    for epoch in range(5):
        loader.set_epoch(epoch)
        for _, bi, members, need in loader._batch_plan():
            totals = (int(loader._ns[members].sum()), int(loader._es[members].sum()))
            assert need == totals  # one shard: the need is the batch's own
            largest[bi] = list(map(max, largest[bi], totals))
    tile = loader_pad_tile()
    for bi, (fitted, worst) in enumerate(zip(loader._bucket_pads, loader._worst_pads)):
        assert fitted == fit_pad_sizes(*largest[bi], worst)
        assert 0 < fitted[0] - largest[bi][0] <= tile + 8
        assert 0 < fitted[1] - largest[bi][1] <= tile + 128
        assert fitted[0] < worst[0] and fitted[1] < worst[1]
    assert loader.pad_sizes == tuple(map(max, *loader._bucket_pads, (0, 0, 0)))
    for epoch in (0, 1):
        loader.set_epoch(epoch)
        assert {(b.node_features.shape[0], b.senders.shape[0]) for b in loader} == {
            p[:2] for p in loader._bucket_pads
        }
    assert loader.padding_stats()["fallback_batches"] == 0


@pytest.mark.parametrize(
    "knobs",
    [dict(shuffle=True), dict(shuffle=False), dict(shuffle=True, reshuffle="batch")],
    ids=["shuffled", "unshuffled", "frozen"],
)
def pytest_equal_graphs_keep_the_worst_case_shape_to_the_row(knobs):
    """Graphs of one size (the md17-shaped cells, the token cells' equal
    sequences): nothing to fit, the shape is the worst case's to the row, so
    the compiled programs are what they were."""
    from hydragnn_tpu.graphs.collate import compute_pad_sizes

    graphs = _lattice_like(count=1100, sizes=(21,))
    for g in graphs:  # equal edge counts too
        g.edge_index = g.edge_index[:, :60]
    loader = GraphDataLoader(graphs, batch_size=256, **knobs, **_SPEC)
    assert loader._bucket_pads == loader._worst_pads == [compute_pad_sizes(graphs, 256)]
    assert loader.pad_sizes == loader._worst_pads[0]


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


@pytest.mark.parametrize("overflowing", [False, True], ids=["fits", "overflows"])
def pytest_bucketed_training_scan_path(overflowing, monkeypatch):
    """Bucketed epochs through the scan path; with the bound cut to the mean
    (``overflowing``) some batches come at their bucket's worst-case shape,
    the driver compiles that shape when it meets it, and the epoch's count of
    them is the gauge ``train/pad_fallback_batches_per_epoch`` (0 otherwise)."""
    from hydragnn_tpu import telemetry

    if overflowing:
        _tight(monkeypatch)
    rng = np.random.default_rng(0)
    if overflowing:
        # Multiples of 64 rows: shapes this small are powers of two otherwise,
        # and the mean and the worst case of 16 graphs round to the same one.
        loader = GraphDataLoader(
            _mixed_dataset(rng, count=120), batch_size=16, shuffle=True,
            num_buckets=2, ladder_step="mult64",
        )
        assert loader._bucket_pads[1] < loader._worst_pads[1]
    else:
        ds = _mixed_dataset(rng, count=40)
        loader = GraphDataLoader(ds, batch_size=8, shuffle=True, num_buckets=3)
    loader.set_head_spec(("graph",), (1,))
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    example = next(iter(loader))
    variables = init_model_variables(model, example)
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    driver = TrainingDriver(model, opt, state)
    losses, fallbacks = [], 0
    for epoch in range(4):
        loader.set_epoch(epoch)
        before = loader.padding_stats()["fallback_batches"]
        loss, _ = driver.train_epoch(loader)
        losses.append(loss)
        gauge = telemetry.gauges_snapshot()["train/pad_fallback_batches_per_epoch"]
        assert gauge == loader.padding_stats()["fallback_batches"] - before
        fallbacks += gauge
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert (fallbacks > 0) == overflowing


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
