"""Collator tests: target unpacking (the packed y/y_loc contract,
reference serialized_dataset_loader.py:220-261) and the padding contract."""

import numpy as np
import pytest

from hydragnn_tpu.graphs import GraphSample, collate_graphs, compute_pad_sizes


def _make_sample(n, graph_dim=2, node_dims=(1, 3)):
    """Sample with one graph feature (dim graph_dim) + node heads of node_dims."""
    x = np.arange(n, dtype=np.float32).reshape(n, 1)
    pos = np.random.RandomState(n).rand(n, 3).astype(np.float32)
    heads = [np.arange(graph_dim, dtype=np.float32) + 10 * n]
    for d in node_dims:
        heads.append((np.arange(n * d, dtype=np.float32) + 100 * n).reshape(n * d))
    y = np.concatenate([h.reshape(-1) for h in heads])
    y_loc = np.zeros((1, len(heads) + 1), dtype=np.int64)
    off = 0
    for i, h in enumerate(heads):
        off += h.size
        y_loc[0, i + 1] = off
    ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
    ea = np.ones((n, 1), dtype=np.float32) * n
    return GraphSample(x=x, pos=pos, y=y, y_loc=y_loc, edge_index=ei, edge_attr=ea)


def pytest_collate_shapes_and_masks():
    graphs = [_make_sample(3), _make_sample(5)]
    types = ("graph", "node", "node")
    dims = (2, 1, 3)
    b = collate_graphs(graphs, types, dims)
    assert b.node_features.shape[0] >= 9  # 8 real + ≥1 pad
    assert int(b.node_mask.sum()) == 8
    assert int(b.edge_mask.sum()) == 8
    assert int(b.graph_mask.sum()) == 2
    # Padding edges only touch padding nodes.
    pad_edges = ~np.asarray(b.edge_mask)
    assert not np.asarray(b.node_mask)[np.asarray(b.senders)[pad_edges]].any()
    assert not np.asarray(b.node_mask)[np.asarray(b.receivers)[pad_edges]].any()
    # Padding nodes belong to a padding graph.
    pad_nodes = ~np.asarray(b.node_mask)
    assert not np.asarray(b.graph_mask)[np.asarray(b.node_graph)[pad_nodes]].any()


def pytest_collate_target_unpacking():
    n = 4
    g = _make_sample(n)
    types = ("graph", "node", "node")
    dims = (2, 1, 3)
    b = collate_graphs([g], types, dims)
    # Graph head: first 2 of packed y.
    assert np.allclose(b.targets[0][0], g.y[:2])
    # Node head dim 1: next n entries.
    assert np.allclose(b.targets[1][:n, 0], g.y[2 : 2 + n])
    # Node head dim 3: row-major [n,3].
    assert np.allclose(b.targets[2][:n], g.y[2 + n :].reshape(n, 3))
    # Edge index offsets: second graph's edges shifted by first graph's n.
    b2 = collate_graphs([g, _make_sample(3)], types, dims)
    assert np.asarray(b2.senders)[np.asarray(b2.edge_mask)].max() >= n


def pytest_pad_sizes_fit_worst_batch():
    graphs = [_make_sample(n) for n in (2, 3, 5, 7, 11)]
    n_pad, e_pad, g_pad = compute_pad_sizes(graphs, batch_size=2)
    assert n_pad > 11 + 7
    assert e_pad >= 11 + 7
    assert g_pad == 3


# (ladder_step, worst batch + 1 as (nodes, edges)) -> the loader's static pad.
# The first three are the benchmark's graph cells (PNA's small bucket, PNA's
# large bucket and its one-bucket evaluation loaders, the md17-shaped loader of
# GATv2 and PaiNN): an absent ``Dataset.ladder_step`` rounds up to the extrema
# kernels' tile. A named ladder means what it always did, and at or under four
# tiles the default is still the power of two.
_ROUND_UPS = [
    (None, (8193, 112641), (8704, 113152)),
    (None, (18433, 401409), (18944, 401920)),
    (None, (10753, 215041), (11264, 215552)),
    (None, (2049, 2050), (2560, 2560)),
    (None, (2048, 1025), (2048, 2048)),
    (None, (520, 130), (1024, 256)),
    (None, (3, 2), (8, 8)),
    ("pow2", (8193, 112641), (16384, 131072)),
    ("pow2", (10753, 215041), (16384, 262144)),
    ("mult64", (4097, 16321), (4160, 16384)),
    ("mult64", (520, 130), (576, 256)),
]


@pytest.mark.parametrize(
    "ladder_step,worst,want", _ROUND_UPS,
    ids=[f"{step}-{worst[0]}x{worst[1]}" for step, worst, _ in _ROUND_UPS],
)
def pytest_loader_pad_round_up(ladder_step, worst, want):
    from hydragnn_tpu.graphs.collate import (
        GraphArena, compute_pad_sizes_from_counts, loader_pad_tile, round_up_pow2,
    )
    from hydragnn_tpu.ops.extrema_scan import _NB, _XB

    tile = loader_pad_tile()
    assert tile % _XB == 0 and tile % _NB == 0 and tile == 512
    # One graph a batch: the worst batch + 1 is the graph's own counts + 1.
    n_pad, e_pad, g_pad = compute_pad_sizes_from_counts(
        [worst[0] - 1, 1], [worst[1] - 1, 1], 1, ladder_step=ladder_step
    )
    assert (n_pad, e_pad, g_pad) == (*want, 2)
    for real, pad in zip(worst, (n_pad, e_pad)):
        assert pad >= real
        if ladder_step is None and pad > 4 * tile:
            assert pad % _XB == 0 and pad % _NB == 0 and pad - real < tile
        elif ladder_step != "mult64":
            assert pad & (pad - 1) == 0
    # Where a shape is chosen per batch the power of two bounds the programs:
    # the round-up's own default and an arena given no pads keep it.
    assert round_up_pow2(worst[0]) == 1 << (max(worst[0], 8) - 1).bit_length()
    if worst[0] <= 1024:
        graph = GraphSample(
            x=np.zeros((worst[0] - 1, 1), np.float32), pos=None,
            y=np.zeros(1, np.float32), y_loc=np.array([[0, 1]], np.int64),
            edge_index=np.zeros((2, worst[1] - 1), np.int32),
        )
        batch = GraphArena([graph]).collate([0], ("graph",), (1,))
        assert batch.node_features.shape[0] == round_up_pow2(worst[0])
        assert batch.senders.shape[0] == round_up_pow2(worst[1])


def pytest_vectorized_collate_matches_per_sample_unpack():
    """The vectorized packer must equal a per-sample reference built directly
    from unpack_targets over random ragged graphs (incl. vector node heads and
    an edgeless graph)."""
    import numpy as np

    from hydragnn_tpu.graphs import GraphSample, collate_graphs
    from hydragnn_tpu.graphs.collate import unpack_targets

    rng = np.random.default_rng(7)
    head_types, head_dims = ("graph", "node", "node"), (2, 1, 3)
    graphs = []
    for k in range(9):
        n = int(rng.integers(1, 7))
        e = 0 if k == 4 else int(rng.integers(1, 2 * n + 1))
        x = rng.normal(size=(n, 2)).astype(np.float32)
        ei = rng.integers(0, n, size=(2, e)).astype(np.int32)
        ea = rng.normal(size=(e, 2)).astype(np.float32)
        parts = [rng.normal(size=(2,)), rng.normal(size=(n,)), rng.normal(size=(n * 3,))]
        y = np.concatenate(parts).astype(np.float32)
        y_loc = np.array([[0, 2, 2 + n, 2 + n + n * 3]], dtype=np.int64)
        graphs.append(
            GraphSample(x=x, pos=np.zeros((n, 3), np.float32), y=y, y_loc=y_loc,
                        edge_index=ei, edge_attr=ea)
        )

    batch = collate_graphs(graphs, head_types, head_dims, edge_dim=1)

    node_off = 0
    edge_off = 0
    for gi, s in enumerate(graphs):
        n, e = s.num_nodes, s.num_edges
        np.testing.assert_array_equal(
            batch.node_features[node_off:node_off + n], s.x
        )
        assert (batch.node_graph[node_off:node_off + n] == gi).all()
        if e:
            # GraphArena stable-sorts each graph's edges by receiver (the
            # sorted-segment-path contract); the reference expectation gets
            # the same permutation. Edge ORDER is semantically free.
            order = np.argsort(s.edge_index[1], kind="stable")
            np.testing.assert_array_equal(
                batch.senders[edge_off:edge_off + e],
                s.edge_index[0][order] + node_off,
            )
            np.testing.assert_array_equal(
                batch.receivers[edge_off:edge_off + e],
                s.edge_index[1][order] + node_off,
            )
            np.testing.assert_array_equal(
                batch.edge_features[edge_off:edge_off + e],
                s.edge_attr[order][:, :1],
            )
        per_head = unpack_targets(s, head_types, head_dims)
        np.testing.assert_allclose(batch.targets[0][gi], per_head[0])
        np.testing.assert_allclose(
            batch.targets[1][node_off:node_off + n], per_head[1]
        )
        np.testing.assert_allclose(
            batch.targets[2][node_off:node_off + n], per_head[2]
        )
        node_off += n
        edge_off += e
    # padding rows untouched
    assert not batch.node_mask[node_off:].any()
    assert not batch.edge_mask[edge_off:].any()


def pytest_arena_collate_matches_collate_graphs():
    """GraphArena.collate must produce byte-identical batches to
    collate_graphs for arbitrary sample subsets, paddings, and head specs."""
    import numpy as np

    from hydragnn_tpu.graphs import GraphSample, collate_graphs
    from hydragnn_tpu.graphs.collate import GraphArena

    rng = np.random.default_rng(3)
    head_types, head_dims = ("graph", "node"), (1, 2)
    graphs = []
    for k in range(12):
        n = int(rng.integers(2, 9))
        e = 0 if k == 5 else int(rng.integers(1, 3 * n))
        x = rng.normal(size=(n, 3)).astype(np.float32)
        ei = rng.integers(0, n, size=(2, e)).astype(np.int32)
        ea = rng.normal(size=(e, 1)).astype(np.float32)
        y = np.concatenate([rng.normal(size=(1,)), rng.normal(size=(n * 2,))])
        y_loc = np.array([[0, 1, 1 + n * 2]], dtype=np.int64)
        graphs.append(
            GraphSample(x=x, pos=np.zeros((n, 3), np.float32),
                        y=y.astype(np.float32), y_loc=y_loc,
                        edge_index=ei, edge_attr=ea)
        )
    arena = GraphArena(graphs)
    for idx in ([0, 3, 5, 7], [11, 2], list(range(12))):
        a = arena.collate(idx, head_types, head_dims, edge_dim=1)
        b = collate_graphs([graphs[i] for i in idx], head_types, head_dims,
                           edge_dim=1)
        for fa, fb in zip(
            (a.node_features, a.senders, a.receivers, a.node_graph,
             a.node_mask, a.edge_mask, a.graph_mask, a.edge_features,
             *a.targets),
            (b.node_features, b.senders, b.receivers, b.node_graph,
             b.node_mask, b.edge_mask, b.graph_mask, b.edge_features,
             *b.targets),
        ):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
        assert a.num_graphs_pad == b.num_graphs_pad


def pytest_arena_edge_cases():
    """Mixed edge_attr presence packs the attrs that exist (zeros for absent);
    unlabeled datasets collate fine without head_types and refuse with them;
    head_dims inconsistent with y_loc raise instead of silently truncating."""
    import numpy as np
    import pytest as _pytest

    from hydragnn_tpu.graphs import GraphSample
    from hydragnn_tpu.graphs.collate import GraphArena

    def mk(n, e, attr, labeled=True):
        y = np.arange(1 + n, dtype=np.float32) if labeled else None
        y_loc = np.array([[0, 1, 1 + n]], dtype=np.int64) if labeled else None
        return GraphSample(
            x=np.ones((n, 1), np.float32), pos=np.zeros((n, 3), np.float32),
            y=y, y_loc=y_loc,
            edge_index=np.zeros((2, e), np.int32),
            edge_attr=np.full((e, 1), 5.0, np.float32) if attr else None,
        )

    # Mixed attrs: sample 0 has attrs, sample 1 doesn't.
    arena = GraphArena([mk(2, 2, True), mk(2, 2, False)])
    batch = arena.collate([0, 1], ("graph", "node"), (1, 1), edge_dim=1)
    np.testing.assert_array_equal(
        batch.edge_features[:4, 0], [5.0, 5.0, 0.0, 0.0]
    )

    # Unlabeled: no heads OK, heads requested -> error.
    arena_u = GraphArena([mk(2, 1, True, labeled=False)])
    b = arena_u.collate([0])
    assert b.targets == ()
    with _pytest.raises(ValueError, match="unlabeled"):
        arena_u.collate([0], ("graph",), (1,))

    # Declared dims inconsistent with y_loc spans -> error, not silent reads.
    arena_l = GraphArena([mk(3, 1, True)])
    with _pytest.raises(ValueError, match="spans"):
        arena_l.collate([0], ("graph", "node"), (2, 1))
    with _pytest.raises(ValueError, match="spans"):
        arena_l.collate([0], ("graph", "node"), (1, 2))


# ---------------------------------------------- prepared graphs (serving flush)
def _random_graph(rng, n, e, attr_dim=0, pos=True, ei_dtype=np.int32):
    return GraphSample(
        x=rng.normal(size=(n, 2)).astype(np.float32),
        pos=rng.normal(size=(n, 3)).astype(np.float32) if pos else None,
        edge_index=rng.integers(0, n, size=(2, e)).astype(ei_dtype) if e else None,
        edge_attr=rng.normal(size=(e, attr_dim)).astype(np.float32)
        if attr_dim and e else None,
    )


def _lattices(rng):
    """A slice of the serving cell's pool: radius graphs of BCC supercells,
    both directions, in the neighbour search's order (unsorted)."""
    from scipy.spatial import cKDTree

    from graftbench.datagen import bcc_lattice

    params = dict(graphs=5, cell_x=[3, 6], cell_y=[3, 5], cell_z=[2, 4], number_types=3)
    out = []
    for x, pos, _ in bcc_lattice.generate(params, 42):
        pairs = cKDTree(pos).query_pairs(2.0, output_type="ndarray")
        both = np.concatenate([pairs, pairs[:, ::-1]]).T
        out.append(GraphSample(
            x=x[:, :2], pos=pos, edge_index=np.ascontiguousarray(both, np.int32)
        ))
    return out, {}


def _one_alone(rng):
    return [_random_graph(rng, 7, 19)], {}


def _zero_edge_among_others(rng):
    return [_random_graph(rng, 5, 11), _random_graph(rng, 3, 0), _random_graph(rng, 6, 14)], {}


def _all_edgeless(rng):
    # The token shape: a document a graph, no edge, the rung's 8 padding edges.
    return [_random_graph(rng, n, 0) for n in (9, 4, 12)], dict(
        with_positions=True, num_edges_pad=8
    )


def _attr_on_all(rng):
    return [_random_graph(rng, n, 3 * n, attr_dim=2) for n in (4, 6, 5)], dict(edge_dim=2)


def _attr_on_some(rng):
    return [
        _random_graph(rng, 4, 9, attr_dim=2), _random_graph(rng, 5, 12),
        _random_graph(rng, 3, 0), _random_graph(rng, 6, 10, attr_dim=2),
    ], dict(edge_dim=2)


def _attr_on_none(rng):
    return [_random_graph(rng, n, 2 * n) for n in (4, 6)], dict(edge_dim=3)


def _attr_unread(rng):
    # The model reads no edge features: a request's own are left out.
    return [_random_graph(rng, n, 2 * n, attr_dim=2) for n in (4, 6)], {}


def _with_positions(rng):
    return [_random_graph(rng, n, 2 * n) for n in (4, 6, 3)], dict(with_positions=True)


def _already_sorted(rng):
    graphs = [_random_graph(rng, n, 4 * n, attr_dim=1) for n in (5, 8)]
    for s in graphs:
        order = np.argsort(s.edge_index[1], kind="stable")
        s.edge_index, s.edge_attr = s.edge_index[:, order], s.edge_attr[order]
    return graphs, dict(edge_dim=1)


def _duplicate_edges(rng):
    # Many equal receivers, and whole edges repeated with DIFFERENT attrs:
    # only a stable sort keeps the attr rows where the arena puts them.
    graphs = []
    for n in (3, 4):
        ei = rng.integers(0, n, size=(2, 40)).astype(np.int32)
        ei = np.concatenate([ei, ei[:, :15]], axis=1)
        graphs.append(GraphSample(
            x=rng.normal(size=(n, 2)).astype(np.float32), edge_index=ei,
            edge_attr=np.arange(ei.shape[1], dtype=np.float32).reshape(-1, 1),
        ))
    return graphs, dict(edge_dim=1)


def _over_65535_nodes(rng):
    # Past the 16-bit keys' reach (the int32 fall-back) and exactly at its
    # edge (receiver 65,535 of 65,536 nodes); int64 / non-contiguous inputs
    # on the way.
    big = _random_graph(rng, 70_000, 90_000, ei_dtype=np.int64)
    big.edge_index[1, :4] = (69_999, 65_536, 65_535, 0)
    big.x = np.asfortranarray(big.x.astype(np.float64))
    edge = _random_graph(rng, 65_536, 80_000)
    edge.edge_index[1, :3] = (65_535, 0, 65_535)
    return [_random_graph(rng, 5, 9), big, edge], {}


def _edges_fill_the_rung(rng):
    graphs = [_random_graph(rng, n, 3 * n) for n in (4, 6)]
    return graphs, dict(num_edges_pad=sum(s.num_edges for s in graphs))


PREPARED_CASES = (
    _lattices, _one_alone, _zero_edge_among_others, _all_edgeless, _attr_on_all,
    _attr_on_some, _attr_on_none, _attr_unread, _with_positions, _already_sorted,
    _duplicate_edges, _over_65535_nodes, _edges_fill_the_rung,
)


@pytest.mark.parametrize("case", PREPARED_CASES, ids=lambda f: f.__name__.strip("_"))
def pytest_collate_prepared_equals_the_arena_bit_for_bit(case):
    """The serving flush (``prepare_graph`` a request, ``collate_prepared`` a
    flush) gives the arena's batch in every field, values and dtypes, and
    leaves the samples as they were."""
    import dataclasses

    from hydragnn_tpu.graphs.collate import (
        GraphArena, collate_prepared, prepare_graph,
    )

    samples, options = case(np.random.default_rng(11))
    edge_dim = options.get("edge_dim", 0)
    with_positions = options.get("with_positions", False)
    pads = dict(
        num_nodes_pad=sum(s.num_nodes for s in samples) + 3,
        num_edges_pad=options.get(
            "num_edges_pad", sum(s.num_edges for s in samples) + 5
        ),
        num_graphs_pad=len(samples) + 1,
        edge_dim=edge_dim, with_positions=with_positions,
    )
    def arrays(s):  # astuple would copy them
        return [getattr(s, f.name) for f in dataclasses.fields(s)]

    before = [s.clone() for s in samples]
    held = [arrays(s) for s in samples]
    want = GraphArena(samples).collate(np.arange(len(samples)), **pads)
    prepared = [prepare_graph(s, edge_dim, with_positions) for s in samples]
    got = collate_prepared(prepared, **pads)

    for field in dataclasses.fields(want):
        a, b = getattr(want, field.name), getattr(got, field.name)
        if isinstance(a, np.ndarray):
            assert isinstance(b, np.ndarray) and a.dtype == b.dtype, field.name
            assert np.array_equal(a, b), field.name
        else:
            assert a == b, field.name
    for p, s in zip(prepared, samples):
        r = p.edge_index[1]
        assert (r[1:] >= r[:-1]).all() and p.x.flags.c_contiguous
        if s.num_edges:
            was = np.asarray(s.edge_index)[1]
            assert p.presorted == bool((was[1:] >= was[:-1]).all())
    # The caller's samples: the same objects holding the same values.
    for s, fields, clone in zip(samples, held, before):
        for now, then, kept in zip(arrays(s), fields, arrays(clone)):
            assert now is then
            assert now is None or np.array_equal(now, kept)
