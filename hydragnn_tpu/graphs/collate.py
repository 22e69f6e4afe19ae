"""Host-side collator: list[GraphSample] → padded GraphBatch numpy arrays.

Replaces torch_geometric's DataLoader collation (reference:
/root/reference/hydragnn/preprocess/load_data.py:53-86) with static-shape padding so
XLA compiles once per (N_pad, E_pad, G_pad) bucket. Also replaces the per-batch
``get_head_indices`` index math (/root/reference/hydragnn/train/train_validate_test.py:177-205):
targets are unpacked from the packed y/y_loc layout into dense per-head arrays here,
on the host, once per batch.
"""

from __future__ import annotations

import functools
import math
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .batch import GraphBatch
from .csr import build_graph_ptr, build_row_ptr, csr_debug_enabled, validate_csr
from .sample import GraphSample


def round_up_pow2(n: int, minimum: int = 8, mode: str = "pow2") -> int:
    """Round up to the next compiled-shape boundary (≥ minimum) to bound XLA
    recompiles. ``mode="pow2"`` (default) is the historical next-power-of-two
    ladder; ``mode="mult64"`` switches to multiples of 64 above 256 so a
    520-node batch pads to 576 instead of 1024 (``Dataset.ladder_step`` in
    the JSON config; graphs/packing.py:round_up_step holds the arithmetic)."""
    if mode == "pow2":
        v = max(int(n), minimum)
        return 1 << (v - 1).bit_length()
    from .packing import round_up_step

    return round_up_step(n, minimum=minimum, mode=mode)


def unpack_targets(
    sample: GraphSample, head_types: Sequence[str], head_dims: Sequence[int]
) -> List[np.ndarray]:
    """Split a packed ``y`` (offsets in ``y_loc``) into per-head dense arrays:
    graph head → [dim]; node head → [n, dim] (row-major per node, matching the
    reshape(-1, 1) packing at serialized_dataset_loader.py:246-256)."""
    out = []
    y = np.asarray(sample.y).reshape(-1)
    y_loc = np.asarray(sample.y_loc).reshape(-1)
    n = sample.num_nodes
    for ihead, (htype, hdim) in enumerate(zip(head_types, head_dims)):
        sl = y[int(y_loc[ihead]) : int(y_loc[ihead + 1])]
        if htype == "graph":
            out.append(sl.reshape(hdim))
        elif htype == "node":
            out.append(sl.reshape(n, hdim))
        else:
            raise ValueError(f"Unknown head type {htype}")
    return out


def collate_graphs(
    graphs: Sequence[GraphSample],
    head_types: Sequence[str] = (),
    head_dims: Sequence[int] = (),
    num_nodes_pad: Optional[int] = None,
    num_edges_pad: Optional[int] = None,
    num_graphs_pad: Optional[int] = None,
    edge_dim: Optional[int] = None,
    with_positions: bool = False,
) -> GraphBatch:
    """Pack graphs into one padded GraphBatch (numpy arrays, host-side).

    Always reserves >=1 padding node and >=1 padding graph; padding edges
    connect padding nodes so unmasked message passing cannot touch real rows.
    One-off convenience over the single packing implementation, GraphArena —
    loaders build the arena once and reuse it per batch.
    """
    return GraphArena(graphs).collate(
        np.arange(len(graphs)),
        head_types=head_types,
        head_dims=head_dims,
        num_nodes_pad=num_nodes_pad,
        num_edges_pad=num_edges_pad,
        num_graphs_pad=num_graphs_pad,
        edge_dim=edge_dim,
        with_positions=with_positions,
    )


class GraphArena:
    """Dataset-level contiguous buffers for zero-Python-loop batch packing.

    Per-sample Python packing (property calls, tiny reshapes per graph) costs
    ~2 ms for a 256-graph batch — a single prefetch thread then feeds a TPU
    ~8x slower than the chip trains. The arena concatenates every sample's
    fields ONCE per dataset; a batch is then a handful of numpy gathers
    (~0.4 ms for the same 256 graphs), independent of graph count in Python
    terms. Trade-off: the arena holds a second, contiguous copy of the
    dataset's arrays (float32/int32) for the loader's lifetime — datasets are
    host-RAM sized in this framework (the reference holds them on the
    accelerator, serialized_dataset_loader.py:137-140), so ~2x host arrays is
    the cost of feeding the chip at line rate.

    Edge-feature semantics: presence and width are resolved ONCE at arena
    (dataset) level from the first edge-bearing sample carrying ``edge_attr``
    — not per batch. A batch whose own graphs all lack ``edge_attr`` still
    gets zero-filled ``edge_features`` (not None) when any other sample in
    the dataset has them, keeping the batch pytree structure identical across
    batches (one jit trace per pad shape instead of two)."""

    def __init__(self, graphs: Sequence[GraphSample]):
        g = len(graphs)
        self.ns = np.fromiter((s.num_nodes for s in graphs), np.int64, g)
        self.es = np.fromiter((s.num_edges for s in graphs), np.int64, g)
        self.node_start = np.zeros(g + 1, np.int64)
        np.cumsum(self.ns, out=self.node_start[1:])
        self.edge_start = np.zeros(g + 1, np.int64)
        np.cumsum(self.es, out=self.edge_start[1:])

        self.x_all = np.concatenate(
            [np.asarray(s.x, dtype=np.float32) for s in graphs]
        )
        # Kept for ``pos_all`` alone, which only a family that reads positions
        # asks for: the serving engine builds an arena a flush.
        self._graphs = graphs
        with_edges = [s for s in graphs if s.num_edges]
        if with_edges:
            self.ei_all = np.concatenate(
                [np.asarray(s.edge_index, dtype=np.int32) for s in with_edges],
                axis=1,
            )
            first_attr = next(
                (s.edge_attr for s in with_edges if s.edge_attr is not None), None
            )
            if first_attr is not None:
                # Samples missing edge_attr contribute zero rows (same as the
                # historical per-sample packer: attrs that exist are packed).
                width = np.asarray(first_attr).shape[1]
                self.ea_all = np.concatenate(
                    [
                        np.asarray(s.edge_attr, dtype=np.float32)[:, :width]
                        if s.edge_attr is not None
                        else np.zeros((s.num_edges, width), np.float32)
                        for s in with_edges
                    ]
                )
            else:
                self.ea_all = None
        else:
            self.ei_all = np.zeros((2, 0), np.int32)
            self.ea_all = None

        # Sort each graph's edges by receiver (stable, one-time): message
        # passing is permutation-invariant over edges, and per-graph sorted
        # runs + ascending batch node offsets + top-index padding edges make
        # every collated batch's receivers globally non-decreasing — the
        # contract the scatter-free sorted segment path requires
        # (ops/segment_sorted.py). edge_attr rows ride the same permutation.
        if self.ei_all.shape[1]:
            graph_of_edge = np.repeat(
                np.arange(g, dtype=np.int64), self.es
            )
            order = np.lexsort((self.ei_all[1], graph_of_edge))
            self.ei_all = self.ei_all[:, order]
            if self.ea_all is not None:
                self.ea_all = self.ea_all[order]
        # CSR batch contract (graphs/csr.py): the sort above is what makes
        # every collated batch's receivers globally non-decreasing, so the
        # row pointers collate() emits are valid. Validated ONCE per arena
        # (first collate) — or every batch under HYDRAGNN_DEBUG_LAYOUT=1.
        self._csr_validated = False

        # Unlabeled datasets (inference-only: y/y_loc absent) simply carry no
        # target arenas; requesting head_types at collate then raises.
        if any(s.y is None or s.y_loc is None for s in graphs):
            self.y_all = None
            self.y_start = None
            self.y_loc = None
        else:
            ys = [np.asarray(s.y, dtype=np.float32).reshape(-1) for s in graphs]
            self.y_start = np.zeros(g + 1, np.int64)
            np.cumsum(
                np.fromiter((y.size for y in ys), np.int64, g),
                out=self.y_start[1:],
            )
            self.y_all = np.concatenate(ys) if ys else np.zeros(0, np.float32)
            self.y_loc = np.stack(
                [np.asarray(s.y_loc, dtype=np.int64).reshape(-1) for s in graphs]
            )

    @functools.cached_property
    def pos_all(self) -> Optional[np.ndarray]:
        """[total nodes, 3] node coordinates (``collate(with_positions=...)``);
        None where a sample has none."""
        if any(s.pos is None for s in self._graphs):
            return None
        return np.concatenate(
            [np.asarray(s.pos, dtype=np.float32).reshape(-1, 3) for s in self._graphs]
        )

    @staticmethod
    def _ragged_rows(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
        """Flat arena row indices for per-sample ranges [start, start+len)."""
        total = int(lens.sum())
        intra = np.arange(total, dtype=np.int64)
        intra -= np.repeat(np.cumsum(lens) - lens, lens)
        return np.repeat(starts, lens) + intra

    def collate(
        self,
        idx,
        head_types: Sequence[str] = (),
        head_dims: Sequence[int] = (),
        num_nodes_pad: Optional[int] = None,
        num_edges_pad: Optional[int] = None,
        num_graphs_pad: Optional[int] = None,
        edge_dim: Optional[int] = None,
        with_positions: bool = False,
    ) -> GraphBatch:
        """Pack the samples at ``idx`` — same output as ``collate_graphs`` on
        the corresponding GraphSample list (parity-tested). ``with_positions``
        adds ``positions`` [N_pad, 3] (padding rows zero, so a padding edge
        has length 0: the families that read them guard the division)."""
        idx = np.asarray(idx, dtype=np.int64)
        g = len(idx)
        ns, es = self.ns[idx], self.es[idx]
        tot_nodes = int(ns.sum())
        tot_edges = int(es.sum())

        n_pad = num_nodes_pad if num_nodes_pad is not None else round_up_pow2(tot_nodes + 1)
        e_pad = num_edges_pad if num_edges_pad is not None else round_up_pow2(tot_edges + 1)
        g_pad = num_graphs_pad if num_graphs_pad is not None else g + 1
        if n_pad <= tot_nodes:
            raise ValueError(f"num_nodes_pad={n_pad} must exceed total nodes {tot_nodes}")
        if e_pad < tot_edges:
            raise ValueError(f"num_edges_pad={e_pad} must fit total edges {tot_edges}")
        if g_pad <= g:
            raise ValueError(f"num_graphs_pad={g_pad} must exceed num graphs {g}")

        feat_dim = self.x_all.shape[1]
        node_features = np.zeros((n_pad, feat_dim), dtype=np.float32)
        senders = np.full((e_pad,), n_pad - 1, dtype=np.int32)
        receivers = np.full((e_pad,), n_pad - 1, dtype=np.int32)
        node_graph = np.full((n_pad,), g_pad - 1, dtype=np.int32)
        node_mask = np.zeros((n_pad,), dtype=bool)
        edge_mask = np.zeros((e_pad,), dtype=bool)
        graph_mask = np.zeros((g_pad,), dtype=bool)
        graph_mask[:g] = True

        node_rows = self._ragged_rows(self.node_start[idx], ns)
        node_features[:tot_nodes] = self.x_all[node_rows]
        node_graph[:tot_nodes] = np.repeat(np.arange(g, dtype=np.int32), ns)
        node_mask[:tot_nodes] = True
        positions = None
        if with_positions:
            if self.pos_all is None:
                raise ValueError(
                    "positions requested but the dataset has samples "
                    "without pos"
                )
            positions = np.zeros((n_pad, 3), dtype=np.float32)
            positions[:tot_nodes] = self.pos_all[node_rows]

        if edge_dim is None:
            has_edge_attr = self.ea_all is not None
            edge_dim_eff = self.ea_all.shape[1] if has_edge_attr else 0
        else:
            has_edge_attr = edge_dim > 0
            edge_dim_eff = edge_dim
        edge_features = (
            np.zeros((e_pad, edge_dim_eff), dtype=np.float32)
            if has_edge_attr
            else None
        )
        if tot_edges:
            edge_rows = self._ragged_rows(self.edge_start[idx], es)
            new_node_off = np.zeros(g, np.int64)
            np.cumsum(ns[:-1], out=new_node_off[1:])
            shift = np.repeat(new_node_off, es)
            senders[:tot_edges] = self.ei_all[0, edge_rows] + shift
            receivers[:tot_edges] = self.ei_all[1, edge_rows] + shift
            edge_mask[:tot_edges] = True
            if edge_features is not None and self.ea_all is not None:
                edge_features[:tot_edges] = self.ea_all[edge_rows, :edge_dim_eff]

        targets = [
            np.zeros(
                (g_pad, hdim) if htype == "graph" else (n_pad, hdim),
                dtype=np.float32,
            )
            for htype, hdim in zip(head_types, head_dims)
        ]
        if head_types and self.y_all is None:
            raise ValueError(
                "targets requested but the dataset has unlabeled samples "
                "(y/y_loc is None)"
            )
        for ih, (htype, hdim) in enumerate(zip(head_types, head_dims)):
            starts = self.y_start[idx] + self.y_loc[idx, ih]
            spans = self.y_loc[idx, ih + 1] - self.y_loc[idx, ih]
            if htype == "graph":
                if not (spans == hdim).all():
                    raise ValueError(
                        f"head {ih}: y_loc spans {np.unique(spans)} != "
                        f"declared graph dim {hdim}"
                    )
                targets[ih][:g] = self.y_all[starts[:, None] + np.arange(hdim)]
            elif htype == "node":
                if not (spans == ns * hdim).all():
                    raise ValueError(
                        f"head {ih}: y_loc spans don't match num_nodes * "
                        f"{hdim} (declared node dim)"
                    )
                rows = self._ragged_rows(starts, ns * hdim)
                targets[ih][:tot_nodes] = self.y_all[rows].reshape(tot_nodes, hdim)
            else:
                raise ValueError(f"Unknown head type {htype}")

        # Precomputed CSR boundaries — one O(E) host pass per batch replaces
        # two searchsorted calls per op per conv layer in the compiled step.
        row_ptr = build_row_ptr(receivers, n_pad)
        graph_ptr = build_graph_ptr(node_graph, g_pad)
        if not self._csr_validated or csr_debug_enabled():
            # Structural O(E) checks only (deep=False): the pointers were
            # bincount-built from these very ids two lines up, so for
            # sorted in-range ids they provably equal the searchsorted
            # boundaries — and serving builds one arena PER micro-batch
            # flush, putting this on the collate hot path. The deep
            # cross-check runs in the debug mode and the check_config gate.
            deep = csr_debug_enabled()
            validate_csr(receivers, row_ptr, n_pad, what="receivers", deep=deep)
            validate_csr(
                node_graph, graph_ptr, g_pad, what="node_graph", deep=deep
            )
            self._csr_validated = True

        return GraphBatch(
            node_features=node_features,
            edge_features=edge_features,
            senders=senders,
            receivers=receivers,
            node_graph=node_graph,
            node_mask=node_mask,
            edge_mask=edge_mask,
            graph_mask=graph_mask,
            targets=tuple(targets),
            row_ptr=row_ptr,
            graph_ptr=graph_ptr,
            positions=positions,
            num_graphs_pad=g_pad,
        )


class PreparedGraph(NamedTuple):
    """One graph in the form ``collate_prepared`` concatenates: what the
    arena makes of a sample, made per graph (``prepare_graph``)."""

    x: np.ndarray  # [n, F] float32, contiguous
    edge_index: np.ndarray  # [2, e] int32, stable-sorted by receiver
    edge_attr: Optional[np.ndarray]  # [e, edge_dim] float32, the same order
    pos: Optional[np.ndarray]  # [n, 3] float32 where positions were asked for
    presorted: bool  # the edge list arrived in that order: nothing was sorted

    @property
    def num_nodes(self) -> int:
        return self.x.shape[0]

    @property
    def num_edges(self) -> int:
        return self.edge_index.shape[1]


_NO_EDGES = np.zeros((2, 0), np.int32)
_NO_EDGES.setflags(write=False)


def _stable_order_by_receiver(receivers: np.ndarray, num_nodes: int) -> np.ndarray:
    """The permutation of a stable sort by receiver. A graph under 65,536
    nodes sorts 16-bit keys, which numpy's stable ``argsort`` takes as a radix
    sort: on the serving host a quarter of the int32 stable sort's time on a
    graph's ~21k receivers, and under a value sort of packed
    ``(receiver << 32) | position`` keys both in turn and on 64 callers'
    threads at once (``benchmarks/prepare_sort_routes.py``; PERF.md §6,
    PR 42). A larger graph takes the int32 stable sort."""
    keys = receivers.astype(np.uint16) if num_nodes <= 1 << 16 else receivers
    return np.argsort(keys, kind="stable")


def prepare_graph(
    sample: GraphSample, edge_dim: int = 0, with_positions: bool = False
) -> PreparedGraph:
    """What ``GraphArena`` makes of ONE sample, for a batch that sees each
    graph once (the serving engine: a request is prepared where it is
    submitted, a flush is ``collate_prepared``). float32 features, int32
    edges in the arena's order (a stable sort by receiver; an edge list that
    arrives sorted, or empty, is taken as it is), ``edge_attr`` rows through
    the same permutation where ``edge_dim`` asks for them. Reads the sample,
    never writes it; an array already in its form is shared, not copied."""
    x = np.ascontiguousarray(sample.x, dtype=np.float32)
    pos = None
    if with_positions:
        if sample.pos is None:
            raise ValueError("positions requested but the sample has no pos")
        pos = np.ascontiguousarray(sample.pos, dtype=np.float32).reshape(-1, 3)
    if not sample.num_edges:
        return PreparedGraph(x, _NO_EDGES, None, pos, True)
    ei = np.ascontiguousarray(sample.edge_index, dtype=np.int32)
    ea = None
    if edge_dim and sample.edge_attr is not None:
        ea = np.asarray(sample.edge_attr, dtype=np.float32)[:, :edge_dim]
    receivers = ei[1]
    presorted = bool((receivers[1:] >= receivers[:-1]).all())
    if not presorted:
        order = _stable_order_by_receiver(receivers, x.shape[0])
        # np.take along an axis: a quarter of ``ei[:, order]``'s time.
        ei = np.take(ei, order, axis=1)
        if ea is not None:
            ea = np.take(ea, order, axis=0)
    return PreparedGraph(x, ei, ea, pos, presorted)


def collate_prepared(
    graphs: Sequence[PreparedGraph],
    num_nodes_pad: int,
    num_edges_pad: int,
    num_graphs_pad: int,
    edge_dim: int = 0,
    with_positions: bool = False,
) -> GraphBatch:
    """The batch ``GraphArena(samples).collate(arange(g), ...)`` gives at the
    same pads, bit for bit (tests/test_collate.py), from graphs prepared with
    the same ``edge_dim`` / ``with_positions``: one pass of slice writes with
    the running node offset added, no sort and no gather. Unlabeled: no
    targets. Graphs that lack ``edge_attr`` leave zero rows, as in the arena."""
    g = len(graphs)
    tot_nodes = sum(p.num_nodes for p in graphs)
    tot_edges = sum(p.num_edges for p in graphs)
    n_pad, e_pad, g_pad = num_nodes_pad, num_edges_pad, num_graphs_pad
    if n_pad <= tot_nodes:
        raise ValueError(f"num_nodes_pad={n_pad} must exceed total nodes {tot_nodes}")
    if e_pad < tot_edges:
        raise ValueError(f"num_edges_pad={e_pad} must fit total edges {tot_edges}")
    if g_pad <= g:
        raise ValueError(f"num_graphs_pad={g_pad} must exceed num graphs {g}")

    node_features = np.zeros((n_pad, graphs[0].x.shape[1]), dtype=np.float32)
    positions = np.zeros((n_pad, 3), dtype=np.float32) if with_positions else None
    edge_features = (
        np.zeros((e_pad, edge_dim), dtype=np.float32) if edge_dim else None
    )
    # Padding edges join the top padding node, padding nodes the top padding
    # graph; only the tails need the sentinel, the heads are written below.
    senders = np.empty((e_pad,), dtype=np.int32)
    receivers = np.empty((e_pad,), dtype=np.int32)
    senders[tot_edges:] = n_pad - 1
    receivers[tot_edges:] = n_pad - 1
    node_graph = np.empty((n_pad,), dtype=np.int32)
    node_graph[tot_nodes:] = g_pad - 1
    node_mask = np.zeros((n_pad,), dtype=bool)
    node_mask[:tot_nodes] = True
    edge_mask = np.zeros((e_pad,), dtype=bool)
    edge_mask[:tot_edges] = True
    graph_mask = np.zeros((g_pad,), dtype=bool)
    graph_mask[:g] = True

    n0 = e0 = 0
    for i, p in enumerate(graphs):
        n1, e1 = n0 + p.num_nodes, e0 + p.num_edges
        node_features[n0:n1] = p.x
        node_graph[n0:n1] = i
        if positions is not None:
            positions[n0:n1] = p.pos
        if e1 > e0:
            np.add(p.edge_index[0], n0, out=senders[e0:e1])
            np.add(p.edge_index[1], n0, out=receivers[e0:e1])
            if edge_features is not None and p.edge_attr is not None:
                edge_features[e0:e1] = p.edge_attr
        n0, e0 = n1, e1

    row_ptr = build_row_ptr(receivers, n_pad)
    graph_ptr = build_graph_ptr(node_graph, g_pad)
    if csr_debug_enabled():
        validate_csr(receivers, row_ptr, n_pad, what="receivers")
        validate_csr(node_graph, graph_ptr, g_pad, what="node_graph")
    return GraphBatch(
        node_features=node_features,
        edge_features=edge_features,
        senders=senders,
        receivers=receivers,
        node_graph=node_graph,
        node_mask=node_mask,
        edge_mask=edge_mask,
        graph_mask=graph_mask,
        targets=(),
        row_ptr=row_ptr,
        graph_ptr=graph_ptr,
        positions=positions,
        num_graphs_pad=g_pad,
    )


def loader_pad_tile() -> int:
    """The step a loader's static pad is rounded up to when its ``Dataset``
    block names no ``ladder_step``: the least common multiple of the extrema
    kernels' edge block and node block (``ops/extrema_scan.py`` ``_XB``,
    ``_NB``), so their padding of ``[E, F]`` and ``[N, F]`` to whole blocks
    stays a no-op on a loader's shapes."""
    from ..ops.extrema_scan import _NB, _XB

    return math.lcm(_XB, _NB)


def compute_pad_sizes(
    graphs: Sequence[GraphSample], batch_size: int, ladder_step: Optional[str] = None
) -> Tuple[int, int, int]:
    """Dataset-level static pad sizes so every batch of ``batch_size`` graphs from
    this dataset fits one compiled shape: a worst-case batch is the ``batch_size``
    largest graphs. ``ladder_step`` picks the round-up (see
    ``compute_pad_sizes_from_counts``)."""
    return compute_pad_sizes_from_counts(
        [s.num_nodes for s in graphs],
        [s.num_edges for s in graphs],
        batch_size,
        ladder_step=ladder_step,
    )


def compute_pad_sizes_from_counts(
    ns, es, batch_size: int, ladder_step: Optional[str] = None
) -> Tuple[int, int, int]:
    """``compute_pad_sizes`` from per-sample (num_nodes, num_edges) count
    arrays alone: the WORST-CASE shape, the one every possible batch of
    ``batch_size`` of these graphs fits (the ``batch_size`` largest in each
    dimension). A serving ladder's top, a test's fixture and the packer's
    capacity are this shape; a loader's bucket runs at ``fit_pad_sizes`` of
    it and keeps it for the batch that does not fit.

    This shape is chosen ONCE a bucket, so it is one compiled program whatever
    it is rounded to, and the power of two that bounds the programs where a
    shape is chosen per batch (``round_up_pow2``'s own default) buys nothing
    here: ``ladder_step=None`` (a ``Dataset`` block that names none; the one
    place this default lives) rounds up to the next multiple of
    ``loader_pad_tile()`` and keeps the power of two at or under four tiles,
    as ``"mult64"`` keeps it under 256. ``"pow2"`` and ``"mult64"`` named
    mean what they always did."""
    nodes = sorted((int(n) for n in ns), reverse=True)[:batch_size]
    edges = sorted((int(e) for e in es), reverse=True)[:batch_size]
    n_pad = _round_up_pad(sum(nodes) + 1, ladder_step)
    e_pad = _round_up_pad(max(sum(edges), 1) + 1, ladder_step)
    return n_pad, e_pad, batch_size + 1


def _round_up_pad(rows: int, ladder_step: Optional[str]) -> int:
    from .packing import round_up_step

    if ladder_step is None:
        return round_up_step(rows, mode="mult64", step=loader_pad_tile())
    return round_up_step(rows, mode=ladder_step)


# How many standard deviations over its mean a drawn batch's total is given
# room for (``drawn_total_bound``).
PAD_SIGMAS = 6
# A fitted shape is a whole number of these parts of the worst-case shape
# (``fit_pad_sizes``). Of 8, 10, 12, 14 and 16, twelve is the one that gave
# the PNA cells' generator ONE set of train shapes over 28 seeded datasets
# and the fewest rows besides (PERF.md section 6, PR 49).
PAD_RUNGS = 12


def drawn_total_bound(counts, batch_size: int) -> float:
    """The total (of nodes, or of edges) that a batch of ``batch_size`` graphs
    DRAWN from ``counts`` by a shuffle stays under: mean + ``PAD_SIGMAS``
    standard deviations of a uniformly drawn subset's total, ``B mu`` and
    ``B sigma^2 (N - B) / (N - 1)`` (drawn without replacement). From the
    count array alone, which is all the streaming loader has.

    Six: the total of some hundreds of bounded terms is near enough normal
    that six deviations are passed about once in 1e9 batches (the lattice
    buckets of the PNA cells: skewness 0.03 of a 512-graph total, ~3e-9 by
    the saddle point), and the rungs of ``fit_pad_sizes`` add room on top. It
    is no guarantee and needs to be none: a small batch's or a heavy-tailed
    bucket's bound lies over the worst case, which caps it, and the batch
    that passes it all the same is collated at the worst-case shape
    (``GraphDataLoader._book_batch``) at the price of one more compiled
    program, counted in ``padding_stats()['fallback_batches']``. Equal
    counts (sigma 0) give exactly their sum."""
    counts = np.asarray(counts, np.float64)
    n = counts.size
    b = min(int(batch_size), n)
    spread = b * counts.var() * (n - b) / max(n - 1, 1)
    return b * counts.mean() + PAD_SIGMAS * math.sqrt(spread)


def fit_pad_sizes(
    need_nodes: float,
    need_edges: float,
    worst: Tuple[int, int, int],
    ladder_step: Optional[str] = None,
) -> Tuple[int, int, int]:
    """The static shape of a bucket whose batches hold up to ``need_nodes``
    nodes and ``need_edges`` edges: in each dimension by itself the lowest
    rung that holds the need and the padding row, a rung being a whole number
    of ``PAD_RUNGS`` equal parts of the ``worst``-case shape
    (``compute_pad_sizes_from_counts``) rounded up as that shape was; the top
    rung is that shape.

    Why parts of the worst case and not the need rounded up to the tile: the
    need is a statistic of the dataset at hand, and two samples of one source
    differ in it by about a percent, so a shape that followed it to the tile
    would be a new set of step programs for every new sample (a cold
    compilation cache: tens of seconds of set-up). The worst case is the
    largest graphs' and stays put where they do; a need moves between two
    neighbouring rungs at most, and a bucket within a twelfth of its worst
    case (graphs of one size above all) keeps that shape to the row."""
    fitted = []
    for need, top in ((need_nodes, worst[0]), (need_edges, worst[1])):
        need = max(math.ceil(need), 1) + 1
        rungs = (
            min(top, _round_up_pad(-(-top * rung // PAD_RUNGS), ladder_step))
            for rung in range(max(need * PAD_RUNGS // top, 1), PAD_RUNGS + 1)
        )
        fitted.append(next((rows for rows in rungs if rows >= need), top))
    return fitted[0], fitted[1], worst[2]
