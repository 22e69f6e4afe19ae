"""Batch iterator over GraphSamples → padded GraphBatches.

Replaces torch_geometric DataLoader + torch DistributedSampler (reference
/root/reference/hydragnn/preprocess/load_data.py:53-86). Sharding follows
DistributedSampler semantics: indices are globally shuffled with a per-epoch seed
(the ``sampler.set_epoch`` contract, train_validate_test.py:96-97), padded to a
multiple of the shard count by wrapping around, then dealt round-robin so every
shard sees the same number of batches.

Recompilation control vs padding waste (SURVEY.md §7 hard part #4): with
``num_buckets=1`` the whole dataset shares one pad shape (one XLA compile).
Datasets mixing small and large graphs can set ``num_buckets=K``: samples are
partitioned into K node-count quantile buckets, each with its own pad shape —
K compiles, far less padding FLOP waste. Batches are formed within buckets and
the batch order is shuffled across buckets per epoch.

A bucket's shape is sized to the batches the loader can draw, not to the
bucket's ``batch_size`` largest graphs (``GraphDataLoader._size_buckets``):
where the membership never changes (``shuffle=False``, ``reshuffle="batch"``)
to the largest batch of the plan, where it is redrawn every epoch to a bound
on a drawn batch's total (``graphs/collate.py`` ``drawn_total_bound``), both
by whole rungs of the worst-case shape (``fit_pad_sizes``). The batch that
does not fit is collated at the worst-case shape, which is one more compiled
program the first time it happens and is counted (``padding_stats()``).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import numpy as np

from ..graphs.batch import GraphBatch
from ..graphs.collate import (
    GraphArena,
    compute_pad_sizes_from_counts,
    drawn_total_bound,
    fit_pad_sizes,
)
from ..graphs.packing import PackCaps, SizeHistogram, first_fit_decreasing
from ..graphs.sample import GraphSample


def invalid_sample_reason(s: GraphSample) -> Optional[str]:
    """Why a sample must not reach collation (None = valid). The quarantine
    validator (docs/FAULT_TOLERANCE.md): catches corrupt/unparseable records
    — non-finite features, out-of-range edge indices, inconsistent packed
    targets — BEFORE they poison a whole padded batch (one bad sample
    otherwise NaNs the loss of every batch-mate, or crashes the collator
    mid-epoch).

    The serving admission check (serve/engine.py:InferenceEngine._validate)
    overlaps on the structural edge/x checks but is a different contract —
    request-facing, model-width-aware, no y/y_loc or finiteness (non-finite
    OUTPUTS are guarded there instead); a change to either's shared
    structural checks should be mirrored in the other."""
    x = s.x
    if x is None or np.ndim(x) != 2:
        return "x is not a [num_nodes, F] array"
    if not np.isfinite(np.asarray(x, dtype=np.float64)).all():
        return "non-finite node features"
    if s.pos is not None and not np.isfinite(
        np.asarray(s.pos, dtype=np.float64)
    ).all():
        return "non-finite node positions"
    n = int(np.shape(x)[0])
    if s.edge_index is not None:
        ei = np.asarray(s.edge_index)
        if ei.ndim != 2 or ei.shape[0] != 2:
            return "edge_index is not [2, num_edges]"
        if ei.size and (ei.min() < 0 or ei.max() >= n):
            return "edge_index references nodes outside the graph"
        if s.edge_attr is not None and np.shape(s.edge_attr)[0] != ei.shape[1]:
            return "edge_attr row count does not match num_edges"
    if s.edge_attr is not None and not np.isfinite(
        np.asarray(s.edge_attr, dtype=np.float64)
    ).all():
        return "non-finite edge attributes"
    if (s.y is None) != (s.y_loc is None):
        return "y and y_loc must be present together"
    if s.y is not None:
        y = np.asarray(s.y).reshape(-1)
        if not np.isfinite(y.astype(np.float64)).all():
            return "non-finite targets"
        y_loc = np.asarray(s.y_loc).reshape(-1)
        if y_loc.size < 2 or (np.diff(y_loc) < 0).any() or y_loc[-1] > y.size:
            return "y_loc offsets are not a valid prefix of y"
    return None


def share_eval_pads(val_loader, test_loader) -> None:
    """Give the two evaluation loaders ONE static shape, the larger of theirs
    in each dimension, so that validation and test run one compiled program.
    The power of two did that by itself; the round-up to the kernels' tile
    (``compute_pad_sizes_from_counts``) does not: two samples of one dataset
    give 283,648 and 288,256 edge rows. Called where the three loaders are
    made, when no ladder is named."""
    pads = val_loader._bucket_pads + test_loader._bucket_pads
    if len(pads) == 2:  # an evaluation loader has one bucket, an empty split none
        shared = tuple(map(max, *pads))
        val_loader._bucket_pads, test_loader._bucket_pads = [shared], [shared]


def keep_worst_case_pads(loader) -> None:
    """Hold ``loader`` to the ONE shape every possible batch fits, for a
    consumer that stacks batches by their place in the plan and so cannot take
    the batch that comes out at another shape (``ElasticTrainer``). Cached
    collations go with the shapes, as in ``set_packing``."""
    loader._fit_pads = False
    loader._batch_cache.clear()
    loader._cache_bytes = 0
    loader.generation += 1
    loader._size_buckets()


class GraphDataLoader:
    # False: every bucket at its worst-case shape (``keep_worst_case_pads``).
    _fit_pads = True

    def __init__(
        self,
        dataset: Sequence[GraphSample],
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        num_shards: int = 1,
        shard_rank: int = 0,
        head_types: Optional[Sequence[str]] = None,
        head_dims: Optional[Sequence[int]] = None,
        edge_dim: Optional[int] = None,
        num_buckets: int = 1,
        reshuffle: str = "sample",
        skip_budget: int = 0,
        fault_plan=None,
        packing: bool = False,
        ladder_step: Optional[str] = None,
        with_positions: Optional[bool] = None,
    ):
        """``reshuffle`` picks the per-epoch shuffling granularity:

        - ``"sample"`` (default, reference parity): batch MEMBERSHIP is
          redrawn every epoch (DistributedSampler ``set_epoch`` semantics) —
          every epoch re-collates and re-feeds fresh host batches.
        - ``"batch"``: membership is frozen at epoch 0; epochs reshuffle only
          the ORDER batches are visited. Collated batches are then cached
          after the first epoch (and the TrainingDriver additionally caches
          the stacked epoch chunks on DEVICE), so steady-state epochs do no
          host collation and no host->device transfer — a win when the
          host is collation-bound. A mild SGD semantics change, which is
          why it is opt-in (``Training.reshuffle`` in the JSON config).

        ``skip_budget > 0`` enables the corrupt-sample quarantine
        (docs/FAULT_TOLERANCE.md): samples failing ``invalid_sample_reason``
        are dropped into ``self.quarantined`` (index + reason) up to the
        budget; exceeding it fails loudly WITH the quarantine log. The
        default 0 performs no validation at all — identical to the
        historical loader. ``fault_plan`` (default: HYDRAGNN_FAULTS env)
        injects seeded sample corruption for the drills.

        ``packing=True`` (``Dataset.packing``) bin-packs graphs into arena
        slots by first-fit-decreasing (graphs/packing.py) instead of cutting
        the shuffled stream every ``batch_size`` graphs: a batch then holds
        as many graphs as fit the bucket's node/edge capacity (up to 4x
        ``batch_size``), so streamed epochs run far fewer, far denser padded
        batches. Batch MEMBERSHIP becomes size-driven (ties and batch order
        still reshuffle per epoch) — a mild SGD semantics change like
        ``reshuffle="batch"``, which is why it is opt-in; same-seed
        convergence parity is locked by tests/test_packing.py.
        ``ladder_step`` names the pad round-up (``"pow2"``: the next power of
        two; ``"mult64"``: multiples of 64 above 256); None, as when the
        ``Dataset`` block names none, rounds a bucket's one static shape up to
        the kernels' tile (``compute_pad_sizes_from_counts``;
        docs/INPUT_PIPELINE.md).

        ``with_positions`` puts the node coordinates into every batch
        (``GraphBatch.positions``) for the families that compute their edge
        geometry in the step. Config completion sets it from the model
        family, as it sets ``edge_dim``; the attribute, ``GraphArena.collate``
        and the streaming loader take a plain bool, False unless asked. Only
        this argument has a None, "nobody said", resolved here to whether
        every sample has ``pos``: the benchmark's reference check builds its
        loader from ``head_types``/``head_dims``/``edge_dim`` alone
        (graftbench/drivers/train_epochs.py ``_program_forward``; PERF.md
        section 7 has the one-line edit that lets the default become False).
        """
        if reshuffle not in ("sample", "batch"):
            raise ValueError(
                f"reshuffle must be 'sample' or 'batch', got {reshuffle!r}"
            )
        self.dataset = list(dataset)
        self.skip_budget = int(skip_budget)
        self.quarantined: List[tuple] = []
        self._apply_fault_plan(fault_plan)
        if self.skip_budget > 0:
            self._quarantine_invalid_samples()
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.num_shards = num_shards
        self.shard_rank = shard_rank
        self.head_types = tuple(head_types) if head_types else None
        self.head_dims = tuple(head_dims) if head_dims else None
        self.edge_dim = edge_dim
        self.with_positions = (
            bool(self.dataset) and all(s.pos is not None for s in self.dataset)
            if with_positions is None
            else bool(with_positions)
        )
        self.reshuffle = reshuffle
        self.packing = bool(packing)
        self.ladder_step = ladder_step
        self.epoch = 0
        # Head-spec generation: bumped by set_head_spec so EXTERNAL caches of
        # collated/device batches (TrainingDriver._scan_cache/_eval_cache)
        # can detect staleness — the loader's own _batch_cache is cleared
        # directly, and this counter keeps the two invalidation contracts
        # symmetric.
        self.generation = 0
        self._arena = None
        self._frozen_plan = None  # reshuffle="batch": membership drawn once
        self._plan_memo = None  # (epoch, generation) -> last computed plan
        self._batch_cache: dict = {}  # plan position -> collated GraphBatch
        # Host-RAM cap for the collation cache (padded batches can be several
        # times the raw dataset): once exceeded, later positions are simply
        # re-collated each epoch. Distinct from the driver's device-cache
        # budget (HYDRAGNN_DEVICE_CACHE_MB) — different resource.
        import os as _os

        self._cache_budget = int(
            _os.environ.get("HYDRAGNN_HOST_CACHE_MB", "1024")
        ) * (1 << 20)
        self._cache_bytes = 0
        # Per-sample size arrays (packing + per-batch accounting) and the
        # per-run size record the ladder fitter consumes
        # (``python -m hydragnn_tpu.graphs.packing fit-ladder``).
        self._ns = np.fromiter(
            (s.num_nodes for s in self.dataset), np.int64, len(self.dataset)
        )
        self._es = np.fromiter(
            (s.num_edges for s in self.dataset), np.int64, len(self.dataset)
        )
        self.size_histogram = SizeHistogram()
        for n, e in zip(self._ns.tolist(), self._es.tolist()):
            self.size_histogram.record_graph(n, e)
        self._pad_stats = self._zero_pad_stats()
        self._num_buckets_requested = max(1, int(num_buckets))
        self._build_buckets(self._num_buckets_requested)

    def _apply_fault_plan(self, fault_plan) -> None:
        """Seeded corrupt-sample injection (the quarantine drill). Runs
        BEFORE validation so the loader both injects and catches its own
        drill corruption in one construction."""
        from ..faults.plan import FaultPlan

        plan = fault_plan if fault_plan is not None else FaultPlan.from_env()
        if plan is not None and (plan.corrupt_count or plan.corrupt_frac):
            plan.corrupt_dataset(self.dataset)

    def _quarantine_invalid_samples(self) -> None:
        """Drop invalid samples (bounded by ``skip_budget``) before buckets
        and pad shapes are computed, so the surviving dataset is exactly what
        every later stage sees. Exceeding the budget raises with the log —
        a dataset that corrupt can only be fixed upstream, and silently
        training on its remainder would misreport coverage."""
        from ..faults.counters import FaultCounters

        kept = []
        for i, s in enumerate(self.dataset):
            reason = invalid_sample_reason(s)
            if reason is None:
                kept.append(s)
            else:
                self.quarantined.append((i, reason))
        if len(self.quarantined) > self.skip_budget:
            log = "; ".join(
                f"sample {i}: {r}" for i, r in self.quarantined[:10]
            )
            raise RuntimeError(
                f"quarantine budget exceeded: {len(self.quarantined)} corrupt "
                f"samples > skip_budget={self.skip_budget} — {log}"
                + (" ..." if len(self.quarantined) > 10 else "")
            )
        if self.quarantined:
            FaultCounters.inc("quarantined_samples", len(self.quarantined))
            self.dataset = kept

    def _build_buckets(self, num_buckets: int) -> None:
        """Partition dataset indices into node-count quantile buckets, each
        with its own static pad shape."""
        n = int(self._ns.size)
        if n == 0:
            self._buckets = []
            self._bucket_pads = self._worst_pads = []
            self._pack_caps = []
            return
        sizes = self._ns  # one source of truth for per-sample node counts
        num_buckets = min(num_buckets, n)
        order = np.argsort(sizes, kind="stable")
        splits = np.array_split(order, num_buckets)
        # Merge buckets that collapsed to identical size ranges (uniform data).
        buckets: List[np.ndarray] = []
        for part in splits:
            if len(part) == 0:
                continue
            if buckets and sizes[part].max() == sizes[buckets[-1]].max() and (
                sizes[part].min() == sizes[buckets[-1]].min()
            ):
                buckets[-1] = np.concatenate([buckets[-1], part])
            else:
                buckets.append(part)
        # Keep ascending dataset order WITHIN each bucket: with shuffle=False
        # and num_buckets=1 iteration order is exactly dataset order (the
        # eval-loader guarantee documented in load_data.create_dataloaders).
        self._buckets = [np.sort(b) for b in buckets]
        self._size_buckets()

    def _size_buckets(self) -> None:
        """Each bucket's static shape (``_bucket_pads``) and the worst-case
        shape beside it (``_worst_pads``: what every possible batch fits, and
        what the batch that does not fit ``_bucket_pads`` is collated at).
        From the count arrays alone (not the sample objects): the streaming
        subclass (datasets/stream.py) shares this with nothing but the GSHD
        index in RAM. Called again whenever the plan's premises change
        (``set_packing``, the streaming loader's ``reshard``)."""
        self._worst_pads = [
            compute_pad_sizes_from_counts(
                self._ns[b],
                self._es[b],
                self.batch_size,
                ladder_step=self.ladder_step,
            )
            for b in self._buckets
        ]
        self._bucket_pads = list(self._worst_pads)
        self._pack_caps = []
        if self.packing:
            # Packing: the bucket's worst-case pad shape becomes a CAPACITY
            # the packer fills with however many graphs fit (bounded at 4x
            # batch_size so G_pad stays a sane static dimension); G_pad grows
            # to the graph capacity + the reserved padding graph. Packing
            # fills a capacity, it does not draw: nothing to fit.
            pads = []
            for b, (n_pad, e_pad, _) in zip(self._buckets, self._worst_pads):
                min_n = max(1, int(self._ns[b].min()))
                g_cap = int(
                    min(
                        max(self.batch_size, (n_pad - 1) // min_n),
                        4 * self.batch_size,
                    )
                )
                self._pack_caps.append(
                    PackCaps(nodes=n_pad - 1, edges=e_pad, graphs=g_cap)
                )
                pads.append((n_pad, e_pad, g_cap + 1))
            self._bucket_pads = self._worst_pads = pads
        elif self._fit_pads and self._fixed_membership:
            # The plan is the same every epoch: its largest batch, a bucket.
            needs = [[0, 0] for _ in self._buckets]
            for _, bi, _, need in self._compute_batch_plan():
                needs[bi] = list(map(max, needs[bi], need))
            self._bucket_pads = [
                fit_pad_sizes(*need, worst, self.ladder_step)
                for need, worst in zip(needs, self._worst_pads)
            ]
        elif self._fit_pads:
            self._bucket_pads = [
                fit_pad_sizes(
                    drawn_total_bound(self._ns[b], self.batch_size),
                    drawn_total_bound(self._es[b], self.batch_size),
                    worst,
                    self.ladder_step,
                )
                for b, worst in zip(self._buckets, self._worst_pads)
            ]

    @property
    def _fixed_membership(self) -> bool:
        """Every epoch runs the same batches (in whatever order)."""
        return not self.shuffle or self.reshuffle == "batch"

    # -- reference parity: sampler.set_epoch reshuffles DP shards each epoch.
    def set_epoch(self, epoch: int) -> None:
        self.epoch = int(epoch)

    def set_head_spec(
        self, head_types: Sequence[str], head_dims: Sequence[int]
    ) -> None:
        """Called by config completion once output heads are inferred from data."""
        self.head_types = tuple(head_types)
        self.head_dims = tuple(head_dims)
        self._batch_cache.clear()  # cached collations baked the old spec
        self._cache_bytes = 0
        self.generation += 1  # external (driver) caches key on this

    def set_packing(
        self, enabled: bool, ladder_step: Optional[str] = None
    ) -> None:
        """Toggle graph packing (and optionally the round-up ladder) after
        construction: rebuilds bucket pads/capacities, drops cached
        collations and the frozen plan, and bumps ``generation`` so external
        caches of collated/device batches (TrainingDriver scan/eval caches)
        detect the shape change — the same invalidation contract as
        ``set_head_spec``."""
        self.packing = bool(enabled)
        if ladder_step is not None:
            self.ladder_step = ladder_step
        self._frozen_plan = None
        self._batch_cache.clear()
        self._cache_bytes = 0
        self.generation += 1
        self._build_buckets(self._num_buckets_requested)

    @staticmethod
    def _zero_pad_stats() -> dict:
        return {
            "batches": 0,
            "real_nodes": 0,
            "pad_nodes": 0,
            "real_edges": 0,
            "pad_edges": 0,
            "real_graphs": 0,
            "pad_graphs": 0,
            "fallback_batches": 0,
        }

    def reset_padding_stats(self) -> None:
        self._pad_stats = self._zero_pad_stats()

    def padding_stats(self) -> dict:
        """Padded-row accounting over every batch yielded since the last
        reset, at the shape each batch was really collated at: waste = share
        of compiled rows that carried no real node/edge/graph (the serving
        metrics' ``padding_waste_*`` definition, on the training side), and
        ``fallback_batches``, how many did not fit their bucket's shape and
        took the worst-case one. Surfaced by ``bench.py --packing``."""
        st = dict(self._pad_stats)
        for kind in ("nodes", "edges", "graphs"):
            pad = st[f"pad_{kind}"]
            st[f"padding_waste_{kind}"] = (
                round(1.0 - st[f"real_{kind}"] / pad, 4) if pad else None
            )
        return st

    def write_size_histogram(self, path: str) -> None:
        """Persist this run's observed sizes for the ladder fitter
        (``python -m hydragnn_tpu.graphs.packing fit-ladder --hist <path>``)."""
        self.size_histogram.save(path)

    @property
    def pad_sizes(self):
        """Pad shape every batch fits (elementwise max over buckets — the
        largest-node bucket need not have the most edges): the buckets' own
        shapes where the membership is fixed, the worst-case ones where a
        shuffle can draw a batch past its bucket's."""
        pads = self._bucket_pads if self._fixed_membership else self._worst_pads
        if not pads:
            return (0, 0, 0)
        return tuple(max(p[i] for p in pads) for i in range(3))

    @property
    def num_buckets(self) -> int:
        return len(self._buckets)

    def _deal(self, idx: np.ndarray, rng: Optional[np.random.Generator]):
        """``[per_shard, num_shards]``: column ``r`` is shard ``r``'s stream
        of a bucket's (shuffled) indices. Every loader sees every shard's
        members, which is what lets them agree on a shape."""
        if self.shuffle and rng is not None:
            idx = idx.copy()
            rng.shuffle(idx)
        # Wrap-pad so all shards get equal counts (DistributedSampler does
        # the same duplication), then deal round-robin.
        per_shard = -(-len(idx) // self.num_shards)
        return np.resize(idx, per_shard * self.num_shards).reshape(
            per_shard, self.num_shards
        )

    def _batch_plan(self) -> List[tuple]:
        """[(plan_pos, bucket_id, [sample indices], need)] for this epoch;
        ``need`` is the (nodes, edges) the batch's shape has to hold
        (``_plan_bucket``).

        reshuffle="sample": membership redrawn per epoch from
        rng(seed+epoch); batch order shuffled across buckets.
        reshuffle="batch": membership drawn ONCE from rng(seed) and frozen
        (plan_pos is a stable identity — the collation cache and the
        driver's device cache key on it); only the visit ORDER reshuffles
        per epoch.

        The plan is a pure function of (epoch, generation), so it is
        memoized per epoch: ``__len__`` + ``__iter__`` in the same epoch
        pay the shuffle/packing planning cost once (the FFD packer is
        O(items x bins) Python — cheap at this framework's host-RAM dataset
        sizes, but not free to re-run casually)."""
        key = (self.epoch, self.generation)
        if self._plan_memo is not None and self._plan_memo[0] == key:
            return self._plan_memo[1]
        plan = self._compute_batch_plan()
        self._plan_memo = (key, plan)
        return plan

    def _compute_batch_plan(self) -> List[tuple]:
        if self.reshuffle == "batch" and self.shuffle:
            if self._frozen_plan is None:
                self._frozen_plan = [
                    (pos, *entry)
                    for pos, entry in enumerate(
                        self._plan_buckets(np.random.default_rng(self.seed))
                    )
                ]
            order = np.random.default_rng(self.seed + self.epoch).permutation(
                len(self._frozen_plan)
            )
            return [self._frozen_plan[i] for i in order]
        rng = (
            np.random.default_rng(self.seed + self.epoch)
            if self.shuffle
            else None
        )
        plan = self._plan_buckets(rng)
        # Packed plans come out of FFD largest-bin-first; restore random
        # visit order (multi-bucket plans always reshuffled, as before).
        if rng is not None and (len(self._buckets) > 1 or self.packing):
            rng.shuffle(plan)
        return [(None, *entry) for entry in plan]

    def _plan_buckets(self, rng) -> List[tuple]:
        """[(bucket_id, members, need)], bucket after bucket."""
        return [
            (bi, members, need)
            for bi, bucket in enumerate(self._buckets)
            for members, need in self._plan_bucket(
                bi, self._deal(np.asarray(bucket), rng)
            )
        ]

    def _plan_bucket(self, bi: int, dealt: np.ndarray) -> List[tuple]:
        """Split one bucket's dealt (sharded, shuffled) index streams into
        this shard's batches, ``[(members, need)]``: fixed ``batch_size``
        cuts, or — with packing — first-fit-decreasing bins under the
        bucket's (nodes, edges, graphs) capacity. The shuffled order is the
        packer's tie-break, so equal-size graphs still migrate between
        batches across epochs.

        ``need`` is the (nodes, edges) the batch's shape must hold: the
        LARGEST shard's totals at this place of the plan, so that every
        process gives the same step the same shape (a lockstep mesh stacks
        them). A packed batch fits its capacity whatever it holds: None."""
        mine = dealt[:, self.shard_rank]
        if self.packing:
            bins = first_fit_decreasing(
                self._ns[mine], self._es[mine], self._pack_caps[bi]
            )
            return [(mine[members], None) for members in bins]
        cuts = []
        for start in range(0, len(dealt), self.batch_size):
            block = dealt[start : start + self.batch_size]
            need = (
                int(self._ns[block].sum(axis=0).max()),
                int(self._es[block].sum(axis=0).max()),
            )
            cuts.append((block[:, self.shard_rank], need))
        return cuts

    def _book_batch(self, bi: int, sample_idx: np.ndarray, need) -> tuple:
        """The shape one batch is collated at, and the books on it: the size
        record (feeds the ladder fitter and bench.py --packing) and the
        padded-row accounting (cached yields included — the device executes
        the same padded shape either way). The shape is the bucket's unless
        ``need`` (``_plan_bucket``; None for a packed batch, which fits its
        capacity) does not fit it with its padding row: then the bucket's
        worst-case shape, which the driver compiles the first time it meets
        it (``TrainingDriver`` groups batches by shape)."""
        tot_n = int(self._ns[sample_idx].sum())
        tot_e = int(self._es[sample_idx].sum())
        self.size_histogram.record_batch(tot_n, tot_e, len(sample_idx))
        st = self._pad_stats
        pads = self._bucket_pads[bi]
        if need is not None and (need[0] >= pads[0] or need[1] >= pads[1]):
            pads = self._worst_pads[bi]
            st["fallback_batches"] += 1
        st["batches"] += 1
        st["real_nodes"] += tot_n
        st["pad_nodes"] += pads[0]
        st["real_edges"] += tot_e
        st["pad_edges"] += pads[1]
        st["real_graphs"] += len(sample_idx)
        st["pad_graphs"] += pads[2]
        return pads

    def __len__(self) -> int:
        return len(self._batch_plan())

    def __iter__(self) -> Iterator[GraphBatch]:
        if self._arena is None and self.dataset:
            # Built once per dataset: batches become pure numpy gathers over
            # contiguous arenas (the per-sample Python walk in collate_graphs
            # caps a prefetch thread well below TPU consumption rate).
            self._arena = GraphArena(self.dataset)
        for pos, bi, sample_idx, need in self._batch_plan():
            n_pad, e_pad, g_pad = self._book_batch(bi, sample_idx, need)
            if pos is not None and pos in self._batch_cache:
                yield self._batch_cache[pos]
                continue
            batch = self._arena.collate(
                sample_idx,
                head_types=self.head_types or (),
                head_dims=self.head_dims or (),
                num_nodes_pad=n_pad,
                num_edges_pad=e_pad,
                num_graphs_pad=g_pad,
                edge_dim=self.edge_dim,
                with_positions=self.with_positions,
            )
            if pos is not None:
                # Frozen membership (reshuffle="batch"): the collation is
                # deterministic per position, so cache it — up to the host
                # byte budget. Invalidated when the head spec changes.
                import jax as _jax

                nbytes = sum(
                    getattr(l, "nbytes", 0)
                    for l in _jax.tree_util.tree_leaves(batch)
                )
                if self._cache_bytes + nbytes <= self._cache_budget:
                    self._batch_cache[pos] = batch
                    self._cache_bytes += nbytes
            yield batch
