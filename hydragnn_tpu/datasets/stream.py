"""graftstream — the out-of-core streaming loader over GSHD shards
(docs/DATA_PLANE.md).

Three pieces:

* :func:`plan_shard_ring` — a pure function turning one epoch's batch plan
  into (decode order, eviction schedule) under a resident-shard capacity.
  Eviction is Belady (farthest next use), so an unshuffled epoch streams one
  shard at a time while a globally-shuffled epoch trades bounded re-decodes
  for bounded RAM — correctness never depends on the capacity.
* :class:`ShardRing` — the bounded decode-ahead ring: a named daemon thread
  ("hydragnn-shard-prefetch", registered in
  ``analysis.rules.THREAD_CALLABLE_BINDINGS``) walks the decode order and
  feeds verified shards through a bounded queue. A corrupt shard is
  delivered as a (sid, None, reason) item — the consumer quarantines it; the
  thread never dies on data corruption.
* :class:`StreamingGraphLoader` — a ``GraphDataLoader`` whose corpus lives
  on disk. The epoch plan is the INHERITED one, computed from the GSHD index
  (per-sample node/edge counts) alone, and every knob — ``num_shards``/
  ``shard_rank`` dealing, buckets, packing, reshuffle — behaves identically:
  streamed training is bit-exact vs the in-memory loader at matched
  seed/shapes (tests/test_stream.py pins collation parity and the elastic
  sample-conservation contract). Under the training ``DeviceFeed`` this
  iterator runs on the feed-host thread, so shard I/O + decode (ring
  thread) overlaps collation (feed-host) overlaps H2D (feed-transfer)
  overlaps device compute.
"""

from __future__ import annotations

import bisect
import os
import queue
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis import tsan
from ..graphs.collate import GraphArena
from ..graphs.packing import SizeHistogram
from ..graphs.sample import GraphSample
from ..preprocess.dataloader import GraphDataLoader
from . import shards as gshd


def plan_shard_ring(
    needs: Sequence[Sequence[int]], capacity: int
) -> Tuple[List[int], List[List[int]]]:
    """Fetch/evict schedule for one epoch: ``needs[k]`` is the ordered list
    of distinct shard ids batch ``k`` touches. Returns ``(fetch_seq,
    evict_after)`` — the exact order the ring thread decodes shards, and the
    shards the consumer drops after each batch. Pure function (the consumer
    and the ring replay the same schedule without sharing mutable state).

    A shard evicted under capacity pressure and needed again later simply
    re-enters ``fetch_seq`` — bounded memory costs a re-decode, never
    correctness. Eviction picks the resident shard with the farthest next
    use (Belady-optimal for a known access sequence); shards never needed
    again are always dropped first."""
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    uses: Dict[int, List[int]] = {}
    for pos, sids in enumerate(needs):
        for sid in sids:
            uses.setdefault(sid, []).append(pos)
    fetch_seq: List[int] = []
    evict_after: List[List[int]] = []
    resident: set = set()
    for pos, sids in enumerate(needs):
        for sid in sids:
            if sid not in resident:
                fetch_seq.append(sid)
                resident.add(sid)
        evictions = [
            sid
            for sid in sorted(resident)
            if bisect.bisect_right(uses[sid], pos) >= len(uses[sid])
        ]
        resident.difference_update(evictions)
        while len(resident) > capacity:
            far = max(
                resident,
                key=lambda sid, pos=pos: (
                    uses[sid][bisect.bisect_right(uses[sid], pos)],
                    sid,
                ),
            )
            resident.discard(far)
            evictions.append(far)
        evict_after.append(sorted(evictions))
    return fetch_seq, evict_after


class ShardRing:
    """Bounded decode-ahead ring of shards on a named daemon thread.

    ``decode(sid)`` runs on the "hydragnn-shard-prefetch" thread
    (``rules.THREAD_CALLABLE_BINDINGS``) and must return ``(payload,
    nbytes)``; a :class:`..checkpoint.format.CheckpointCorruptError` from it
    becomes a ``(sid, None, reason)`` item so the consumer can quarantine
    the shard without losing the run. Any OTHER exception re-raises at the
    consumer, exactly like the training ``_Prefetcher``. The queue depth
    bounds decode-ahead; abandoning consumption (``close``) cancels the
    thread so neither it nor decoded shards leak."""

    _SENTINEL = object()

    def __init__(
        self, fetch_seq: Sequence[int], decode: Callable, depth: int = 2
    ):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._cancel = threading.Event()
        self._err: Optional[BaseException] = None
        self._lock = tsan.instrument_lock(threading.Lock(), "ShardRing._lock")
        with self._lock:
            self.shards_decoded = 0  # guarded-by: self._lock
            self.shards_failed = 0  # guarded-by: self._lock
            self.bytes_decoded = 0  # guarded-by: self._lock

        def _run():
            try:
                for sid in fetch_seq:
                    if self._cancel.is_set():
                        return
                    item = self._decode_one(sid, decode)
                    tsan.yield_point("stream.ring.pre_put")
                    while not self._cancel.is_set():
                        try:
                            self._q.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if self._cancel.is_set():
                        return
            except BaseException as e:  # re-raised at the consumer
                self._err = e
            finally:
                # Sentinel must not be dropped (see _Prefetcher): block with
                # cancel checks so a full queue cannot strand the consumer.
                while not self._cancel.is_set():
                    try:
                        self._q.put(self._SENTINEL, timeout=0.1)
                        break
                    except queue.Full:
                        continue

        self._thread = threading.Thread(
            target=_run, name="hydragnn-shard-prefetch", daemon=True
        )
        self._thread.start()

    def _decode_one(self, sid: int, decode: Callable):
        from ..checkpoint.format import CheckpointCorruptError

        try:
            payload, nbytes = decode(sid)
        except CheckpointCorruptError as e:
            with self._lock:
                self.shards_failed += 1
                tsan.shared_access("ShardRing.stats")
            return (sid, None, e.reason)
        with self._lock:
            self.shards_decoded += 1
            self.bytes_decoded += int(nbytes)
            tsan.shared_access("ShardRing.stats")
        return (sid, payload, None)

    def get(self):
        """Next ``(sid, payload, reason)`` in fetch order; ``None`` when the
        fetch sequence is exhausted. Re-raises a ring-thread failure."""
        item = self._q.get()
        if item is self._SENTINEL:
            if self._err is not None:
                raise self._err
            return None
        return item

    def stats(self) -> dict:
        with self._lock:
            tsan.shared_access("ShardRing.stats")
            return {
                "shards_decoded": self.shards_decoded,
                "shards_failed": self.shards_failed,
                "bytes_decoded": self.bytes_decoded,
            }

    def close(self) -> None:
        self._cancel.set()
        # Drain so a producer blocked on put() wakes and exits.
        try:
            while True:
                self._q.get_nowait()
        except Exception:
            pass

    def join(self, timeout: float = 5.0) -> bool:
        self._thread.join(timeout)
        return not self._thread.is_alive()


class _DecodedShard:
    """One resident decoded shard: its samples, its base global index, and a
    lazily-built arena (constructed by the consumer on first single-shard
    batch — the fast collation path)."""

    __slots__ = ("samples", "base", "_arena")

    def __init__(self, samples: List[GraphSample], base: int):
        self.samples = samples
        self.base = base
        self._arena: Optional[GraphArena] = None

    @property
    def arena(self) -> GraphArena:
        if self._arena is None:
            self._arena = GraphArena(self.samples)
        return self._arena


class _CorpusView:
    """Sequence-style view over the on-disk corpus for the config-completion
    and visualization paths (``loader.dataset[0]``, ``for s in
    loader.dataset``). Sequential iteration decodes one shard at a time;
    random access keeps a one-shard cache. Never used on the training hot
    path — batches come through the shard ring."""

    def __init__(self, loader: "StreamingGraphLoader"):
        self._loader = loader

    def __len__(self) -> int:
        return int(self._loader._ns.size)

    def __iter__(self):
        manifest = self._loader.manifest
        for sh in manifest["shards"]:
            yield from gshd.load_shard(
                os.path.join(manifest["_dir"], sh["file"])
            )

    def __getitem__(self, i: int) -> GraphSample:
        n = len(self)
        i = int(i)
        if i < 0:
            i += n
        if not 0 <= i < n:
            raise IndexError(i)
        return self._loader._sample_at(i)


class StreamingGraphLoader(GraphDataLoader):
    """``GraphDataLoader`` over an on-disk GSHD corpus (docs/DATA_PLANE.md).

    The corpus never materializes in host RAM: only the index (16
    bytes/sample), at most ``resident_shards`` decoded shards (+
    ``ring_depth`` decode-ahead), and the batch being collated are resident.
    The epoch plan — shuffling, ``num_shards``/``shard_rank`` round-robin
    dealing, quantile buckets, FFD packing, reshuffle granularity — is the
    inherited implementation computed over the index, so streamed training
    is bit-exact vs the in-memory loader at matched seed/shapes, and
    graftmesh's rank views / graftelastic's ``shard_schedule`` consume the
    same dealing contract unchanged.

    Quarantine is SHARD-granular: a corrupt shard (flipped byte, torn file,
    swapped content — anything v2 digest verification rejects) is dropped
    into ``self.quarantined`` up to ``skip_budget`` shards, loudly; its
    samples are skipped for the run. Exceeding the budget fails with the
    quarantine log, mirroring the in-memory sample quarantine."""

    def __init__(
        self,
        manifest_path: str,
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
        packing: bool = False,
        ladder_step: Optional[str] = None,
        ring_depth: int = 2,
        resident_shards: int = 8,
        with_positions: bool = False,
    ):
        if reshuffle not in ("sample", "batch"):
            raise ValueError(
                f"reshuffle must be 'sample' or 'batch', got {reshuffle!r}"
            )
        self.manifest = gshd.read_manifest(manifest_path)
        self.manifest_path = gshd.manifest_path_of(manifest_path)
        self._ns, self._es = gshd.read_index(self.manifest)
        self._shard_starts = gshd.shard_offsets(self.manifest)
        self.skip_budget = int(skip_budget)
        self.quarantined: List[tuple] = []  # (shard file, reason)
        self._bad_shards: Dict[int, str] = {}
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.num_shards = num_shards
        self.shard_rank = shard_rank
        self.head_types = tuple(head_types) if head_types else None
        self.head_dims = tuple(head_dims) if head_dims else None
        if edge_dim is None:
            # Dataset-level edge width from the manifest: per-batch arenas
            # must resolve edge presence/width the way the in-memory
            # DATASET-level arena does, or a batch without edge_attr samples
            # would change the pytree structure (bit-exactness contract).
            width = int(
                (self.manifest.get("fields") or {}).get("edge_attr_width", 0)
            )
            edge_dim = width or None
        self.edge_dim = edge_dim
        # GraphBatch.positions for the families that read them; config
        # completion sets it from the model family, as it sets edge_dim.
        self.with_positions = with_positions
        self.reshuffle = reshuffle
        self.packing = bool(packing)
        self.ladder_step = ladder_step
        self.epoch = 0
        self.generation = 0
        self._arena = None
        self._frozen_plan = None
        self._plan_memo = None
        self._batch_cache: dict = {}
        self._cache_budget = int(
            os.environ.get("HYDRAGNN_HOST_CACHE_MB", "1024")
        ) * (1 << 20)
        self._cache_bytes = 0
        self.size_histogram = SizeHistogram()
        for n, e in zip(self._ns.tolist(), self._es.tolist()):
            self.size_histogram.record_graph(n, e)
        self._pad_stats = self._zero_pad_stats()
        self.ring_depth = max(1, int(ring_depth))
        self.resident_shards = max(1, int(resident_shards))
        self.dataset = _CorpusView(self)
        self._view_cache: Optional[Tuple[int, List[GraphSample]]] = None
        self._last_ring_stats: Optional[dict] = None
        # Decoded shards persisted across epochs when the epoch's shard set
        # fits the resident budget (see __iter__). Consumer-thread-only.
        self._resident: Dict[int, Optional[_DecodedShard]] = {}
        # (shard-set key, arena, per-shard merged offsets): one gather arena
        # over the warm resident set, so steady epochs collate exactly like
        # the in-memory loader (consumer-thread-only; see _iter_resident).
        self._merged: Optional[Tuple[tuple, GraphArena, np.ndarray]] = None
        self._num_buckets_requested = max(1, int(num_buckets))
        self._build_buckets(self._num_buckets_requested)

    # ------------------------------------------------------------ shard access
    def _shard_of(self, idx: np.ndarray) -> np.ndarray:
        return np.searchsorted(self._shard_starts, idx, side="right") - 1

    def _decode_shard(self, sid: int) -> Tuple[_DecodedShard, int]:
        """Read + digest-verify + decode one shard. Runs on the ring's
        shard-prefetch thread; touches no loader state."""
        from ..checkpoint.format import CheckpointCorruptError

        entry = self.manifest["shards"][int(sid)]
        path = os.path.join(self.manifest["_dir"], entry["file"])
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError as e:
            raise CheckpointCorruptError(path, f"unreadable ({e})") from e
        samples = gshd.decode_shard(blob, path)
        if len(samples) != int(entry["num_samples"]):
            raise CheckpointCorruptError(
                path,
                f"sample count {len(samples)} != manifest "
                f"{entry['num_samples']}",
            )
        base = int(self._shard_starts[int(sid)])
        return _DecodedShard(samples, base), len(blob)

    def _sample_at(self, i: int) -> GraphSample:
        sid = int(self._shard_of(np.asarray([i]))[0])
        if self._view_cache is None or self._view_cache[0] != sid:
            shard, _ = self._decode_shard(sid)
            self._view_cache = (sid, shard.samples)
        return self._view_cache[1][i - int(self._shard_starts[sid])]

    # ------------------------------------------------------------- quarantine
    def _note_bad_shard(self, sid: int, reason: str) -> None:
        """Consumer-side shard quarantine: one flipped byte costs one shard,
        loudly — and never the run while the budget holds."""
        if sid in self._bad_shards:
            return
        from ..faults.counters import FaultCounters

        entry = self.manifest["shards"][sid]
        self._bad_shards[sid] = reason
        self.quarantined.append((entry["file"], reason))
        FaultCounters.inc("quarantined_shards")
        if len(self.quarantined) > self.skip_budget:
            log = "; ".join(f"{f}: {r}" for f, r in self.quarantined[:10])
            raise RuntimeError(
                f"shard quarantine budget exceeded: {len(self.quarantined)} "
                f"corrupt shard(s) > skip_budget={self.skip_budget} — {log}"
                + (" ..." if len(self.quarantined) > 10 else "")
            )
        print(
            f"WARNING: quarantined corrupt shard {entry['file']} ({reason}); "
            f"{entry['num_samples']} sample(s) skipped for this run"
        )

    # ---------------------------------------------------------------- elastic
    def reshard(self, num_shards: int, shard_rank: int) -> None:
        """Re-deal epoch plans to a changed world (graftelastic transitions
        over an out-of-core corpus): same wrap-pad round-robin contract as
        construction, with plan memo / frozen plan / caches invalidated and
        ``generation`` bumped so external device caches detect it. The
        on-disk corpus is untouched — a world transition costs no conversion
        and no corpus scan (sample conservation: tests/test_stream.py)."""
        self.num_shards = int(num_shards)
        self.shard_rank = int(shard_rank)
        self._frozen_plan = None
        self._plan_memo = None
        self._batch_cache.clear()
        self._cache_bytes = 0
        self._merged = None
        self.generation += 1
        self._size_buckets()  # a fixed plan's largest batch moved with it

    def ring_stats(self) -> Optional[dict]:
        """Decode counters of the most recent epoch's shard ring (bench)."""
        return self._last_ring_stats

    # -------------------------------------------------------------- iteration
    def __iter__(self):
        plan = self._batch_plan()
        if not plan:
            return
        needs: List[List[int]] = []
        order: List[int] = []
        order_set: set = set()
        for _pos, _bi, sample_idx, _need in plan:
            sids = self._shard_of(np.asarray(sample_idx, np.int64))
            seen: List[int] = []
            seen_set: set = set()
            for sid in sids.tolist():
                if sid not in seen_set:
                    seen_set.add(sid)
                    seen.append(sid)
                if sid not in order_set:
                    order_set.add(sid)
                    order.append(sid)
            needs.append(seen)
        capacity = max(self.resident_shards, max(len(s) for s in needs))
        if len(order) <= capacity:
            # The whole epoch's shard set fits the resident budget: decoded
            # shards (and their arenas) persist across epochs, so steady
            # epochs are decode-free once warm — the out-of-core analog of
            # the in-memory loader's long-lived arena. RAM stays bounded by
            # ``capacity`` (stale shards from a previous plan are dropped).
            for sid in list(self._resident):
                if sid not in order_set:
                    del self._resident[sid]
            yield from self._iter_resident(plan, needs, order)
        else:
            # Epoch touches more shards than fit: replay the Belady
            # fetch/evict schedule; nothing persists across epochs.
            self._resident.clear()
            self._merged = None
            yield from self._iter_belady(plan, needs, capacity)

    def _iter_resident(self, plan, needs, order):
        missing = [sid for sid in order if sid not in self._resident]
        ring = (
            ShardRing(missing, self._decode_shard, depth=self.ring_depth)
            if missing
            else None
        )
        # Fully warm (steady-state epochs): gather from ONE arena over the
        # resident set — collation cost identical to the in-memory loader.
        merged = self._ensure_merged_arena(order) if ring is None else None
        try:
            for k, (pos, bi, sample_idx, need) in enumerate(plan):
                for sid in needs[k]:
                    if sid in self._resident:
                        continue
                    self._resident[sid] = self._next_from_ring(ring, sid)
                batch = self._emit(
                    pos,
                    bi,
                    np.asarray(sample_idx, np.int64),
                    need,
                    self._resident,
                    merged=merged,
                )
                if batch is not None:
                    yield batch
        finally:
            if ring is not None:
                self._last_ring_stats = ring.stats()
                ring.close()
            else:
                self._last_ring_stats = {
                    "shards_decoded": 0,
                    "shards_failed": 0,
                    "bytes_decoded": 0,
                }

    def _ensure_merged_arena(self, order):
        """(arena, offsets) over the warm resident shard set, in global
        sample order; rebuilt only when the set (or its quarantine state)
        changes. Doubles the resident window's footprint (decoded views +
        arena concat) in exchange for in-memory-parity steady epochs."""
        key = tuple(
            sid for sid in sorted(order) if self._resident.get(sid) is not None
        )
        if self._merged is not None and self._merged[0] == key:
            return self._merged[1], self._merged[2]
        samples: List[GraphSample] = []
        offsets = np.full(len(self.manifest["shards"]), -1, np.int64)
        for sid in key:
            offsets[sid] = len(samples)
            samples.extend(self._resident[sid].samples)
        arena = GraphArena(samples)
        self._merged = (key, arena, offsets)
        return arena, offsets

    def _iter_belady(self, plan, needs, capacity):
        fetch_seq, evict_after = plan_shard_ring(needs, capacity)
        ring = ShardRing(fetch_seq, self._decode_shard, depth=self.ring_depth)
        resident: Dict[int, Optional[_DecodedShard]] = {}
        try:
            for k, (pos, bi, sample_idx, need) in enumerate(plan):
                for sid in needs[k]:
                    if sid in resident:
                        continue
                    resident[sid] = self._next_from_ring(ring, sid)
                batch = self._emit(
                    pos, bi, np.asarray(sample_idx, np.int64), need, resident
                )
                if batch is not None:
                    yield batch
                for sid in evict_after[k]:
                    resident.pop(sid, None)
        finally:
            self._last_ring_stats = ring.stats()
            ring.close()

    def _next_from_ring(self, ring, sid):
        """Pull the next scheduled shard off the ring; it MUST be ``sid``
        (consumer and ring replay the same schedule). Corrupt payloads are
        quarantined here, on the consumer thread."""
        got = ring.get() if ring is not None else None
        if got is None:
            raise RuntimeError(
                "shard ring exhausted before the plan (fetch schedule "
                "mismatch)"
            )
        gsid, payload, reason = got
        if gsid != sid:
            raise RuntimeError(
                f"shard ring out of order: wanted shard {sid}, got {gsid}"
            )
        if payload is None:
            self._note_bad_shard(sid, reason or "corrupt")
        return payload

    def _emit(self, pos, bi, sample_idx, need, resident, merged=None):
        """Collate one plan entry from resident shards (members of
        quarantined shards are dropped; an emptied batch is skipped; the
        shape is chosen from the plan's ``need``, which no quarantine
        moves, so that every process still chooses alike)."""
        sids = self._shard_of(sample_idx)
        keep = np.fromiter(
            (resident.get(int(s)) is not None for s in sids),
            bool,
            len(sids),
        )
        if not keep.all():
            sample_idx = sample_idx[keep]
            sids = sids[keep]
        if sample_idx.size == 0:
            return None
        n_pad, e_pad, g_pad = self._book_batch(bi, sample_idx, need)
        if pos is not None and pos in self._batch_cache:
            return self._batch_cache[pos]
        if merged is not None:
            # Warm resident set: one vectorized gather from the merged
            # arena, the same shape of work as the in-memory loader.
            arena, offsets = merged
            merged_idx = (
                offsets[sids] + sample_idx - self._shard_starts[sids]
            )
            batch = arena.collate(
                merged_idx,
                head_types=self.head_types or (),
                head_dims=self.head_dims or (),
                num_nodes_pad=n_pad,
                num_edges_pad=e_pad,
                num_graphs_pad=g_pad,
                edge_dim=self.edge_dim,
                with_positions=self.with_positions,
            )
            return self._maybe_cache(pos, batch)
        first = int(sids[0])
        if bool((sids == first).all()):
            # Single-shard batch: gather straight from the shard's arena —
            # the zero-Python-loop path (dominant for unshuffled epochs and
            # shard-aligned plans).
            shard = resident[first]
            batch = shard.arena.collate(
                sample_idx - shard.base,
                head_types=self.head_types or (),
                head_dims=self.head_dims or (),
                num_nodes_pad=n_pad,
                num_edges_pad=e_pad,
                num_graphs_pad=g_pad,
                edge_dim=self.edge_dim,
                with_positions=self.with_positions,
            )
        else:
            samples = [
                resident[int(s)].samples[int(i) - resident[int(s)].base]
                for i, s in zip(sample_idx.tolist(), sids.tolist())
            ]
            batch = GraphArena(samples).collate(
                np.arange(len(samples)),
                head_types=self.head_types or (),
                head_dims=self.head_dims or (),
                num_nodes_pad=n_pad,
                num_edges_pad=e_pad,
                num_graphs_pad=g_pad,
                edge_dim=self.edge_dim,
                with_positions=self.with_positions,
            )
        return self._maybe_cache(pos, batch)

    def _maybe_cache(self, pos, batch):
        if pos is not None:
            # Frozen membership (reshuffle="batch"): cache collations up to
            # the host byte budget, same contract as the in-memory loader.
            import jax as _jax

            nbytes = sum(
                getattr(leaf, "nbytes", 0)
                for leaf in _jax.tree_util.tree_leaves(batch)
            )
            if self._cache_bytes + nbytes <= self._cache_budget:
                self._batch_cache[pos] = batch
                self._cache_bytes += nbytes
        return batch
