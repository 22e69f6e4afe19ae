"""graftstream contract tests (ISSUE 16, docs/DATA_PLANE.md): the GSHD
format's exact round-trip + damage taxonomy, streamed-vs-in-memory collation
bit-exactness, prefetch/resident bounds, the rank-view dealing contract
across elastic transitions, batch-inference parity, and the datasets CLI."""

import json
import os
import pickle
import shutil
import subprocess
import sys
from collections import Counter

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk_samples(n_graphs, seed=0, labeled=True, edge_attr=True):
    """Synthetic training-ready samples: heads ("graph","node") with dims
    (1,2) — y is [1 graph scalar | 2*n node values], y_loc the prefix."""
    from hydragnn_tpu.graphs.sample import GraphSample

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_graphs):
        n = int(rng.integers(3, 9))
        e = int(rng.integers(2, 7))
        kw = dict(
            x=rng.standard_normal((n, 4)).astype(np.float32),
            pos=rng.standard_normal((n, 3)).astype(np.float32),
            edge_index=rng.integers(0, n, size=(2, e)).astype(np.int64),
        )
        if edge_attr:
            kw["edge_attr"] = rng.standard_normal((e, 1)).astype(np.float32)
        if labeled:
            kw["y"] = rng.standard_normal((1 + 2 * n,)).astype(np.float32)
            kw["y_loc"] = np.asarray([[0, 1, 1 + 2 * n]], np.int64)
        out.append(GraphSample(**kw))
    return out


def _write_corpus(tmp_path, n_graphs=40, shard_size=8, seed=0, **kw):
    from hydragnn_tpu.datasets import shards

    samples = _mk_samples(n_graphs, seed=seed, **kw)
    corpus = str(tmp_path / "corpus")
    shards.write_gshd(corpus, samples, shard_size=shard_size, name="t")
    return corpus, samples


def _sample_equal(a, b):
    import dataclasses

    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if va is None or vb is None:
            if not (va is None and vb is None):
                return False
            continue
        va, vb = np.asarray(va), np.asarray(vb)
        if va.dtype != vb.dtype or not np.array_equal(va, vb):
            return False
    return True


# ------------------------------------------------------------------- format
def pytest_gshd_round_trip_bit_exact(tmp_path):
    """Every field survives write->read with its exact dtype/shape/bytes,
    including absent (None) fields; conversion is byte-deterministic."""
    from hydragnn_tpu.datasets import shards

    samples = _mk_samples(11, seed=3)
    samples[4].edge_attr = None  # mixed presence within one shard
    samples[7].supercell_size = np.eye(3, dtype=np.float64)
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    shards.write_gshd(d1, samples, shard_size=4, name="t")
    shards.write_gshd(d2, samples, shard_size=4, name="t")

    back = list(shards.iter_samples(d1))
    assert len(back) == len(samples)
    assert all(_sample_equal(a, b) for a, b in zip(samples, back))
    # Wall-clock-free encoding: the same corpus converts byte-identically.
    for f in sorted(os.listdir(d1)):
        if f.endswith(".gshd"):
            assert (
                open(os.path.join(d1, f), "rb").read()
                == open(os.path.join(d2, f), "rb").read()
            ), f
    report = shards.verify_gshd(d1)
    assert report["ok"] and report["num_samples"] == 11


def pytest_gshd_damage_taxonomy(tmp_path):
    """Flipped byte, truncation, swapped files, wrong container kind: each
    is caught before any deserializer touches the bytes."""
    from hydragnn_tpu.checkpoint.format import CheckpointCorruptError
    from hydragnn_tpu.datasets import shards

    corpus, _ = _write_corpus(tmp_path, n_graphs=16, shard_size=4)
    files = sorted(
        f for f in os.listdir(corpus) if f.startswith("shard-")
    )

    # 1. One flipped byte -> digest mismatch at decode.
    blob = bytearray(open(os.path.join(corpus, files[0]), "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with pytest.raises(CheckpointCorruptError):
        shards.decode_shard(bytes(blob), files[0])

    # 2. Truncation.
    whole = open(os.path.join(corpus, files[0]), "rb").read()
    with pytest.raises(CheckpointCorruptError):
        shards.decode_shard(whole[: len(whole) // 2], files[0])

    # 3. Wrong container kind (an index blob where a shard should be).
    index_blob = open(os.path.join(corpus, shards.INDEX_NAME), "rb").read()
    with pytest.raises(CheckpointCorruptError, match="not a gshd shard"):
        shards.decode_shard(index_blob, "swapped")

    # 4. Swapped shard FILES are internally valid containers — the
    # manifest's whole-file sha256 is what catches them (verify).
    damaged = str(tmp_path / "swapped")
    shutil.copytree(corpus, damaged)
    a, b = os.path.join(damaged, files[0]), os.path.join(damaged, files[1])
    tmp = a + ".tmp"
    os.rename(a, tmp)
    os.rename(b, a)
    os.rename(tmp, b)
    report = shards.verify_gshd(damaged)
    assert not report["ok"]
    assert any("sha256" in e for e in report["errors"])


# ----------------------------------------------------- collation bit-exactness
@pytest.mark.parametrize(
    "knobs",
    [
        dict(shuffle=True, num_buckets=1, reshuffle="sample", packing=False,
             with_positions=False),
        dict(shuffle=True, num_buckets=2, reshuffle="batch", packing=True,
             with_positions=True),
        dict(shuffle=False, num_buckets=1, reshuffle="sample", packing=False,
             with_positions=False),
    ],
)
def pytest_streamed_collation_bit_exact_vs_in_memory(tmp_path, knobs):
    """The streamed loader's batches are BIT-identical to the in-memory
    loader's at matched seed/knobs — both on the warm resident path and on
    the Belady replay path (resident_shards below the epoch's shard set).
    ``with_positions`` is such a knob (config completion sets it on both):
    said, because the in-memory constructor's unsaid default follows the
    samples and the streamed one's is False (PR 26)."""
    import jax

    from hydragnn_tpu.datasets.stream import StreamingGraphLoader
    from hydragnn_tpu.preprocess.dataloader import GraphDataLoader

    corpus, samples = _write_corpus(tmp_path, n_graphs=37, shard_size=8)
    common = dict(
        batch_size=8, seed=5, head_types=("graph", "node"),
        head_dims=(1, 2), edge_dim=1, **knobs,
    )
    mem = GraphDataLoader(samples, **common)
    for resident in (8, 1):  # warm/merged path, then forced Belady path
        st = StreamingGraphLoader(corpus, resident_shards=resident, **common)
        for epoch in (0, 1, 2):
            mem.set_epoch(epoch)
            st.set_epoch(epoch)
            got_mem = list(mem)
            got_st = list(st)
            assert len(got_mem) == len(got_st)
            for bm, bs in zip(got_mem, got_st):
                lm = jax.tree_util.tree_leaves(bm)
                ls = jax.tree_util.tree_leaves(bs)
                assert len(lm) == len(ls)
                for x, y in zip(lm, ls):
                    assert np.asarray(x).dtype == np.asarray(y).dtype
                    assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("ladder_step", [None, "pow2", "mult64"])
def pytest_streamed_and_in_memory_loaders_round_their_pads_alike(tmp_path, ladder_step):
    """Both loaders take a bucket's static pad from the same count arrays
    through the same function, so an absent ``ladder_step`` gives both the
    same multiples of the kernels' tile (two buckets, each over 2,048 rows:
    under that the default is a power of two like ``"pow2"``), a named one
    what it always gave, and the first batches are bit-identical."""
    from hydragnn_tpu.datasets import shards
    from hydragnn_tpu.datasets.stream import StreamingGraphLoader
    from hydragnn_tpu.graphs.collate import loader_pad_tile
    from hydragnn_tpu.graphs.sample import GraphSample
    from hydragnn_tpu.preprocess.dataloader import GraphDataLoader

    rng = np.random.default_rng(11)
    samples = []
    for i in range(192):
        n = int(rng.integers(20, 30)) * (1 if i % 2 else 3)
        e = 4 * n
        samples.append(GraphSample(
            x=rng.standard_normal((n, 4)).astype(np.float32),
            pos=rng.standard_normal((n, 3)).astype(np.float32),
            edge_index=rng.integers(0, n, size=(2, e)).astype(np.int64),
            y=rng.standard_normal((1,)).astype(np.float32),
            y_loc=np.asarray([[0, 1]], np.int64),
        ))
    corpus = str(tmp_path / "corpus")
    shards.write_gshd(corpus, samples, shard_size=32, name="t")
    common = dict(
        batch_size=96, shuffle=True, seed=2, num_buckets=2, head_types=("graph",),
        head_dims=(1,), with_positions=False, ladder_step=ladder_step,
    )
    mem = GraphDataLoader(samples, **common)
    st = StreamingGraphLoader(corpus, **common)
    assert mem.num_buckets == st.num_buckets == 2
    assert mem._bucket_pads == st._bucket_pads and mem._worst_pads == st._worst_pads
    tile = loader_pad_tile()
    # Rungs of the worst case, rounded as it is: the fitted shapes' too.
    for n_pad, e_pad, _ in mem._bucket_pads + mem._worst_pads:
        assert n_pad > 4 * tile and e_pad > 4 * tile
        power_of_two = n_pad & (n_pad - 1) == 0 and e_pad & (e_pad - 1) == 0
        if ladder_step is None:
            assert n_pad % tile == 0 and e_pad % tile == 0 and not power_of_two
        elif ladder_step == "pow2":
            assert power_of_two
        else:
            assert n_pad % 64 == 0 and e_pad % 64 == 0 and e_pad % tile != 0
    bm, bs = next(iter(mem)), next(iter(st))
    assert bm.node_features.shape[0] in {p[0] for p in mem._bucket_pads}
    assert np.array_equal(bm.senders, bs.senders)
    assert np.array_equal(bm.node_features, bs.node_features)


@pytest.mark.parametrize(
    "knobs",
    [
        dict(shuffle=True, num_buckets=2),
        dict(shuffle=True, num_buckets=2, reshuffle="batch"),
        dict(shuffle=False),
        dict(shuffle=True, num_buckets=2, num_shards=2, shard_rank=1),
    ],
    ids=["drawn", "frozen", "unshuffled", "shard-1-of-2"],
)
def pytest_streamed_and_in_memory_loaders_size_and_choose_alike(tmp_path, knobs, monkeypatch):
    """The streaming loader sizes its buckets from the GSHD index as the
    in-memory loader does from its samples (the same bound, the same plan,
    the same rungs), and with the bound cut to the mean both send the same
    batches to the worst-case shape, bit-identical, and count them alike; a
    ``reshard`` sizes a fixed plan anew."""
    import jax

    from hydragnn_tpu.datasets import shards
    from hydragnn_tpu.datasets.stream import StreamingGraphLoader
    from hydragnn_tpu.graphs import collate
    from hydragnn_tpu.graphs.sample import GraphSample
    from hydragnn_tpu.preprocess.dataloader import GraphDataLoader

    rng = np.random.default_rng(3)
    samples = []
    for n in rng.choice((8, 12, 12, 16, 18, 24, 24, 36), size=768):
        n = int(n)
        samples.append(GraphSample(
            x=rng.standard_normal((n, 4)).astype(np.float32),
            pos=rng.standard_normal((n, 3)).astype(np.float32),
            edge_index=rng.integers(0, n, size=(2, 4 * n - int(rng.integers(0, n)))).astype(np.int64),
            y=rng.standard_normal((1,)).astype(np.float32),
            y_loc=np.asarray([[0, 1]], np.int64),
        ))
    corpus = str(tmp_path / "corpus")
    shards.write_gshd(corpus, samples, shard_size=96, name="t")
    common = dict(  # multiples of 64 rows: the tile is wide beside 128 graphs' spread
        batch_size=128, seed=4, head_types=("graph",), head_dims=(1,),
        with_positions=False, ladder_step="mult64", **knobs,
    )
    fitted = {}
    for tight in (False, True):
        if tight:
            monkeypatch.setattr(collate, "PAD_SIGMAS", 0)
            monkeypatch.setattr(collate, "PAD_RUNGS", 4096)
        mem = GraphDataLoader(samples, **common)
        st = StreamingGraphLoader(corpus, **common)
        assert mem._bucket_pads == st._bucket_pads and mem._worst_pads == st._worst_pads
        assert all(f < w for f, w in zip(mem._bucket_pads, mem._worst_pads))
        fitted[tight] = mem._bucket_pads
        for epoch in (0, 1):
            mem.set_epoch(epoch)
            st.set_epoch(epoch)
            for bm, bs in zip(mem, st, strict=True):
                for x, y in zip(jax.tree_util.tree_leaves(bm), jax.tree_util.tree_leaves(bs), strict=True):
                    assert np.array_equal(np.asarray(x), np.asarray(y))
        assert mem.padding_stats() == st.padding_stats()
        fell = mem.padding_stats()["fallback_batches"]
        # Only a drawn batch can pass its bucket's shape.
        assert (fell > 0) == (tight and knobs["shuffle"] and "reshuffle" not in knobs)
    assert fitted[True] != fitted[False]
    if not knobs["shuffle"]:
        st.reshard(2, 0)  # other batches: a fixed plan's shape follows them
        other = GraphDataLoader(samples, num_shards=2, shard_rank=0, **common)
        assert st._bucket_pads == other._bucket_pads != fitted[True]


# -------------------------------------------------------------- prefetch ring
def pytest_plan_shard_ring_bounds_and_coverage():
    """The Belady schedule never holds more than ``capacity`` shards and
    every batch's needs are resident at use time — for any capacity."""
    from hydragnn_tpu.datasets.stream import plan_shard_ring

    rng = np.random.default_rng(0)
    needs = [
        list(dict.fromkeys(rng.integers(0, 9, size=4).tolist()))
        for _ in range(30)
    ]
    for capacity in (1, 2, 3, 9):
        cap = max(capacity, max(len(s) for s in needs))
        fetch_seq, evict_after = plan_shard_ring(needs, cap)
        it = iter(fetch_seq)
        resident = set()
        for k, sids in enumerate(needs):
            for sid in sids:
                if sid not in resident:
                    assert next(it) == sid  # replay matches fetch order
                    resident.add(sid)
            assert set(sids) <= resident
            resident.difference_update(evict_after[k])
            # Capacity is enforced at batch boundaries (post-eviction).
            assert len(resident) <= cap
        assert next(it, None) is None  # nothing decoded that no batch needs
    with pytest.raises(ValueError):
        plan_shard_ring(needs, 0)


def pytest_prefetch_depth_and_resident_cache(tmp_path):
    """Belady epochs decode exactly the fetch schedule; warm resident epochs
    decode NOTHING (ring_stats all zero) once the corpus fits the budget."""
    from hydragnn_tpu.datasets.stream import StreamingGraphLoader

    corpus, _ = _write_corpus(tmp_path, n_graphs=32, shard_size=4)

    tight = StreamingGraphLoader(
        corpus, batch_size=4, shuffle=True, seed=1,
        resident_shards=1, ring_depth=1,
    )
    for _ in tight:
        pass
    stats = tight.ring_stats()
    assert stats["shards_decoded"] >= 8  # all 8 shards, plus re-decodes
    assert stats["bytes_decoded"] > 0

    roomy = StreamingGraphLoader(
        corpus, batch_size=4, shuffle=True, seed=1, resident_shards=8,
    )
    for _ in roomy:
        pass
    assert roomy.ring_stats()["shards_decoded"] == 8  # cold: each once
    roomy.set_epoch(1)
    for _ in roomy:
        pass
    assert roomy.ring_stats() == {
        "shards_decoded": 0, "shards_failed": 0, "bytes_decoded": 0,
    }


def pytest_shard_ring_error_propagates_to_consumer(tmp_path):
    """A non-corruption decode failure re-raises at the consumer (never a
    silent thread death)."""
    from hydragnn_tpu.datasets.stream import ShardRing

    def boom(sid):
        raise OSError("disk on fire")

    ring = ShardRing([0, 1], boom, depth=1)
    with pytest.raises(OSError, match="disk on fire"):
        ring.get()
    ring.close()
    assert ring.join(30)


# --------------------------------------------------------------- quarantine
def pytest_corrupt_shard_quarantine_and_budget(tmp_path):
    """One flipped byte costs one shard, loudly, never the run — while the
    budget holds; past it the epoch fails with the quarantine log."""
    from hydragnn_tpu.datasets.stream import StreamingGraphLoader

    corpus, samples = _write_corpus(tmp_path, n_graphs=24, shard_size=6)
    victim = os.path.join(corpus, "shard-00002.gshd")
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0x01
    with open(victim, "wb") as f:
        f.write(bytes(blob))

    loader = StreamingGraphLoader(
        corpus, batch_size=5, shuffle=True, seed=0, skip_budget=1,
    )
    seen = 0
    for batch in loader:
        seen += int(np.asarray(batch.graph_mask).sum())
    assert len(loader.quarantined) == 1
    assert loader.quarantined[0][0] == "shard-00002.gshd"
    assert seen == len(samples) - 6  # exactly the bad shard's samples lost

    strict = StreamingGraphLoader(
        corpus, batch_size=5, shuffle=True, seed=0, skip_budget=0,
    )
    with pytest.raises(RuntimeError, match="quarantine budget"):
        for _ in strict:
            pass


# ------------------------------------------------------------ dealing contract
def pytest_rank_views_disjoint_and_conserved_across_reshard(tmp_path):
    """Rank views cover the corpus exactly (wrap-pad accounted) and stay
    exact after a live ``reshard`` to a different world size."""
    from hydragnn_tpu.datasets.stream import StreamingGraphLoader

    corpus, samples = _write_corpus(tmp_path, n_graphs=37, shard_size=8)
    n = len(samples)

    def world_view(loader, world):
        flat, per_rank = [], []
        for rank in range(world):
            loader.reshard(world, rank)
            mine = []
            for _, _, idx, _ in loader._batch_plan():
                mine.extend(np.asarray(idx).tolist())
            per_rank.append(mine)
            flat.extend(mine)
        return flat, per_rank

    loader = StreamingGraphLoader(corpus, batch_size=4, shuffle=True, seed=9)
    for world in (3, 2):  # 3-world, then a live transition to 2-world
        flat, per_rank = world_view(loader, world)
        pad = -(-n // world) * world
        counts = Counter(flat)
        assert set(flat) == set(range(n))
        assert len(flat) == pad
        assert max(counts.values()) <= 2
        assert sum(1 for c in counts.values() if c == 2) == pad - n
        # Disjoint apart from the wrap-pad duplicates.
        once = [i for i, c in counts.items() if c == 1]
        for i in once:
            assert sum(i in r for r in per_rank) == 1


# ------------------------------------------------------------ batch inference
def pytest_batch_inference_parity_and_pred_shard_integrity(tmp_path):
    """serve.batch predictions are exactly engine.predict's, shard-aligned
    with global indices; prediction shards are digest-verified; a corrupt
    input shard is skipped within budget and fatal past it."""
    from benchmarks.serve_load import build_serving_engine
    from hydragnn_tpu.checkpoint.format import CheckpointCorruptError
    from hydragnn_tpu.datasets import shards
    from hydragnn_tpu.serve.batch import (
        decode_pred_shard,
        iter_predictions,
        run_batch_inference,
    )

    engine, graphs = build_serving_engine(
        hidden=4, layers=1, max_batch_graphs=4, max_delay_ms=1.0,
        pool_size=20,
    )
    corpus = str(tmp_path / "infer")
    shards.write_gshd(corpus, graphs, shard_size=5, name="infer")
    out = str(tmp_path / "preds")
    try:
        manifest = run_batch_inference(engine, corpus, out, chunk_size=6)
        direct = engine.predict(graphs, timeout=120.0)

        seen = 0
        for idx, heads in iter_predictions(out):
            seen += 1
            assert len(heads) == len(direct[idx])
            for h, r in zip(heads, direct[idx]):
                assert np.array_equal(h, np.asarray(r))
        assert seen == len(graphs) == manifest["num_samples"]
        assert manifest["graphs_per_sec"] and manifest["graphs_per_sec"] > 0
        assert [s["source"] for s in manifest["shards"]] == [
            s["file"] for s in shards.read_manifest(corpus)["shards"]
        ]

        # Prediction shards carry the same digest armor as data shards.
        pred0 = os.path.join(out, manifest["shards"][0]["file"])
        blob = bytearray(open(pred0, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        with pytest.raises(CheckpointCorruptError):
            decode_pred_shard(bytes(blob), pred0)

        # Corrupt INPUT shard: skipped within budget, fatal past it.
        victim = os.path.join(corpus, "shard-00001.gshd")
        vblob = bytearray(open(victim, "rb").read())
        vblob[len(vblob) // 2] ^= 0xFF
        with open(victim, "wb") as f:
            f.write(bytes(vblob))
        tolerant = run_batch_inference(
            engine, corpus, str(tmp_path / "p2"), chunk_size=6, skip_budget=1
        )
        assert [s["file"] for s in tolerant["skipped_shards"]] == [
            "shard-00001.gshd"
        ]
        assert tolerant["num_samples"] == len(graphs) - 5
        with pytest.raises(RuntimeError, match="skip_budget"):
            run_batch_inference(
                engine, corpus, str(tmp_path / "p3"), chunk_size=6,
                skip_budget=0,
            )
    finally:
        engine.close()


# ------------------------------------------------------------------------ CLI
def pytest_datasets_cli_convert_verify_ls(tmp_path):
    """convert -> verify -> ls round-trip through the actual CLI entry, and
    verify exits nonzero on a damaged directory."""
    from hydragnn_tpu.datasets.__main__ import main

    samples = _mk_samples(10, seed=2)
    pkl = str(tmp_path / "corpus.pkl")
    with open(pkl, "wb") as f:
        pickle.dump(None, f)
        pickle.dump(None, f)
        pickle.dump(samples, f)

    out = str(tmp_path / "gshd")
    assert main(["convert", pkl, out, "--shard-size", "4"]) == 0
    assert main(["verify", out]) == 0
    assert main(["ls", out]) == 0
    assert main(["verify", out, "--json"]) == 0

    victim = os.path.join(out, "shard-00001.gshd")
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(victim, "wb") as f:
        f.write(bytes(blob))
    assert main(["verify", out]) == 1


@pytest.mark.slow
def pytest_datasets_cli_subprocess_smoke(tmp_path):
    """The module actually runs as ``python -m hydragnn_tpu.datasets``."""
    corpus, _ = _write_corpus(tmp_path, n_graphs=8, shard_size=4)
    proc = subprocess.run(
        [sys.executable, "-m", "hydragnn_tpu.datasets", "verify", corpus],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr
    assert "ok: 8 samples" in proc.stdout


# ------------------------------------------------------------- deprecations
def pytest_pickle_read_path_warns_once():
    """The raw-pickle read path warns (once) and names the convert CLI."""
    import warnings

    from hydragnn_tpu.preprocess import serialized_loader as sl

    sl._pickle_warned = False
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        sl.warn_pickle_corpus_once()
        sl.warn_pickle_corpus_once()
    assert len(w) == 1
    assert issubclass(w[0].category, DeprecationWarning)
    assert "python -m hydragnn_tpu.datasets convert" in str(w[0].message)
    sl._pickle_warned = False


def pytest_visualizer_history_json_sidecar(tmp_path):
    """Loss history round-trips through the JSON sidecar; the pickle
    fallback still reads (one release of compat) with a warning."""
    import warnings

    from hydragnn_tpu.postprocess import visualizer as vz

    history = {
        "total_loss": [1.0, 0.5],
        "task_loss": np.asarray([[0.6, 0.4], [0.3, 0.2]]),
    }
    doc = {
        k: (np.asarray(v).tolist() if not isinstance(v, (int, float)) else v)
        for k, v in history.items()
    }
    with open(tmp_path / "history_loss.json", "w") as f:
        json.dump(doc, f)
    back = vz.load_history(str(tmp_path))
    assert back["total_loss"] == [1.0, 0.5]
    assert np.allclose(back["task_loss"], history["task_loss"])

    legacy = str(tmp_path / "legacy")
    os.makedirs(legacy)
    with open(os.path.join(legacy, "history_loss.pkl"), "wb") as f:
        pickle.dump(history, f)
    vz._pickle_history_warned = False
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        back = vz.load_history(legacy)
    assert back["total_loss"] == [1.0, 0.5]
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    vz._pickle_history_warned = False


# --------------------------------------------------------------- GSHD routing
def pytest_gshd_paths_route_through_streaming_loader(tmp_path):
    """A config whose Dataset.path values are GSHD dirs gets streaming
    loaders from dataset_loading_and_splitting, honoring the dealing knobs."""
    from hydragnn_tpu.datasets import shards
    from hydragnn_tpu.datasets.stream import StreamingGraphLoader
    from hydragnn_tpu.preprocess.load_data import create_streaming_dataloaders

    paths = {}
    for (split, n), seed in zip(
        (("train", 24), ("validate", 8), ("test", 8)), (11, 22, 33)
    ):
        d = str(tmp_path / split)
        shards.write_gshd(d, _mk_samples(n, seed=seed),
                          shard_size=8, name=split)
        paths[split] = d
    config = {
        "Dataset": {"path": paths},
        "NeuralNetwork": {
            "Training": {"batch_size": 6},
            "Architecture": {},
        },
    }
    train, val, test, _ = create_streaming_dataloaders(config)
    assert all(
        isinstance(x, StreamingGraphLoader) for x in (train, val, test)
    )
    assert len(train.dataset) == 24 and train.shuffle
    assert len(val.dataset) == 8 and not val.shuffle
    assert train.dataset[0].x.shape[1] == 4  # _CorpusView random access
    assert train.dataset[-1].num_nodes == train._ns[-1]
