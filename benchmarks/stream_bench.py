"""graftstream benchmark: the out-of-core data plane A/B + drill matrix
(bench.py --stream → STREAM_rNN.json, docs/DATA_PLANE.md).

Four sections, all over the SAME production corpus (ci_multihead through
``bench.build_production_pipeline``, converted to GSHD with the real
``datasets convert`` path):

* **train A/B** — steady-epoch wall, in-memory loader vs streamed loader,
  through the real TrainingDriver + DeviceFeed, with the per-epoch
  transfer/compute split from ``FeedStats`` for each arm. The acceptance
  gates ride here: final parameters BIT-EXACT across arms (identical epoch
  plans + collations ⇒ identical optimizer trajectory) and streamed steady
  wall within 5% of in-memory.
* **batch inference** — a GSHD corpus streamed through an engine's packed
  bucket ladder via ``serve.batch.run_batch_inference``; graphs/s headline
  + exact output parity vs direct ``engine.predict``.
* **corrupt-shard drill** — one flipped byte in a real shard: quarantined
  (loudly, run survives) under ``skip_budget=1``; fails the epoch at budget
  0.
* **elastic transition** — rank views over the streamed corpus at world N,
  ``reshard`` to world M mid-sequence: per-world union still covers the
  corpus exactly (wrap-pad accounted), the graftelastic dealing contract.

Run on CPU this measures plumbing, not TPU numbers; the artifact labels the
platform (same convention as every bench arm).
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _tree_equal(a, b) -> bool:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _train_ab(tmp: str, epochs: int = 5, batch_size: int = 64) -> dict:
    """In-memory vs streamed steady-epoch A/B over the production pipeline.
    Also converts the corpus into ``tmp``/gshd (reused by later sections)."""
    from bench import build_production_pipeline
    from hydragnn_tpu.datasets import shards

    pipe_mem = build_production_pipeline(batch_size=batch_size)
    cfg = pipe_mem["config"]

    gshd_root = os.path.join(tmp, "gshd")
    gshd_paths = {}
    t0 = time.perf_counter()
    for split, pkl in cfg["Dataset"]["path"].items():
        split_dir = os.path.join(gshd_root, split)
        shards.convert_pickle_corpus(
            pkl, split_dir, config=cfg, shard_size=64, name=split
        )
        gshd_paths[split] = split_dir
    convert_s = time.perf_counter() - t0

    pipe_st = build_production_pipeline(
        batch_size=batch_size, dataset_overrides={"path": gshd_paths}
    )
    from hydragnn_tpu.datasets.stream import StreamingGraphLoader

    assert isinstance(pipe_st["train_loader"], StreamingGraphLoader), (
        "GSHD paths did not route through the streaming loader"
    )

    arms = {}
    for arm, pipe in (("in_memory", pipe_mem), ("streamed", pipe_st)):
        loader, driver = pipe["train_loader"], pipe["driver"]
        loader.set_epoch(0)
        t0 = time.perf_counter()
        driver.train_epoch(loader)
        compile_s = time.perf_counter() - t0
        epoch_walls = []
        for e in range(epochs):
            loader.set_epoch(e + 1)
            t0 = time.perf_counter()
            driver.train_epoch(loader)
            epoch_walls.append(time.perf_counter() - t0)
        # Min over steady epochs: the noise-robust wall estimator (identical
        # work every epoch; scheduler jitter only ever adds time).
        steady_s = min(epoch_walls)
        arms[arm] = {
            "compile_epoch_s": round(compile_s, 3),
            "steady_epoch_s": round(steady_s, 4),
            "steady_epoch_mean_s": round(sum(epoch_walls) / epochs, 4),
            "graphs_per_sec": round(len(loader.dataset) / steady_s, 1),
            "feed_split_last_epoch": driver.feed_stats.as_dict(),
        }
        if arm == "streamed":
            arms[arm]["ring_stats_last_epoch"] = loader.ring_stats()

    bit_exact = _tree_equal(
        pipe_mem["driver"].state.params, pipe_st["driver"].state.params
    )
    ratio = arms["streamed"]["steady_epoch_s"] / arms["in_memory"]["steady_epoch_s"]
    return {
        "gshd_paths": gshd_paths,
        "config": cfg,
        "train_graphs": len(pipe_mem["train_loader"].dataset),
        "epochs_steady": epochs,
        "batch_size": batch_size,
        "convert_s": round(convert_s, 3),
        "arms": arms,
        "params_bit_exact": bool(bit_exact),
        "streamed_over_inmemory_wall": round(ratio, 4),
        "wall_within_5pct": bool(ratio <= 1.05),
        "ok": bool(bit_exact),
    }


def _batch_inference(tmp: str) -> dict:
    """GSHD corpus → engine's packed ladder → prediction shards; graphs/s
    headline + exact parity vs direct predict()."""
    from hydragnn_tpu.datasets import shards
    from hydragnn_tpu.serve.batch import iter_predictions, run_batch_inference
    from benchmarks.serve_load import build_serving_engine

    engine, graphs = build_serving_engine(
        pool_size=96, max_batch_graphs=16, max_delay_ms=0.5, packing=True
    )
    corpus = os.path.join(tmp, "infer_corpus")
    shards.write_gshd(corpus, graphs, shard_size=16, name="infer")
    out = os.path.join(tmp, "preds")
    try:
        manifest = run_batch_inference(engine, corpus, out, chunk_size=32)
        direct = engine.predict(graphs, timeout=120.0)
    finally:
        engine.close()
    parity = True
    seen = 0
    for idx, heads in iter_predictions(out):
        seen += 1
        ref = direct[idx]
        if len(heads) != len(ref) or not all(
            np.array_equal(h, np.asarray(r)) for h, r in zip(heads, ref)
        ):
            parity = False
    return {
        "graphs": len(graphs),
        "graphs_per_sec": round(manifest["graphs_per_sec"], 1),
        "wall_s": round(manifest["wall_s"], 4),
        "pred_shards": len(manifest["shards"]),
        "parity_vs_predict": bool(parity and seen == len(graphs)),
        "ok": bool(parity and seen == len(graphs)),
    }


def _corrupt_drill(tmp: str, train_dir: str) -> dict:
    """Flip one byte in a real shard: skip_budget=1 survives (one shard
    quarantined, loudly), budget 0 fails the epoch."""
    from hydragnn_tpu.datasets.stream import StreamingGraphLoader

    damaged = os.path.join(tmp, "damaged_train")
    shutil.copytree(train_dir, damaged)
    victim = sorted(glob.glob(os.path.join(damaged, "shard-*.gshd")))[1]
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(victim, "wb") as f:
        f.write(bytes(blob))

    tolerant = StreamingGraphLoader(
        damaged, batch_size=16, shuffle=True, seed=0, skip_budget=1
    )
    batches = sum(1 for _ in tolerant)
    survived = batches > 0 and len(tolerant.quarantined) == 1

    strict = StreamingGraphLoader(
        damaged, batch_size=16, shuffle=True, seed=0, skip_budget=0
    )
    raised = False
    try:
        for _ in strict:
            pass
    except RuntimeError:
        raised = True
    return {
        "quarantined": list(tolerant.quarantined),
        "batches_with_budget_1": batches,
        "survived_with_budget_1": bool(survived),
        "raised_with_budget_0": bool(raised),
        "ok": bool(survived and raised),
    }


def _elastic_transition(train_dir: str, world_a: int = 2, world_b: int = 3) -> dict:
    """World N→M transition over the streamed corpus: every world's rank
    views jointly cover the corpus exactly (wrap-pad accounted) with the
    same dealing contract graftelastic's shard_schedule consumes."""
    from hydragnn_tpu.datasets.stream import StreamingGraphLoader

    def world_multiset(world):
        out = []
        per_rank = []
        for rank in range(world):
            loader = StreamingGraphLoader(
                train_dir, batch_size=8, shuffle=True, seed=7,
                num_shards=world, shard_rank=rank,
            )
            mine = []
            for _, _, idx, _ in loader._batch_plan():
                mine.extend(np.asarray(idx).tolist())
            per_rank.append(mine)
            out.extend(mine)
        return loader, out, per_rank

    loader, flat_a, _ = world_multiset(world_a)
    n = len(loader.dataset)
    pad_a = -(-n // world_a) * world_a

    # The SAME loader objects transition via reshard() — here one stands in
    # for each rank of the new world.
    flat_b = []
    for rank in range(world_b):
        loader.reshard(world_b, rank)
        for _, _, idx, _ in loader._batch_plan():
            flat_b.extend(np.asarray(idx).tolist())
    pad_b = -(-n // world_b) * world_b

    cover_a = set(flat_a) == set(range(n)) and len(flat_a) == pad_a
    cover_b = set(flat_b) == set(range(n)) and len(flat_b) == pad_b
    return {
        "train_graphs": n,
        "world_a": world_a,
        "world_b": world_b,
        "conserved_world_a": bool(cover_a),
        "conserved_world_b_after_reshard": bool(cover_b),
        "ok": bool(cover_a and cover_b),
    }


def run_stream_bench() -> dict:
    tmp = tempfile.mkdtemp(prefix="hydragnn_stream_bench_")
    try:
        ab = _train_ab(tmp)
        train_dir = ab.pop("gshd_paths")["train"]
        ab.pop("config")
        infer = _batch_inference(tmp)
        corrupt = _corrupt_drill(tmp, train_dir)
        elastic = _elastic_transition(train_dir)
        ok = all(sec["ok"] for sec in (ab, infer, corrupt, elastic))
        return {
            "train_ab": ab,
            "batch_inference": infer,
            "corrupt_shard_drill": corrupt,
            "elastic_transition": elastic,
            "drills_passed": int(corrupt["ok"]) + int(elastic["ok"]),
            "drills_total": 2,
            "ok": bool(ok),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    import json

    import jax

    if os.environ.get("HYDRAGNN_TPU_TESTS") != "1":
        jax.config.update("jax_platforms", "cpu")
    result = run_stream_bench()
    result["backend"] = jax.default_backend()
    print(json.dumps(result, indent=2))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
