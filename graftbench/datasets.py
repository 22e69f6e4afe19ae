"""From a traffic mix's ``graphs`` entry to a serialized dataset the program
reads: the generator is found by name under ``graftbench/datagen/``, its
graphs are min-max normalized as ``preprocess/raw_loader.py`` does
(``_normalize_dataset``: globally, per logical feature) and pickled under
that loader's output contract (two min-max tables, then the samples), so the
program's own ``dataset_loading_and_splitting`` takes it from there: split,
radius graph, edge lengths, target packing, loaders.

A dataset is keyed by generator, parameters and seed and kept under
``graftbench/.cache/data/``: a later run of the same cell and seed skips the
generation. The key holds nothing of the run, so the path never moves.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import pickle
import time

import numpy as np


def _scale(a: np.ndarray, lo, hi) -> np.ndarray:
    span = hi - lo
    return np.divide(a - lo, span, out=np.zeros_like(a), where=span != 0)


def materialize(graphs: dict, seed: int, cache_root: str):
    """Returns (``Dataset`` config block with ``path.total`` set, seconds
    spent generating -- 0.0 on a cache hit)."""
    from hydragnn_tpu.graphs.sample import GraphSample

    gen = importlib.import_module(f"graftbench.datagen.{graphs['generator']}")
    key = hashlib.sha256(
        json.dumps([graphs, int(seed)], sort_keys=True).encode()
    ).hexdigest()[:16]
    directory = os.path.join(cache_root, "data", f"{graphs['generator']}-{key}")
    block = json.loads(json.dumps(gen.DATASET))
    total = os.path.join(directory, block["name"] + ".pkl")
    block["path"] = {"total": total}
    if os.path.exists(total):
        return block, 0.0
    t0 = time.perf_counter()
    raw = gen.generate(graphs, seed)
    if set(block["node_features"]["dim"] + block["graph_features"]["dim"]) != {1}:
        raise ValueError("graftbench.datasets normalizes one-column features only")
    xs = np.concatenate([x for x, _, _ in raw])
    ys = np.stack([y for _, _, y in raw])
    x_lo, x_hi, y_lo, y_hi = xs.min(0), xs.max(0), ys.min(0), ys.max(0)
    samples = [
        GraphSample(x=_scale(x, x_lo, x_hi), pos=pos, y=_scale(y, y_lo, y_hi))
        for x, pos, y in raw
    ]
    os.makedirs(directory, exist_ok=True)
    tmp = total + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(np.stack([x_lo, x_hi]), f)
        pickle.dump(np.stack([y_lo, y_hi]), f)
        pickle.dump(samples, f)
    os.replace(tmp, total)
    return block, time.perf_counter() - t0
