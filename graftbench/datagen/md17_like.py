"""Seeded conformations of one fixed 21-atom molecule, MD17-style.

``examples/md17`` trains on frames of one molecule's trajectory with the
energy per atom as the single graph target and the atomic number as the only
node feature. There is no network here, so the frames are made: a fixed
geometry of 9 C, 8 H and 4 O (aspirin's formula; the geometry itself is
drawn once from a fixed stream, atoms at least 1.1 apart) shaken by Gaussian
thermal noise from ``--seed``, with a Morse-like pair potential as energy.
Every graph has the same 21 atoms, so every batch has the same shape.
"""

from __future__ import annotations

import numpy as np

DATASET = {
    "name": "graftbench_md17",
    "format": "unit_test",
    "compositional_stratified_splitting": False,
    "rotational_invariance": False,
    "node_features": {"name": ["atomic_number"], "dim": [1], "column_index": [0]},
    "graph_features": {"name": ["energy"], "dim": [1], "column_index": [0]},
}

_NUMBERS = np.array([6] * 9 + [1] * 8 + [8] * 4, dtype=np.float64)


def _geometry() -> np.ndarray:
    """21 points in a 5 x 5 x 3 box, pairwise at least 1.1 apart; the same
    for every seed (it is the molecule, not the sample)."""
    rng = np.random.default_rng(170_021)
    pts: list = []
    while len(pts) < len(_NUMBERS):
        p = rng.uniform(0.0, 1.0, 3) * (5.0, 5.0, 3.0)
        if all(np.linalg.norm(p - q) >= 1.1 for q in pts):
            pts.append(p)
    return np.asarray(pts)


def generate(params: dict, seed: int):
    """``params``: graphs, noise (standard deviation of the displacement)."""
    rng = np.random.default_rng([int(seed), 0x17])
    number = int(params["graphs"])
    base = _geometry()
    n = len(base)
    pos = base[None] + rng.normal(0.0, float(params.get("noise", 0.08)), (number, n, 3))
    dist = np.linalg.norm(pos[:, :, None, :] - pos[:, None, :, :], axis=-1)
    iu = np.triu_indices(n, 1)
    d, d0 = dist[:, iu[0], iu[1]], np.linalg.norm(base[:, None] - base[None], axis=-1)[iu]
    depth = np.sqrt(_NUMBERS[iu[0]] * _NUMBERS[iu[1]]) / 8.0
    energy = (depth * (1.0 - np.exp(-(d - d0))) ** 2).sum(axis=1) / n
    x = _NUMBERS.astype(np.float32)[:, None]
    return [
        (x.copy(), pos[i].astype(np.float32), np.array([energy[i]], np.float32))
        for i in range(number)
    ]
