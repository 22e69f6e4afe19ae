"""Seeded BCC lattices with one graph target and three node targets.

The task of ``tests/deterministic_graph_data.py`` (copied arithmetic: atom
type -> 2-nearest-neighbour mean -> its square and cube, graph target their
sum), made in memory instead of as one LSMS text file a graph: the k-NN
regressor and the text round trip took 12.5 s for 7,680 graphs and are not
what the benchmark measures. Departures from the original: a cKDTree query in
place of sklearn's KNeighborsRegressor (same neighbours, ties broken by index
like sklearn's brute search is not guaranteed -- the targets are synthetic
either way), and no ``%.2f`` rounding of the written columns.
"""

from __future__ import annotations

import numpy as np
from scipy.spatial import cKDTree

# The ``Dataset`` block preprocess/ needs to read what generate() returns
# (tests/inputs/ci_multihead.json's; column_index is the text format's and is
# not read on this path).
DATASET = {
    "name": "graftbench_bcc",
    "format": "unit_test",
    "compositional_stratified_splitting": True,
    "rotational_invariance": False,
    "node_features": {
        "name": ["x", "x2", "x3"], "dim": [1, 1, 1], "column_index": [0, 6, 7],
    },
    "graph_features": {
        "name": ["sum_x_x2_x3"], "dim": [1], "column_index": [0],
    },
}


def generate(params: dict, seed: int):
    """``params``: graphs, cell_x/cell_y/cell_z ([lo, hi) unit cells),
    number_types. Returns one ``(x [n, 3], pos [n, 3], y [1])`` a graph, the
    columns of ``x`` being the atom type, and the square and cube of its
    2-nearest-neighbour mean (the LSMS columns 0, 6 and 7 of the original)."""
    rng = np.random.default_rng([int(seed), 0xBCC])
    number = int(params["graphs"])
    types = int(params.get("number_types", 3))
    cells = np.stack(
        [rng.integers(lo, hi, size=number) for lo, hi in (
            params["cell_x"], params["cell_y"], params["cell_z"]
        )],
        axis=1,
    )
    out = []
    for ux, uy, uz in cells:
        corner = np.stack(
            np.meshgrid(np.arange(ux), np.arange(uy), np.arange(uz), indexing="ij"),
            axis=-1,
        ).reshape(-1, 3).astype(np.float64)
        pos = np.empty((2 * len(corner), 3))
        pos[0::2] = corner
        pos[1::2] = corner + 0.5
        kind = rng.integers(0, types, size=len(pos)).astype(np.float64)
        _, nbr = cKDTree(pos).query(pos, k=2)
        smooth = kind[nbr].mean(axis=1)
        x = np.stack([kind, smooth**2, smooth**3], axis=1)
        y = np.array([smooth.sum() + x[:, 1:].sum()])
        out.append((x.astype(np.float32), pos.astype(np.float32), y.astype(np.float32)))
    return out
