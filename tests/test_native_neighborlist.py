"""Parity tests: native C++ cell-list neighbor builder (hydragnn_tpu/native)
vs the pure-Python cKDTree path in preprocess/graph_build.py. Both must yield
identical edge SETS (ordering may differ; segment aggregation is
order-invariant) and identical per-receiver caps."""

import numpy as np
import pytest

from hydragnn_tpu import native
from hydragnn_tpu.preprocess import graph_build

needs_native = pytest.mark.skipif(
    not native.available(), reason="native neighborlist not built"
)


def _python_flat(pos, radius, max_nb, loop=False):
    """Run graph_build.radius_graph with the native library disabled (the load
    is cached in native._lib/_tried, so swap those, not the env var)."""
    saved = native._lib, native._tried
    native._lib, native._tried = None, True
    try:
        ei, _ = graph_build.radius_graph(pos, radius, max_nb, loop)
        return ei
    finally:
        native._lib, native._tried = saved


@needs_native
def pytest_flat_parity_random():
    rng = np.random.default_rng(0)
    for n, radius, max_nb in [(20, 0.4, 6), (150, 0.25, 10), (300, 0.15, 20)]:
        pos = rng.random((n, 3))
        native_ei = native.radius_graph(pos, radius, max_nb, False)
        python_ei = _python_flat(pos, radius, max_nb)
        ns = {(int(a), int(b)) for a, b in native_ei.T}
        ps = {(int(a), int(b)) for a, b in python_ei.T}
        # Caps may legitimately differ on distance ties; edge counts and
        # per-receiver degree must match exactly.
        assert native_ei.shape == python_ei.shape
        np.testing.assert_array_equal(
            np.bincount(native_ei[1], minlength=n),
            np.bincount(python_ei[1], minlength=n),
        )
        # With random positions there are no ties → exact set equality.
        assert ns == ps


@needs_native
def pytest_flat_cap_is_nearest_first():
    # Receiver at origin with senders at increasing distances; cap keeps the
    # closest ones.
    pos = np.array(
        [[0, 0, 0], [0.1, 0, 0], [0.2, 0, 0], [0.3, 0, 0], [0.4, 0, 0]],
        dtype=np.float64,
    )
    ei = native.radius_graph(pos, radius=1.0, max_neighbours=2, loop=False)
    to_zero = sorted(int(s) for s, r in ei.T if r == 0)
    assert to_zero == [1, 2]


def _bcc_supercell(a=2.0, reps=3):
    """BCC supercell (reps³ cells, 2 atoms each) — large enough that no (i, j)
    pair repeats across images, like the reference's 250-atom PBC test
    (/root/reference/tests/test_periodic_boundary_conditions.py)."""
    basis = np.array([[0, 0, 0], [a / 2, a / 2, a / 2]])
    pos = np.concatenate(
        [
            basis + np.array([i, j, k]) * a
            for i in range(reps)
            for j in range(reps)
            for k in range(reps)
        ]
    )
    return pos, np.eye(3) * a * reps


@needs_native
def pytest_pbc_parity_bcc():
    # BCC supercell, r covering the first neighbor shell: 8 neighbors each
    # (some via images).
    a = 2.0
    pos, cell = _bcc_supercell(a)
    radius = a * np.sqrt(3) / 2 + 1e-6

    native_ei, native_len = native.periodic_radius_graph(pos, cell, radius)
    # Python fallback path (force by calling the internals with native off):
    import hydragnn_tpu.native as nat

    old = nat._lib, nat._tried
    nat._lib, nat._tried = None, True
    try:
        python_ei, python_len = graph_build.periodic_radius_graph(
            pos, cell, radius
        )
    finally:
        nat._lib, nat._tried = old

    def canon(ei, ln):
        order = np.lexsort((ln.round(9), ei[0], ei[1]))
        return ei[:, order], ln[order]

    nei, nln = canon(native_ei, native_len)
    pei, pln = canon(python_ei, python_len)
    np.testing.assert_array_equal(nei, pei)
    np.testing.assert_allclose(nln, pln, atol=1e-12)
    # 8 first-shell neighbors per atom
    assert np.all(np.bincount(native_ei[1], minlength=len(pos)) == 8)


@needs_native
def pytest_pbc_duplicate_edges_raise():
    # One atom in a tiny cell with a radius beyond the cell size sees the same
    # neighbor through multiple images → the reference's assertion.
    pos = np.zeros((1, 3))
    cell = np.eye(3)
    with pytest.raises(AssertionError, match="duplicate edges"):
        native.periodic_radius_graph(pos, cell, radius=1.5)


@needs_native
def pytest_pbc_max_neighbours_cap():
    a = 2.0
    pos, cell = _bcc_supercell(a)
    radius = a + 1e-6  # first (8) + second (6) shells = 14 neighbors
    ei_full, _ = native.periodic_radius_graph(pos, cell, radius)
    assert np.all(np.bincount(ei_full[1], minlength=len(pos)) == 14)
    ei, ln = native.periodic_radius_graph(pos, cell, radius, max_neighbours=8)
    counts = np.bincount(ei[1], minlength=len(pos))
    assert np.all(counts == 8)
    # kept edges are the nearest shell
    assert float(ln.max()) < a


@needs_native
def pytest_rebuild_discards_the_binary_on_disk(monkeypatch):
    """_neighborlist.so is untracked, so a copied checkout carries another
    machine's binary: rebuild() compiles neighborlist.cc again whatever is on
    disk. And a failed build says so instead of silently taking the numpy
    path."""
    import os

    before = os.stat(native._SO).st_mtime_ns
    assert native.rebuild() and native.available()
    assert os.stat(native._SO).st_mtime_ns > before

    monkeypatch.setattr(native, "_SRC", native._SRC + ".missing")
    try:
        with pytest.warns(RuntimeWarning, match="numpy/cKDTree path"):
            assert not native.rebuild()
    finally:
        monkeypatch.undo()
        assert native.rebuild()
