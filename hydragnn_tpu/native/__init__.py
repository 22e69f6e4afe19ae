"""Native (C++) data-pipeline kernels, loaded via ctypes.

The reference gets its neighbor-list construction from torch-cluster's CUDA/C++
RadiusGraph and ase's C neighbor list (/root/reference/hydragnn/preprocess/
utils.py:51-123). Here the equivalent is a small C++ cell-list library,
compiled on first use with the system toolchain (no pybind11 in the image —
plain C ABI + ctypes keeps the build to one g++ invocation).

``available()`` is False when compilation fails (with a warning that says
why) or HYDRAGNN_NATIVE=0, and callers in preprocess/graph_build.py then take
the numpy/cKDTree path; both paths produce identical edge sets
(tests/test_native_neighborlist.py). ``rebuild()`` compiles again whatever
binary is on disk — ``_neighborlist.so`` is untracked, so a copied checkout
carries the binary of the machine it was copied from.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import warnings
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "neighborlist.cc")
_SO = os.path.join(_HERE, "_neighborlist.so")

_lib = None
_tried = False


def _compile() -> bool:
    # Built under a name of this process's own and installed by a rename:
    # processes that start together in a fresh checkout (six test workers)
    # each build, and none may load a binary another is still writing.
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except (subprocess.SubprocessError, FileNotFoundError) as e:
        detail = getattr(e, "stderr", b"") or b""
        warnings.warn(
            f"native neighbour list not built ({type(e).__name__}: "
            f"{detail.decode(errors='replace')[-300:] or e}); graph "
            "construction takes the numpy/cKDTree path",
            RuntimeWarning,
            stacklevel=3,
        )
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("HYDRAGNN_NATIVE", "1") in ("0", "false", "False"):
        return None
    stale = not os.path.exists(_SO) or (
        os.path.exists(_SRC) and os.path.getmtime(_SO) < os.path.getmtime(_SRC)
    )
    if stale and not _compile():
        return None
    try:
        lib = ctypes.CDLL(_SO)
    except OSError:
        return None
    i64, f64p, i64p = (
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
    )
    lib.hg_radius_graph_flat.restype = i64
    lib.hg_radius_graph_flat.argtypes = [
        f64p, i64, ctypes.c_double, i64, ctypes.c_int, i64p, i64p, i64,
    ]
    lib.hg_radius_graph_pbc.restype = i64
    lib.hg_radius_graph_pbc.argtypes = [
        f64p, i64, f64p, ctypes.c_double, i64, ctypes.c_int,
        i64p, i64p, f64p, i64,
    ]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def rebuild() -> bool:
    """Compile ``neighborlist.cc`` again and load the result, discarding any
    ``_neighborlist.so`` already on disk. True when the native path is in
    use afterwards."""
    global _lib, _tried
    _lib, _tried = None, False
    try:
        os.remove(_SO)
    except FileNotFoundError:
        pass
    return available()


def radius_graph(
    pos: np.ndarray, radius: float, max_neighbours: int, loop: bool = False
) -> np.ndarray:
    """Flat radius graph via the native cell list → edge_index [2, E]
    (edges j → i, nearest-first per receiver, capped at max_neighbours)."""
    lib = _load()
    assert lib is not None, "native neighborlist unavailable"
    pos = np.ascontiguousarray(pos, dtype=np.float64)
    n = pos.shape[0]
    cap = max(n * max_neighbours, 1)
    senders = np.empty(cap, dtype=np.int64)
    receivers = np.empty(cap, dtype=np.int64)
    count = lib.hg_radius_graph_flat(
        pos, n, float(radius), int(max_neighbours), int(loop),
        senders, receivers, cap,
    )
    assert count >= 0, "native neighborlist capacity error"
    return np.stack([senders[:count], receivers[:count]])


def periodic_radius_graph(
    pos: np.ndarray,
    cell: np.ndarray,
    radius: float,
    max_neighbours: Optional[int] = None,
    loop: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Periodic neighbor list over cell images → (edge_index [2, E],
    lengths [E]). Raises the reference's duplicate-edge assertion when the
    cutoff is inconsistent with the cell size."""
    lib = _load()
    assert lib is not None, "native neighborlist unavailable"
    pos = np.ascontiguousarray(pos, dtype=np.float64)
    cell = np.ascontiguousarray(np.asarray(cell, dtype=np.float64).reshape(3, 3))
    n = pos.shape[0]
    cap = max(64 * n, 64)
    while True:
        senders = np.empty(cap, dtype=np.int64)
        receivers = np.empty(cap, dtype=np.int64)
        lengths = np.empty(cap, dtype=np.float64)
        count = lib.hg_radius_graph_pbc(
            pos, n, cell, float(radius),
            -1 if max_neighbours is None else int(max_neighbours),
            int(loop), senders, receivers, lengths, cap,
        )
        if count == -1:
            cap *= 4
            continue
        assert count != -2, (
            "Adding periodic boundary conditions would result in duplicate "
            "edges. Cutoff radius must be reduced or system size increased."
        )
        return (
            np.stack([senders[:count], receivers[:count]]),
            lengths[:count],
        )
