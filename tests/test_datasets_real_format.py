"""Real-format dataset parser tests: genuine GDB-9 extended-XYZ records
(tests/fixtures/qm9_raw — the published dsgdb9nsd layout incl. the Fortran
``*^`` exponent notation) and MD17 npz slices in both published layouts
(sGDML R/z/E/F — what PyG's MD17 downloads, reference examples/md17/
md17.py:42-48 — and revised-MD17 coords/nuclear_charges/energies/forces).
The synthetic fallbacks are exercised everywhere else; these pin the
real-bytes paths."""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hydragnn_tpu.datasets.md17 import load_md17
from hydragnn_tpu.datasets.qm9 import PROPERTY_INDEX, load_qm9

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def pytest_qm9_parses_real_gdb9_records(tmp_path):
    import shutil

    root = tmp_path / "qm9"
    os.makedirs(root, exist_ok=True)
    shutil.copytree(os.path.join(FIXTURES, "qm9_raw"), root / "raw")
    samples = load_qm9(root=str(root))
    assert len(samples) == 5  # all fixtures parsed, no synthetic fallback

    # dsgdb9nsd_000001 = methane: 5 atoms (1 C + 4 H), 15 properties.
    methane = samples[0]
    assert methane.num_nodes == 5
    np.testing.assert_array_equal(
        np.sort(methane.x[:, 0]), [1.0, 1.0, 1.0, 1.0, 6.0]
    )
    assert methane.y.shape == (15,)
    # Property order is file order: U0 for methane is -40.47893 Ha.
    assert methane.y[PROPERTY_INDEX["U0"]] == np.float32(-40.47893)
    assert methane.y[PROPERTY_INDEX["G"]] == np.float32(-40.498597)
    # First-atom position read exactly.
    np.testing.assert_allclose(
        methane.pos[0], [-0.0126981359, 1.0858041578, 0.0080009958], rtol=1e-6
    )

    # dsgdb9nsd_000005 (HCN) carries *^ exponent notation in atom charges —
    # the parser must not choke on it and coordinates must still be exact.
    hcn = samples[4]
    assert hcn.num_nodes == 3
    np.testing.assert_array_equal(np.sort(hcn.x[:, 0]), [1.0, 6.0, 7.0])
    np.testing.assert_allclose(hcn.pos[1, 1], 2.289464157, rtol=1e-7)


def pytest_qm9_num_samples_and_hooks(tmp_path):
    import shutil

    root = tmp_path / "qm9"
    os.makedirs(root, exist_ok=True)
    shutil.copytree(os.path.join(FIXTURES, "qm9_raw"), root / "raw")
    samples = load_qm9(
        root=str(root),
        num_samples=3,
        pre_filter=lambda s: s.num_nodes > 3,
        pre_transform=lambda s: s,
    )
    # 3 files read (000001-000003), water (3 atoms) filtered out.
    assert len(samples) == 2


def pytest_md17_parses_sgdml_npz():
    samples = load_md17(root=os.path.join(FIXTURES, "md17"), name="uracil")
    assert len(samples) == 5
    s = samples[0]
    assert s.num_nodes == 12
    np.testing.assert_array_equal(
        np.sort(np.unique(s.x[:, 0])), [1.0, 6.0, 7.0, 8.0]
    )
    assert s.y.shape == (1,)
    assert s.y[0] < -200000  # kcal/mol total-energy scale, not synthetic
    assert s.forces.shape == (12, 3)
    # Frames differ (trajectory, not a repeated frame).
    assert not np.allclose(samples[0].pos, samples[1].pos)


def pytest_md17_parses_rmd17_layout():
    samples = load_md17(
        root=os.path.join(FIXTURES, "md17"), name="aspirin", num_samples=3
    )
    assert len(samples) == 3
    assert samples[0].num_nodes == 12
    assert samples[0].forces.shape == (12, 3)


def pytest_a_reader_never_sees_a_partial_serialized_file(tmp_path, monkeypatch):
    """Raw LSMS files -> ``serialized_dataset/<name>.pkl`` again while the file
    is in use (six test workers train the same dataset from one checkout): a
    reader that opens it between any two of the writer's three dumps gets the
    whole of the file that was there, never a truncated one."""
    import pickle

    from hydragnn_tpu.preprocess import raw_loader
    from tests.deterministic_graph_data import deterministic_graph_data

    raw = tmp_path / "dataset" / "unit_test"
    os.makedirs(raw)
    deterministic_graph_data(str(raw), number_configurations=12)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    config = {
        "name": "unit_test", "format": "unit_test", "path": {"total": str(raw)},
        "node_features": {"name": ["x", "x2", "x3"], "dim": [1, 1, 1], "column_index": [0, 6, 7]},
        "graph_features": {"name": ["sum_x_x2_x3"], "dim": [1], "column_index": [0]},
    }
    raw_loader.RawDataLoader(config).load_raw_data()
    path = tmp_path / "serialized_dataset" / "unit_test.pkl"

    def read():
        with open(path, "rb") as f:
            return [pickle.load(f) for _ in range(3)]  # a short file: EOFError

    first = read()
    assert len(first[2]) == 12
    seen = []
    dump = pickle.dump

    def dump_then_read(obj, f):
        dump(obj, f)
        seen.append(read())

    monkeypatch.setattr(raw_loader.pickle, "dump", dump_then_read)
    raw_loader.RawDataLoader(config).load_raw_data()
    monkeypatch.undo()
    assert len(seen) == 3
    for got in seen + [read()]:
        assert np.array_equal(got[0], first[0]) and np.array_equal(got[1], first[1])
        assert len(got[2]) == 12
    assert os.listdir(path.parent) == ["unit_test.pkl"]  # no temporary name left
