"""Large-graph (FeSi_1024-style) end-to-end story.

The graph axis exists for datasets whose individual graphs are large (the
reference's FeSi_1024 configs, /root/reference/README.md:56: 1024-atom
unit cells). This test builds a synthetic 1024-atom-per-graph dataset with the
same BCC generator the CI datasets use (8x8x8 cells x 2 atoms), trains through
the HIGH-LEVEL API (run_training/run_prediction) twice — single-device and
edge-sharded over a graph:4 virtual mesh — asserts the two agree (the
edge-sharded composition is exact-gradient: segment psums + grad psum), and
records step times to LARGEGRAPH_r05.json at the repo root.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import hydragnn_tpu
from hydragnn_tpu.parallel.distributed import make_mesh
from tests.deterministic_graph_data import deterministic_graph_data

ATOMS = 1024  # 8 x 8 x 8 BCC cells x 2 atoms
N_CONFIGS = 16


def _config():
    with open(os.path.join(REPO, "tests/inputs", "ci.json")) as f:
        config = json.load(f)
    config["Dataset"]["name"] = "unit_test_large1024"
    config["Dataset"]["path"] = {"total": "dataset/unit_test_large1024"}
    # 16 random-type 1024-atom configs have ~unique compositions — one class
    # per sample breaks StratifiedShuffleSplit; plain split is fine here.
    config["Dataset"]["compositional_stratified_splitting"] = False
    arch = config["NeuralNetwork"]["Architecture"]
    arch["model_type"] = "PNA"
    arch["hidden_dim"] = 16
    arch["num_conv_layers"] = 2
    training = config["NeuralNetwork"]["Training"]
    training["batch_size"] = 4
    training["num_epoch"] = 2
    config["Verbosity"]["level"] = 0
    return config


def _in_workdir(workdir, fn):
    cwd = os.getcwd()
    os.makedirs(workdir, exist_ok=True)
    os.chdir(workdir)
    os.environ["SERIALIZED_DATA_PATH"] = str(workdir)
    try:
        raw = os.path.join(str(workdir), "dataset", "unit_test_large1024")
        if not os.path.isdir(raw):
            os.makedirs(raw)
            deterministic_graph_data(
                raw,
                number_configurations=N_CONFIGS,
                unit_cell_x_range=(8, 9),
                unit_cell_y_range=(8, 9),
                unit_cell_z_range=(8, 9),
            )
        return fn()
    finally:
        os.chdir(cwd)


def _train(mesh):
    config = _config()
    t0 = time.perf_counter()
    history = hydragnn_tpu.run_training(config, mesh=mesh)
    return round(time.perf_counter() - t0, 2), {
        k: [round(float(v), 6) for v in history[k]]
        for k in ("total_loss_train", "total_loss_val", "total_loss_test")
    }


def _predict(mesh):
    error, rmse_task, tv, pv = hydragnn_tpu.run_prediction(_config(), mesh=mesh)
    return {
        "error": float(error),
        "rmse_task": [float(r) for r in np.atleast_1d(np.asarray(rmse_task))],
    }


def graph_axis_equivalence(tmp_path, monkeypatch, agg_arm):
    # "sorted" = the TPU production default since r05 (graph-sharded edges of
    # a sorted batch stay sorted per shard); exercised explicitly on the CPU
    # suite where the platform default is the XLA scatter bundle.
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1" if agg_arm == "sorted" else "0")
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs a 4-device (virtual) mesh")
    mesh4 = make_mesh(data_axis=1, graph_axis=4)

    # (1) Tight equivalence where it is well-posed: evaluate the SAME trained
    # checkpoint single-device and edge-sharded -- one forward pass, so only
    # fp32 reduction-order noise may differ. (Step-level gradient equivalence
    # is locked separately by tests/test_distributed.py; comparing whole
    # TRAINING trajectories is chaotic -- ~6 AdamW steps amplify 1e-7
    # reduction noise to percent-level eval differences.)
    d = tmp_path / "single"
    train_single_s, curves_single = _in_workdir(d, lambda: _train(None))
    eval_single = _in_workdir(d, lambda: _predict(None))
    eval_sharded_same_ckpt = _in_workdir(d, lambda: _predict(mesh4))
    assert np.isfinite(eval_single["error"])
    assert abs(eval_single["error"] - eval_sharded_same_ckpt["error"]) <= 1e-3 * max(
        abs(eval_single["error"]), 1.0
    ), (eval_single, eval_sharded_same_ckpt)
    for a, b in zip(
        eval_single["rmse_task"], eval_sharded_same_ckpt["rmse_task"]
    ):
        assert abs(a - b) <= 1e-3 * max(abs(a), 1.0)

    # (2) The full high-level training path under graph sharding runs end to
    # end and must land within a SCATTER ALLOWANCE of the same-seed
    # single-device result (same config, same init seed, same data): the two
    # trajectories differ only by fp32 reduction order and the DP dropout-key
    # fold, which over this test's ~6 AdamW steps produces percent-level —
    # not multiple-of — eval differences. Allowance: 1.35x relative + 0.02
    # absolute (observed ratio across rounds is ~0.7-1.1x; r05 recorded
    # sharded 0.204 vs single 0.301). The old fixed 0.5 ceiling is KEPT as
    # the outer min() backstop: a regression that degrades both arms equally
    # would satisfy any purely relative gate.
    d2 = tmp_path / "sharded"
    train_sharded_s, curves_sharded = _in_workdir(d2, lambda: _train(mesh4))
    eval_after_sharded_train = _in_workdir(d2, lambda: _predict(mesh4))
    assert np.isfinite(eval_after_sharded_train["error"])
    quality_bound = min(1.35 * eval_single["error"] + 0.02, 0.5)
    assert eval_after_sharded_train["error"] <= quality_bound, (
        eval_after_sharded_train,
        eval_single,
        quality_bound,
    )

    epochs = _config()["NeuralNetwork"]["Training"]["num_epoch"]
    artifact = {
        "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": jax.default_backend(),
        "virtual_mesh": jax.default_backend() == "cpu",
        "atoms_per_graph": ATOMS,
        "num_graphs": N_CONFIGS,
        "model": "PNA hidden=16 x2",
        "train_epoch_s_single": round(train_single_s / epochs, 2),
        "train_epoch_s_graph4": round(train_sharded_s / epochs, 2),
        "eval_single": eval_single,
        "eval_sharded_same_ckpt": eval_sharded_same_ckpt,
        "eval_after_sharded_train": eval_after_sharded_train,
        "quality_bound_vs_single": round(float(quality_bound), 6),
        # Per-epoch loss curves of both arms — the trajectory-level evidence
        # behind the relative quality gate above.
        "curves_single": curves_single,
        "curves_graph4": curves_sharded,
        "note": "same-checkpoint eval agreement asserted to 1e-3; sharded-"
        "train error gated at 1.35x single-device + 0.02 (documented "
        "scatter allowance); virtual CPU mesh timings are plumbing "
        "canaries, not scaling evidence",
    }
    # Under tmp_path: a tier-1 run writes nothing into the tracked tree.
    with open(tmp_path / f"LARGEGRAPH_{agg_arm}.json", "w") as f:
        json.dump(artifact, f, indent=2)


# One file an arm (the other is tests/test_largegraph_sorted.py): each case is
# two trainings and three predictions, ``--dist loadfile`` gives a file to
# ONE worker, and a file of two tests starts among the last.
@pytest.mark.mpi_skip
@pytest.mark.parametrize("agg_arm", ["xla"])
def pytest_largegraph_graph_axis_equivalence(tmp_path, monkeypatch, agg_arm):
    graph_axis_equivalence(tmp_path, monkeypatch, agg_arm)
