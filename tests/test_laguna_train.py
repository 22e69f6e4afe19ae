"""Laguna through ``run_training`` (tests/test_laguna.py has the small model
and the why of a file of its own)."""

import copy
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.test_laguna import ARCH, D, LAYERS, PUBLISHED, V  # noqa: E402


def pytest_run_training_trains_the_family_through_the_loaders(tmp_path, monkeypatch):
    """``run_training`` on a ``model_type: "LAGUNA"`` config: the benchmark's
    generator and configuration file at small sizes, the loaders' split,
    config completion (the head as wide as its classes, both tables read),
    rematerialized blocks, ``TrainingDriver``'s scanned epoch. The loss falls
    from ln(vocab) and the counters are published."""
    import hydragnn_tpu
    from graftbench import datasets
    from hydragnn_tpu import telemetry

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    block, _ = datasets.materialize(
        {"generator": "token_chain", "graphs": 40, "tokens": 24, "vocab": V,
         "successors": 2}, 7, str(tmp_path / "cache"),
    )
    nn_block = copy.deepcopy(PUBLISHED)
    nn_block["Architecture"].update(
        {k: v for k, v in ARCH.items() if k != "token_minmax"}, hidden_dim=D,
        num_conv_layers=LAYERS,
    )
    assert nn_block["Architecture"]["remat"] is True
    nn_block["Variables_of_interest"]["num_classes"] = [V]
    nn_block["Training"].update(batch_size=4, num_epoch=6, learning_rate=0.01)
    config = {
        "Verbosity": {"level": 0}, "Dataset": block, "NeuralNetwork": nn_block,
        "Visualization": {"create_plots": 0},
    }
    history = hydragnn_tpu.run_training(config)
    losses = history["total_loss_train"]
    assert abs(losses[0] - np.log(V)) < 1.0 and losses[-1] < losses[0] - 1.0, losses
    assert all(np.isfinite(history["total_loss_val"]))
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["output_dim"] == [V] and arch["target_dim"] == [1]
    assert arch["head_loss"] == ["cross_entropy"]
    lo, hi = arch["token_minmax"]
    assert 0 <= lo < hi <= V - 1 and arch["class_minmax"][0][1] <= V - 1
    gauges = telemetry.gauges_snapshot()
    assert gauges["train/moe_rows_held_per_epoch"] > 0
    assert gauges["train/moe_load_max_per_epoch"] >= gauges["train/moe_load_min_per_epoch"]
