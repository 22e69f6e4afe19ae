"""World-safe exercise of the top-level ``run_prediction`` surface — the
4-tuple return contract and the denormalize path — designed to run under the
2-process launcher (tests/run_suite_2proc.py) as well as serially
(reference /root/reference/hydragnn/run_prediction.py:27-80
returns (error, error_rmse_task, true_values, predicted_values)).

test_graphs.py already drives run_prediction under 2 ranks, but always with
``denormalize_output: false`` and without pinning the contract itself; this
file asserts both, on a short training run whose distinct epoch count gives it
its own checkpoint log-name (no collision with the convergence matrix's
checkpoints)."""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import hydragnn_tpu
from tests.test_graphs import ensure_raw_datasets


def pytest_run_prediction_contract_denormalize():
    os.environ["SERIALIZED_DATA_PATH"] = os.getcwd()
    with open(os.path.join(os.getcwd(), "tests/inputs", "ci.json")) as f:
        config = json.load(f)
    # Cheap run: the assertions here are contract + denormalize correctness,
    # not convergence (the convergence matrix owns accuracy). The distinct
    # epoch count is encoded into the log name, so this test trains and
    # restores its own checkpoint.
    config["NeuralNetwork"]["Architecture"]["model_type"] = "SAGE"
    config["NeuralNetwork"]["Training"]["num_epoch"] = 2
    config["NeuralNetwork"]["Variables_of_interest"]["denormalize_output"] = True

    ensure_raw_datasets(config)
    hydragnn_tpu.run_training(config)

    result = hydragnn_tpu.run_prediction(config)
    # The reference's exact 4-tuple contract.
    assert isinstance(result, tuple) and len(result) == 4
    error, error_rmse_task, true_values, predicted_values = result
    assert np.isfinite(float(error))
    n_heads = len(config["NeuralNetwork"]["Variables_of_interest"]["output_index"])
    assert len(error_rmse_task) == n_heads
    assert len(true_values) == n_heads and len(predicted_values) == n_heads

    for ihead in range(n_heads):
        tv = np.asarray(true_values[ihead], dtype=np.float64)
        pv = np.asarray(predicted_values[ihead], dtype=np.float64)
        assert tv.shape == pv.shape and tv.size > 0
        assert np.all(np.isfinite(tv)) and np.all(np.isfinite(pv))

    # Denormalize really ran: config carries the y_minmax it used, and the
    # returned values live on the ORIGINAL scale — the normalized [0,1] band
    # cannot reach the recorded min/max span unless it was rescaled.
    # (update_config mutated our dict in place during run_training.)
    y_minmax = config["NeuralNetwork"]["Variables_of_interest"].get("y_minmax")
    assert y_minmax, "denormalize_output=true must populate y_minmax"
    for ihead, pair in enumerate(y_minmax):
        tv = np.asarray(true_values[ihead], dtype=np.float64)
        lo, hi = float(np.min(pair)), float(np.max(pair))
        # Denormalized truths live inside the recorded dataset envelope...
        assert tv.min() >= lo - 1e-5 and tv.max() <= hi + 1e-5, (
            f"head {ihead}: values outside the recorded y_minmax envelope"
        )
        # ...and when that envelope is distinguishable from the normalized
        # [0,1] band, the values must actually leave the band.
        if hi - lo > 1.5:
            assert tv.min() < -0.01 or tv.max() > 1.01, (
                f"head {ihead}: values look normalized, denormalize did not run"
            )
