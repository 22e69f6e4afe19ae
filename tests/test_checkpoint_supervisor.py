"""Crash-resume under the supervisor: ``run_training(supervise=True)`` with an
injected kill (tests/test_checkpoint.py has the subsystem's units and the why
of a file of its own)."""

import os


def pytest_supervisor_restarts_killed_scan_run(tmp_path, monkeypatch):
    """Crash-resume as a first-class API: run_training(supervise=True) with an
    injected kill@K fault (HYDRAGNN_FAULTS) on the SCAN epoch path (mesh=None,
    no profiler — the production single-device path). The child dies by
    SIGKILL mid-run, the supervisor restarts it, Training.resume picks up the
    periodic checkpoint, and the restart metadata (logs/<name>/supervisor.json)
    records the death + completion."""
    import json
    import signal

    from hydragnn_tpu.faults import read_supervisor_meta
    from hydragnn_tpu.run_training import run_training
    from hydragnn_tpu.utils.model import load_checkpoint_meta
    from tests.deterministic_graph_data import deterministic_graph_data

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # children must stay on CPU
    # kill@2: the scan path feeds one train batch per epoch here (24 samples,
    # batch 32), so the third fed TRAIN batch = epoch 2 — after the epoch-1
    # and epoch-2 periodic checkpoints landed. Fires only in incarnation 0
    # (HYDRAGNN_RESTART_COUNT gating), so the restart completes.
    monkeypatch.setenv("HYDRAGNN_FAULTS", "kill@2")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    config["Visualization"] = {"create_plots": False}
    tr = config["NeuralNetwork"]["Training"]
    tr["num_epoch"] = 4
    tr["periodic_checkpoint_every"] = 1
    for split, cnt in {"train": 24, "test": 8, "validate": 8}.items():
        p = f"dataset/unit_test_singlehead_{split}"
        os.makedirs(p, exist_ok=True)
        deterministic_graph_data(p, number_configurations=cnt)
        config["Dataset"]["path"][split] = p

    meta = run_training(dict(config), supervise=True, max_restarts=2)

    assert meta["completed"] is True
    assert meta["restarts"] == 1, meta
    assert len(meta["attempts"]) == 2
    # First incarnation died by SIGKILL; the restart exited clean.
    assert meta["attempts"][0]["returncode"] == -signal.SIGKILL
    assert meta["attempts"][1]["returncode"] == 0
    # The persisted metadata matches what the API returned.
    from hydragnn_tpu.utils.config_utils import get_log_name_config

    log_name = get_log_name_config(config)
    on_disk = read_supervisor_meta(log_name)
    assert on_disk["restarts"] == 1 and on_disk["completed"] is True
    # The run actually finished all epochs after resume.
    assert load_checkpoint_meta(log_name)["epoch"] == 4
