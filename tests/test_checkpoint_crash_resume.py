"""``Training.resume`` after a SIGKILL mid-run (tests/test_checkpoint.py has the
subsystem's units and the why of a file of its own)."""

import os

import pytest


def pytest_crash_resume_after_kill(tmp_path, monkeypatch):
    """Training.resume (extension over the reference's weights-only warm
    start, SURVEY.md §5.3/5.4): a run SIGKILLed after its first periodic
    checkpoint resumes at the saved epoch — same config, same log name — with
    scheduler decision state and loss history intact, and finishes with the
    full history length."""
    import json
    import signal
    import subprocess
    import sys
    import time as _time

    from hydragnn_tpu.run_training import run_training
    from hydragnn_tpu.utils.model import load_checkpoint_meta
    from tests.deterministic_graph_data import deterministic_graph_data

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    config["Visualization"] = {"create_plots": False}
    tr = config["NeuralNetwork"]["Training"]
    # Long enough that the run cannot finish inside the kill poll below: an
    # epoch of these 48 graphs is milliseconds once its one scan program a
    # batch shape is compiled (nothing compiles after the first epoch).
    epochs = 60
    tr["num_epoch"] = epochs
    tr["periodic_checkpoint_every"] = 2
    tr["resume"] = 1
    for split, cnt in {"train": 48, "test": 16, "validate": 16}.items():
        p = f"dataset/unit_test_singlehead_{split}"
        os.makedirs(p, exist_ok=True)
        deterministic_graph_data(p, number_configurations=cnt)
        config["Dataset"]["path"][split] = p
    with open("config.json", "w") as f:
        json.dump(config, f)

    script = (
        "import os, sys\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        f"sys.path.insert(0, {repo!r})\n"
        "import hydragnn_tpu\n"
        "hydragnn_tpu.run_training('config.json')\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", script], cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ, SERIALIZED_DATA_PATH=str(tmp_path)),
    )
    # Kill the instant the first periodic checkpoint lands (epoch 2 of 60).
    # Under the test's own limit (tests/conftest.py), so that this assertion
    # and not the limit says what went wrong.
    deadline = _time.time() + 240
    ckpt = None
    while _time.time() < deadline and proc.poll() is None:
        if os.path.isdir("logs"):
            hits = [
                d for d in os.listdir("logs")
                if os.path.exists(f"logs/{d}/{d}.pk")
            ]
            if hits:
                ckpt = hits[0]
                break
        _time.sleep(0.05)
    assert ckpt is not None, "no periodic checkpoint appeared before timeout"
    proc.send_signal(signal.SIGKILL)
    proc.wait()

    meta = load_checkpoint_meta(ckpt)
    if meta["epoch"] >= epochs:  # machine outran the 50 ms kill poll — no signal
        pytest.skip("training finished before SIGKILL landed")
    assert 0 < meta["epoch"] < epochs  # genuinely mid-run
    assert meta["scheduler"] is not None
    assert len(meta["history"]["total_loss_train"]) == meta["epoch"]

    # Same config, same log name: resume completes the remaining epochs.
    history = run_training(dict(config))
    assert len(history["total_loss_train"]) == epochs
    assert load_checkpoint_meta(ckpt)["epoch"] == epochs

    # Resuming a finished run trains zero further epochs.
    history2 = run_training(dict(config))
    assert len(history2["total_loss_train"]) == epochs
