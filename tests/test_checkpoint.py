"""Checkpoint subsystem units: atomic single-file save/restore round-trip and
the periodic mid-training save (our documented improvement over the reference's
end-of-run-only save, /root/reference/hydragnn/utils/model.py:35-47 +
run_training.py:120)."""

import glob
import pytest
import os

import numpy as np
import jax

from hydragnn_tpu.graphs import GraphSample, collate_graphs
from hydragnn_tpu.models import create_model, init_model_variables
from hydragnn_tpu.train.train_validate_test import (
    TrainingDriver,
    train_validate_test,
)
from hydragnn_tpu.train.trainer import create_train_state
from hydragnn_tpu.utils.model import load_existing_model, save_model
from hydragnn_tpu.utils.optimizer import select_optimizer

HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 4,
        "num_headlayers": 1,
        "dim_headlayers": [4],
    },
}


def _tiny_setup(rng):
    graphs = []
    for _ in range(8):
        n = int(rng.integers(3, 6))
        x = rng.normal(size=(n, 1)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        y = np.array([x.sum()], dtype=np.float32)
        y_loc = np.array([[0, 1]], dtype=np.int64)
        graphs.append(
            GraphSample(x=x, pos=np.zeros((n, 3), np.float32), y=y, y_loc=y_loc,
                        edge_index=ei)
        )
    batch = collate_graphs(graphs, ("graph",), (1,))
    model = create_model("SAGE", 1, 4, (1,), ("graph",), HEADS, [1.0], 1)
    variables = init_model_variables(model, batch)
    return model, variables, batch, graphs


class _ListLoader:
    def __init__(self, batches, dataset):
        self.batches = batches
        self.dataset = dataset

    def __iter__(self):
        return iter(self.batches)

    def __len__(self):
        return len(self.batches)


def pytest_checkpoint_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    model, variables, batch, _ = _tiny_setup(rng)
    opt = select_optimizer("AdamW", 1e-3)
    opt_state = opt.init(variables["params"])

    save_model(variables, opt_state, "ckpt_unit", path=str(tmp_path))
    assert os.path.exists(tmp_path / "ckpt_unit" / "ckpt_unit.pk")
    # no torn tmp files left behind
    assert not glob.glob(str(tmp_path / "ckpt_unit" / "*.tmp"))

    # perturb, restore, compare
    zeroed = jax.tree_util.tree_map(lambda p: p * 0, variables["params"])
    restored, restored_opt = load_existing_model(
        {"params": zeroed, "batch_stats": variables.get("batch_stats", {})},
        "ckpt_unit",
        path=str(tmp_path) + "/",
        opt_state=opt_state,
    )
    orig = jax.tree_util.tree_leaves(variables["params"])
    back = jax.tree_util.tree_leaves(restored["params"])
    for a, b in zip(orig, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def pytest_periodic_checkpoint(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    rng = np.random.default_rng(0)
    model, variables, batch, graphs = _tiny_setup(rng)
    opt = select_optimizer("AdamW", 1e-2)
    state = create_train_state(model, variables, opt)
    driver = TrainingDriver(model, opt, state)
    loader = _ListLoader([batch], graphs)

    train_validate_test(
        driver, loader, loader, loader, num_epoch=3,
        checkpoint_name="periodic_unit", checkpoint_every=2,
    )
    # saved at epoch 2 (and only via the periodic path — no end-of-run save here)
    assert os.path.exists("logs/periodic_unit/periodic_unit.pk")


def pytest_keep_last_k_retention_manifest_and_tmp_cleanup(tmp_path):
    """save_model(keep_last_k=2): epoch-tagged retained checkpoints pruned to
    the last 2 with an atomically-updated manifest, the latest-checkpoint
    contract (<name>.pk) intact, and tmp hygiene per the async-writer rules:
    saves use writer-owned UNIQUE tmp names and leave none behind, a foreign
    ``.tmp`` is NOT touched at save entry (it could be a live concurrent
    async write — cleanup is scoped to run startup), and the explicit startup
    cleanup helper removes it."""
    from hydragnn_tpu.utils.model import (
        cleanup_stale_checkpoint_tmp,
        load_checkpoint_manifest,
        load_checkpoint_meta,
    )

    rng = np.random.default_rng(0)
    model, variables, batch, _ = _tiny_setup(rng)
    opt = select_optimizer("AdamW", 1e-3)
    opt_state = opt.init(variables["params"])

    run_dir = tmp_path / "ret_unit"
    os.makedirs(run_dir)
    # A foreign tmp (torn leftover OR a concurrent writer's live file): save
    # must neither fail on it nor delete it.
    (run_dir / "ret_unit.pk.tmp").write_bytes(b"foreign")
    for epoch in (1, 2, 3):
        save_model(
            variables, opt_state, "ret_unit", path=str(tmp_path) + "/",
            meta={"epoch": epoch}, keep_last_k=2,
        )
    files = sorted(os.listdir(run_dir))
    assert "ret_unit.pk.tmp" in files, "save entry must not remove foreign tmp"
    # ... but the saves' own unique tmp names all got renamed away.
    assert glob.glob(str(run_dir / "*.tmp")) == [str(run_dir / "ret_unit.pk.tmp")]
    # Latest + last-2 retained; epoch 1 pruned.
    assert "ret_unit.pk" in files
    assert "ret_unit.e000002.pk" in files and "ret_unit.e000003.pk" in files
    assert "ret_unit.e000001.pk" not in files
    manifest = load_checkpoint_manifest("ret_unit", path=str(tmp_path) + "/")
    assert manifest["keep_last_k"] == 2
    assert [e["epoch"] for e in manifest["entries"]] == [2, 3]
    assert all(os.path.exists(run_dir / e["file"]) for e in manifest["entries"])
    assert load_checkpoint_meta("ret_unit", path=str(tmp_path) + "/")["epoch"] == 3
    # Retained files are loadable checkpoints (same payload as the latest).
    from hydragnn_tpu.utils.model import load_checkpoint_file

    restored, _, meta = load_checkpoint_file(
        {"params": variables["params"], "batch_stats": {}},
        str(run_dir / "ret_unit.e000002.pk"),
    )
    assert meta["epoch"] == 2
    # Explicit startup cleanup helper (run_training/supervisor startup, when
    # no writer can be in flight) removes the foreign tmp and any junk.
    (run_dir / "junk.tmp").write_bytes(b"x")
    removed = cleanup_stale_checkpoint_tmp(str(run_dir))
    assert len(removed) == 2 and not glob.glob(str(run_dir / "*.tmp"))


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
    deadline = _time.time() + 600
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
