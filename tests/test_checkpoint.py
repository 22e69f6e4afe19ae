"""Checkpoint subsystem units: atomic single-file save/restore round-trip and
the periodic mid-training save (our documented improvement over the reference's
end-of-run-only save, /root/reference/hydragnn/utils/model.py:35-47 +
run_training.py:120).

The two crash drills, each a training in a child process that is killed and
started again, are files of their own (tests/test_checkpoint_supervisor.py,
tests/test_checkpoint_crash_resume.py): ``--dist loadfile`` gives a file to
ONE worker, and a file of few tests starts last."""

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
