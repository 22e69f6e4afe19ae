"""Training.reshuffle="batch" — frozen batch membership with per-epoch ORDER
shuffling, enabling collation caching in the loader and device-resident chunk
caching in the driver (zero host collation / host->device transfer in steady
epochs). Opt-in because it mildly changes SGD semantics vs the reference's
DistributedSampler membership reshuffle (default reshuffle="sample",
/root/reference/hydragnn/preprocess/load_data.py:57-70)."""

import jax
import numpy as np
import pytest

from hydragnn_tpu import telemetry
from hydragnn_tpu.graphs import GraphSample
from hydragnn_tpu.graphs.collate import GraphArena
from hydragnn_tpu.models import create_model, init_model_variables
from hydragnn_tpu.parallel.distributed import make_mesh
from hydragnn_tpu.preprocess.dataloader import GraphDataLoader
from hydragnn_tpu.train.train_validate_test import TrainingDriver
from hydragnn_tpu.train.trainer import create_train_state
from hydragnn_tpu.utils.optimizer import select_optimizer

HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 4,
        "num_headlayers": 1,
        "dim_headlayers": [4],
    },
}


def _dataset(rng, count=30, lo=4, hi=12):
    graphs = []
    for _ in range(count):
        n = int(rng.integers(lo, hi))
        x = rng.normal(size=(n, 1)).astype(np.float32)
        ei = np.stack([np.arange(n), (np.arange(n) + 1) % n]).astype(np.int32)
        graphs.append(
            GraphSample(
                x=x, pos=np.zeros((n, 3), np.float32),
                y=np.array([x.sum()], np.float32),
                y_loc=np.array([[0, 1]], np.int64), edge_index=ei,
            )
        )
    return graphs


def _membership(loader, epoch):
    loader.set_epoch(epoch)
    return [
        frozenset(np.asarray(b.targets[0])[np.asarray(b.graph_mask)].ravel().tolist())
        for b in loader
    ]


def pytest_batch_mode_freezes_membership_shuffles_order():
    rng = np.random.default_rng(0)
    ds = _dataset(rng)
    loader = GraphDataLoader(ds, batch_size=7, shuffle=True, reshuffle="batch")
    loader.set_head_spec(("graph",), (1,))
    e0, e1 = _membership(loader, 0), _membership(loader, 1)
    # Same batches (membership frozen), different visit order.
    assert sorted(map(sorted, e0)) == sorted(map(sorted, e1))
    assert e0 != e1
    # Every sample still covered exactly once per epoch.
    assert sum(len(m) for m in e0) == len(ds)

    # Contrast: sample mode redraws membership.
    sample = GraphDataLoader(ds, batch_size=7, shuffle=True, reshuffle="sample")
    sample.set_head_spec(("graph",), (1,))
    s0, s1 = _membership(sample, 0), _membership(sample, 1)
    assert sorted(map(sorted, s0)) != sorted(map(sorted, s1))


def pytest_batch_mode_caches_collation(monkeypatch):
    rng = np.random.default_rng(1)
    ds = _dataset(rng)
    loader = GraphDataLoader(ds, batch_size=6, shuffle=True, reshuffle="batch")
    loader.set_head_spec(("graph",), (1,))
    calls = {"n": 0}
    real = GraphArena.collate

    def counting(self, *a, **k):
        calls["n"] += 1
        return real(self, *a, **k)

    monkeypatch.setattr(GraphArena, "collate", counting)
    n_batches = len(loader)
    for epoch in range(3):
        loader.set_epoch(epoch)
        assert sum(1 for _ in loader) == n_batches
    assert calls["n"] == n_batches  # collated once, replayed twice

    # set_head_spec invalidates (cached batches baked the old spec).
    loader.set_head_spec(("graph",), (1,))
    list(loader)
    assert calls["n"] == 2 * n_batches


def pytest_invalid_reshuffle_rejected():
    import pytest

    with pytest.raises(ValueError):
        GraphDataLoader([], batch_size=4, reshuffle="epoch")


def _driver_for(loader, layout="one_device"):
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    example = next(iter(loader))
    variables = init_model_variables(model, example)
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    mesh = None
    if layout == "mesh4":  # four of the forced host devices (tests/conftest.py)
        mesh = make_mesh(data_axis=4, graph_axis=1, devices=jax.devices()[:4])
    return TrainingDriver(model, opt, state, mesh=mesh)


# An evaluation loader's cache does not ask whether the driver has a mesh.
LAYOUTS = pytest.mark.parametrize("layout", ["one_device", "mesh4"])


def _eval_loader(ds, batch_size=5):
    ev = GraphDataLoader(ds, batch_size=batch_size, shuffle=False)
    ev.set_head_spec(("graph",), (1,))
    return ev


def pytest_driver_device_cache_replays_without_loader(monkeypatch):
    rng = np.random.default_rng(2)
    ds = _dataset(rng)
    loader = GraphDataLoader(ds, batch_size=5, shuffle=True, reshuffle="batch")
    loader.set_head_spec(("graph",), (1,))
    driver = _driver_for(loader)

    losses = []
    loader.set_epoch(0)
    losses.append(driver.train_epoch(loader)[0])
    assert driver._scan_cache.get(id(loader)), "device cache not built"

    # Steady epochs must not touch the loader at all.
    def boom(self):
        raise AssertionError("loader iterated despite device cache")

    monkeypatch.setattr(GraphDataLoader, "__iter__", boom)
    for epoch in (1, 2):
        loader.set_epoch(epoch)
        losses.append(driver.train_epoch(loader)[0])
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # still training


def pytest_driver_cache_disabled_in_sample_mode():
    rng = np.random.default_rng(3)
    ds = _dataset(rng)
    loader = GraphDataLoader(ds, batch_size=5, shuffle=True)  # sample mode
    loader.set_head_spec(("graph",), (1,))
    driver = _driver_for(loader)
    driver.train_epoch(loader)
    assert id(loader) not in driver._scan_cache


def pytest_driver_cache_respects_budget(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_DEVICE_CACHE_MB", "0")
    rng = np.random.default_rng(4)
    ds = _dataset(rng)
    loader = GraphDataLoader(ds, batch_size=5, shuffle=True, reshuffle="batch")
    loader.set_head_spec(("graph",), (1,))
    driver = _driver_for(loader)
    loader.set_epoch(0)
    l0 = driver.train_epoch(loader)[0]
    verdict = driver._scan_cache.get(id(loader))
    # Over budget: chunks=None, but the loader ref is pinned so a recycled
    # id() can never inherit the verdict.
    assert verdict["chunks"] is None and verdict["loader"] is loader
    loader.set_epoch(1)
    l1 = driver.train_epoch(loader)[0]  # plain path still trains
    assert np.isfinite(l0) and np.isfinite(l1)


@LAYOUTS
def pytest_eval_cache_identical_metrics_single_pass(monkeypatch, layout):
    """The second ``evaluate()`` reads the cache alone (no loader, no ``h2d``
    span) and every pass returns the first one's numbers and rows, which are
    an uncached driver's (``HYDRAGNN_DEVICE_CACHE_MB=0``), bit for bit. 30
    graphs in batches of 5 are six batches: on the mesh a group of four and a
    group of two padded with two empty batches."""
    ds = _dataset(np.random.default_rng(5))
    ev = _eval_loader(ds)
    driver = _driver_for(ev, layout)

    telemetry.reset()
    try:
        loss_a, rmses_a = driver.evaluate(ev)
        assert driver._eval_cache[id(ev)]["batches"], "eval cache not built"
        steps = 6 if layout == "one_device" else 2
        gauges = telemetry.gauges_snapshot()
        assert gauges["eval/steps_per_pass"] == steps
        assert gauges["eval/cached_steps_per_pass"] == 0
        assert gauges["eval/cache_mb"] > 0

        def boom(self):
            raise AssertionError("eval loader iterated despite device cache")

        monkeypatch.setattr(GraphDataLoader, "__iter__", boom)
        h2d = telemetry.counter_value("span_n/h2d")
        assert h2d == steps  # one transfer a step, the cache's build included
        loss_b, rmses_b = driver.evaluate(ev)
        assert loss_a == loss_b and rmses_a == rmses_b
        assert telemetry.counter_value("span_n/h2d") == h2d
        gauges = telemetry.gauges_snapshot()
        assert gauges["eval/cached_steps_per_pass"] == steps
        assert gauges["eval/steps_per_pass"] == steps

        # return_values path rides the cached host copies.
        loss_c, rmses_c, tv, pv = driver.evaluate(ev, return_values=True)
    finally:
        telemetry.reset()
    assert loss_c == loss_a and rmses_c == rmses_a
    assert tv[0].shape == pv[0].shape and tv[0].shape[0] == len(ds)

    monkeypatch.undo()  # the uncached driver reads the loader, every pass
    monkeypatch.setenv("HYDRAGNN_DEVICE_CACHE_MB", "0")
    plain = _driver_for(ev, layout)
    ev2 = _eval_loader(ds)
    for _ in range(2):
        loss_p, rmses_p, tv_p, pv_p = plain.evaluate(ev2, return_values=True)
        assert plain._eval_cache[id(ev2)]["batches"] is None
        assert loss_p == loss_a and rmses_p == rmses_a
        np.testing.assert_array_equal(tv_p[0], tv[0])
        np.testing.assert_array_equal(pv_p[0], pv[0])


@LAYOUTS
def pytest_eval_cache_respects_budget(monkeypatch, layout):
    """``pytest_driver_cache_respects_budget``'s evaluation twin: a budget of
    0 pins the verdict (nothing held, the loader's reference kept) and the
    plain path still evaluates, pass after pass, through the loader."""
    monkeypatch.setenv("HYDRAGNN_DEVICE_CACHE_MB", "0")
    ds = _dataset(np.random.default_rng(4))
    ev = _eval_loader(ds)
    driver = _driver_for(ev, layout)
    telemetry.reset()
    try:
        l0 = driver.evaluate(ev)[0]
        verdict = driver._eval_cache[id(ev)]
        assert verdict["batches"] is None and verdict["loader"] is ev
        l1 = driver.evaluate(ev)[0]
        assert driver._eval_cache[id(ev)] is verdict
        gauges = telemetry.gauges_snapshot()
    finally:
        telemetry.reset()
    assert np.isfinite(l0) and l0 == l1
    assert gauges["eval/cached_steps_per_pass"] == 0
    assert gauges["eval/steps_per_pass"] > 0 and gauges["eval/cache_mb"] == 0


def pytest_config_completion_defaults_reshuffle():
    import json
    import os

    from hydragnn_tpu.utils.config_utils import update_config_minmax  # noqa: F401
    # The default rides _DEFAULTS in update_config; assert the constant is
    # registered so dumped configs record the knob.
    from hydragnn_tpu.utils import config_utils

    assert ((("NeuralNetwork", "Training"), "reshuffle", "sample")
            in config_utils._DEFAULTS)


def pytest_batch_mode_composes_with_resume(tmp_path, monkeypatch):
    """Training.resume under reshuffle="batch": the device/scan caches are
    driver-instance state, so a resumed run (fresh driver) must rebuild them
    and finish with the full history — the production combination of the two
    round-5 extensions (crash resume + device-resident batching)."""
    import json
    import os

    from hydragnn_tpu.run_training import run_training
    from hydragnn_tpu.utils.model import load_checkpoint_meta, save_model
    from tests.deterministic_graph_data import deterministic_graph_data

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "tests/inputs/ci.json")) as f:
        config = json.load(f)
    config["Visualization"] = {"create_plots": False}
    tr = config["NeuralNetwork"]["Training"]
    tr["num_epoch"] = 4
    tr["periodic_checkpoint_every"] = 2
    tr["resume"] = 1
    tr["reshuffle"] = "batch"
    for split, cnt in {"train": 48, "test": 16, "validate": 16}.items():
        p = f"dataset/unit_test_singlehead_{split}"
        os.makedirs(p, exist_ok=True)
        deterministic_graph_data(p, number_configurations=cnt)
        config["Dataset"]["path"][split] = p

    history1 = run_training(dict(config))
    assert len(history1["total_loss_train"]) == 4

    # Rewind the finished checkpoint's meta to epoch 2 (the crash-resume
    # install pattern from tests/test_resume_2proc.py) and resume.
    from hydragnn_tpu.checkpoint import update_checkpoint_meta

    log = [d for d in os.listdir("logs") if os.path.exists(f"logs/{d}/{d}.pk")][0]
    ckpt = f"logs/{log}/{log}.pk"
    meta = load_checkpoint_meta(log)
    meta["epoch"] = 2
    meta["history"] = {k: v[:2] for k, v in meta["history"].items()}
    update_checkpoint_meta(ckpt, meta)

    history2 = run_training(dict(config))
    assert len(history2["total_loss_train"]) == 4
    assert load_checkpoint_meta(log)["epoch"] == 4
    np.testing.assert_allclose(
        history2["total_loss_train"][:2], history1["total_loss_train"][:2]
    )
