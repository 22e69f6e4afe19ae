"""Fault-tolerance layer (hydragnn_tpu/faults/ + the guarded step, hardened
feed, and quarantine it threads through) — tier-1, CPU, deterministic.

One test per injected fault proving its designated survival mechanism fires
(guard skip / rollback / quarantine / transfer retry) with its counter
incremented, plus the inertness contracts: guards disabled = the seed code
path (no flag computed at all), guards enabled with no faults = bit-identical
results to guards-off. The supervised kill/restart drill lives in
tests/test_checkpoint.py (it shares that file's subprocess harness)."""


import numpy as np
import pytest

import jax

from hydragnn_tpu.faults import FaultCounters, FaultPlan, InjectedTransientError
from hydragnn_tpu.graphs import GraphSample
from hydragnn_tpu.models import create_model, init_model_variables
from hydragnn_tpu.preprocess.dataloader import (
    GraphDataLoader,
    invalid_sample_reason,
)
from hydragnn_tpu.train.pipeline import DeviceFeed
from hydragnn_tpu.train.train_validate_test import TrainingDriver
from hydragnn_tpu.train.trainer import create_train_state
from hydragnn_tpu.utils.optimizer import get_learning_rate, select_optimizer

HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 4,
        "num_headlayers": 1,
        "dim_headlayers": [4],
    },
}


@pytest.fixture(autouse=True)
def _reset_fault_counters():
    FaultCounters.reset()
    yield
    FaultCounters.reset()


def _dataset(rng, count=26, lo=4, hi=12):
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


def _loader(graphs, **kw):
    kw.setdefault("batch_size", 4)
    kw.setdefault("shuffle", False)
    loader = GraphDataLoader(graphs, **kw)
    loader.set_head_spec(("graph",), (1,))
    return loader


def _driver_for(loader, ft=None, plan=None):
    """Deterministic driver (seeded init): same loader → bit-identical runs."""
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    variables = init_model_variables(model, next(iter(loader)))
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    return TrainingDriver(model, opt, state, fault_tolerance=ft, fault_plan=plan)


def _params_leaves(driver):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(driver.state.params)]


def _train(driver, loader, epochs=1):
    loss = None
    for epoch in range(epochs):
        loader.set_epoch(epoch)
        loss, _ = driver.train_epoch(loader)
    return loss


class _ActiveProf:
    """Active-profiler stub: routes train_epoch onto the per-batch path."""

    active = True

    def step(self):
        pass


# ----------------------------------------------------------------- fault plan
def pytest_fault_plan_parsing_and_determinism():
    p = FaultPlan(
        "seed=7,nan_grad@2,nan_grad@5-6,corrupt_sample:count=3,"
        "slow_collate@1:ms=5,transfer_crash@0,kill@99"
    )
    assert p.active and p.seed == 7
    assert p._nan_steps == {2, 5, 6}
    assert p._kill_steps == {99}
    assert p._transfer_crashes == {0}
    # Seeded draw: same spec, same dataset size → the same corrupt indices.
    assert p.corrupt_sample_indices(40) == FaultPlan(
        "seed=7,corrupt_sample:count=3"
    ).corrupt_sample_indices(40)
    assert len(p.corrupt_sample_indices(40)) == 3
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan("explode@3")
    assert FaultPlan("").active is False
    assert FaultPlan.from_env() is None  # env not set under pytest


# ----------------------------------------------------- guard: inert when clean
def pytest_guard_clean_run_bit_identical_to_unguarded():
    """Acceptance contract: guards enabled + no faults = bit-identical params
    and losses to guards-off, on BOTH epoch paths (scan and per-batch)."""
    ds = _dataset(np.random.default_rng(0))
    loader = _loader(ds)
    plain = _driver_for(loader)
    guarded = _driver_for(loader, ft={"enabled": True})

    # Scan path epoch, then a per-batch path epoch (profiler stub).
    for drv in (plain, guarded):
        _train(drv, loader)
        drv.train_epoch(loader, profiler=_ActiveProf())
    for a, b in zip(_params_leaves(plain), _params_leaves(guarded)):
        np.testing.assert_array_equal(a, b)
    assert guarded.guard.bad_steps == 0
    assert FaultCounters.get("bad_steps") == 0


def pytest_guard_off_is_truly_unguarded():
    """With the guard disabled, an injected NaN batch DOES poison params —
    proving the disabled path carries no hidden guard (and that the drill's
    injection actually produces the failure mode)."""
    ds = _dataset(np.random.default_rng(1))
    loader = _loader(ds)
    d = _driver_for(loader, plan=FaultPlan("nan_grad@1"))
    loss = _train(d, loader)
    assert not np.isfinite(loss)
    assert not all(np.isfinite(p).all() for p in _params_leaves(d))


# -------------------------------------------------------- guard: skip/rollback
def pytest_nan_step_skipped_on_scan_path():
    ds = _dataset(np.random.default_rng(2))
    loader = _loader(ds)
    clean = _driver_for(loader)
    loss_clean = _train(clean, loader)

    d = _driver_for(
        loader,
        ft={"enabled": True, "max_bad_steps": 8},
        plan=FaultPlan("nan_grad@2"),
    )
    loss = _train(d, loader)
    assert np.isfinite(loss)
    assert all(np.isfinite(p).all() for p in _params_leaves(d))
    assert d.guard.bad_steps == 1
    assert FaultCounters.get("bad_steps") == 1
    # Same ballpark as the clean run: one skipped step, not a derailment.
    assert 0.2 * loss_clean < loss < 5.0 * loss_clean


def pytest_nan_step_skipped_on_per_batch_path():
    ds = _dataset(np.random.default_rng(3))
    loader = _loader(ds)
    d = _driver_for(
        loader,
        ft={"enabled": True, "max_bad_steps": 8},
        plan=FaultPlan("nan_grad@1"),
    )
    loader.set_epoch(0)
    loss, _ = d.train_epoch(loader, profiler=_ActiveProf())
    assert np.isfinite(loss)
    assert all(np.isfinite(p).all() for p in _params_leaves(d))
    assert d.guard.bad_steps == 1


def pytest_consecutive_bad_steps_roll_back_with_lr_backoff():
    ds = _dataset(np.random.default_rng(4))
    loader = _loader(ds)
    d = _driver_for(
        loader,
        ft={"enabled": True, "max_bad_steps": 2, "lr_backoff": 0.5},
        plan=FaultPlan("nan_grad@1-6"),
    )
    lr0 = get_learning_rate(d.state.opt_state)
    loss = _train(d, loader, epochs=2)
    assert np.isfinite(loss)
    assert d.guard.rollbacks >= 1
    assert FaultCounters.get("rollbacks") >= 1
    assert all(np.isfinite(p).all() for p in _params_leaves(d))
    # Rollback applied the LR backoff to the restored state.
    assert get_learning_rate(d.state.opt_state) == pytest.approx(lr0 * 0.5)


def pytest_guard_skips_nan_on_mesh_dp_step():
    """The shard_map DP step's guard: the flag is computed AFTER the psum, so
    every device skips in lockstep and params stay finite and replicated."""
    from hydragnn_tpu.parallel import make_mesh

    ds = _dataset(np.random.default_rng(5), count=32)
    loader = _loader(ds, batch_size=4)
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    variables = init_model_variables(model, next(iter(loader)))
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    mesh = make_mesh(data_axis=8, graph_axis=1)
    d = TrainingDriver(
        model, opt, state, mesh=mesh,
        fault_tolerance={"enabled": True, "max_bad_steps": 8},
        fault_plan=FaultPlan("nan_grad@1"),
    )
    loader.set_epoch(0)
    loss, _ = d.train_epoch(loader)
    assert np.isfinite(loss)
    assert all(np.isfinite(p).all() for p in _params_leaves(d))
    assert d.guard.bad_steps == 1


# ------------------------------------------------------------------ quarantine
def pytest_quarantine_drops_corrupt_samples_within_budget():
    ds = _dataset(np.random.default_rng(6))
    plan = FaultPlan("seed=3,corrupt_sample:count=2")
    loader = _loader(list(ds), skip_budget=4, fault_plan=plan)
    assert len(loader.quarantined) == 2
    assert len(loader.dataset) == len(ds) - 2
    assert all("non-finite" in reason for _, reason in loader.quarantined)
    assert FaultCounters.get("quarantined_samples") == 2
    d = _driver_for(loader)
    assert np.isfinite(_train(d, loader))


def pytest_quarantine_budget_exceeded_fails_loudly_with_log():
    ds = _dataset(np.random.default_rng(7))
    with pytest.raises(RuntimeError, match="quarantine budget exceeded") as ei:
        _loader(
            list(ds),
            skip_budget=1,
            fault_plan=FaultPlan("seed=3,corrupt_sample:count=3"),
        )
    assert "non-finite node features" in str(ei.value)  # the quarantine log


def pytest_invalid_sample_reason_taxonomy():
    good = _dataset(np.random.default_rng(8), count=1)[0]
    assert invalid_sample_reason(good) is None
    bad_edge = good.clone()
    bad_edge.edge_index = np.array([[0, 99], [1, 0]], np.int32)
    assert "outside the graph" in invalid_sample_reason(bad_edge)
    bad_y = good.clone()
    bad_y.y_loc = np.array([[0, 999]], np.int64)
    assert "y_loc" in invalid_sample_reason(bad_y)
    bad_x = good.clone()
    bad_x.x = np.full_like(bad_x.x, np.inf)
    assert "non-finite" in invalid_sample_reason(bad_x)
    # skip_budget=0 (default): no validation, corrupt passes through (seed
    # behavior) — the guard, not the loader, is then the survival mechanism.
    loader = GraphDataLoader([bad_x, good], batch_size=2, shuffle=False)
    assert len(loader.dataset) == 2 and loader.quarantined == []


# -------------------------------------------------------------- transfer retry
def pytest_transient_transfer_failure_retried_with_backoff():
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] == 2:
            raise InjectedTransientError("flap")
        return x * 10

    feed = DeviceFeed(iter(range(4)), transfer=flaky, transfer_backoff_s=0.001)
    assert list(feed) == [0, 10, 20, 30]
    assert calls["n"] == 5  # one retry
    assert FaultCounters.get("transfer_retries") == 1
    assert feed.join(5)


def pytest_non_transient_transfer_failure_propagates_immediately():
    calls = {"n": 0}

    def broken(x):
        calls["n"] += 1
        raise ValueError("shape mismatch")  # programming error: no retry

    feed = DeviceFeed(iter(range(3)), transfer=broken, transfer_backoff_s=0.001)
    with pytest.raises(ValueError, match="shape mismatch"):
        list(feed)
    assert calls["n"] == 1
    assert FaultCounters.get("transfer_retries") == 0
    assert feed.join(5)

    # Neither is HBM exhaustion (what RESOURCE_EXHAUSTED means on a chip
    # attached to the host) nor an error that merely names a transport.
    from hydragnn_tpu.train.pipeline import transfer_error_is_transient

    assert not transfer_error_is_transient(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating 2.1G")
    )
    assert not transfer_error_is_transient(RuntimeError("UNAVAILABLE: x"))


def pytest_transfer_retries_exhausted_propagates():
    def always_down(x):
        raise InjectedTransientError("still down")

    feed = DeviceFeed(
        iter(range(3)),
        transfer=always_down,
        transfer_retries=2,
        transfer_backoff_s=0.001,
    )
    with pytest.raises(InjectedTransientError, match="still down"):
        list(feed)
    assert FaultCounters.get("transfer_retries") == 2  # capped attempts
    assert feed.join(5)


def pytest_injected_transfer_crash_survived_bit_exact():
    """End to end through the driver: a transient transfer crash is retried
    and the epoch's results are BIT-identical to the clean run (the retry
    re-transfers the same payload — nothing numerical may change)."""
    ds = _dataset(np.random.default_rng(9))
    loader = _loader(ds)
    clean = _driver_for(loader)
    loss_clean = _train(clean, loader)

    d = _driver_for(loader, plan=FaultPlan("transfer_crash@0"))
    loss = _train(d, loader)
    assert loss == loss_clean
    for a, b in zip(_params_leaves(clean), _params_leaves(d)):
        np.testing.assert_array_equal(a, b)
    assert FaultCounters.get("transfer_retries") == 1


def pytest_slow_collate_absorbed_without_numerical_change():
    ds = _dataset(np.random.default_rng(10))
    loader = _loader(ds)
    clean = _driver_for(loader)
    loss_clean = _train(clean, loader)
    d = _driver_for(loader, plan=FaultPlan("slow_collate@1:ms=20"))
    assert _train(d, loader) == loss_clean
    assert FaultCounters.get("injected_slow_collate") == 1
