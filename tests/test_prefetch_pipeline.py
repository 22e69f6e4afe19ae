"""Double-buffered device-feed pipeline (hydragnn_tpu/train/pipeline.py):
batch-for-batch output parity between the piped and unpiped dispatch paths,
cancellation/exception propagation through the two stages, head-spec
generation invalidation of the driver's device caches, and the
single-transfer cache build (one jax.device_put per chunk/batch)."""


import numpy as np
import pytest

import jax

from hydragnn_tpu.graphs import GraphSample
from hydragnn_tpu.graphs.batch import GraphBatch
from hydragnn_tpu.models import create_model, init_model_variables
from hydragnn_tpu.preprocess.dataloader import GraphDataLoader
from hydragnn_tpu.train.pipeline import DeviceFeed
from hydragnn_tpu.train.train_validate_test import TrainingDriver
from hydragnn_tpu.train.trainer import create_train_state, stack_batches
from hydragnn_tpu.utils.optimizer import select_optimizer

HEADS = {
    "graph": {
        "num_sharedlayers": 1,
        "dim_sharedlayers": 4,
        "num_headlayers": 1,
        "dim_headlayers": [4],
    },
}


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


def _driver_for(loader):
    """Deterministic driver: create_model/init_model_variables are seeded, so
    two calls with the same loader yield bit-identical initial states."""
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    example = next(iter(loader))
    variables = init_model_variables(model, example)
    opt = select_optimizer("AdamW", 5e-3)
    state = create_train_state(model, variables, opt)
    return TrainingDriver(model, opt, state)


class _ActiveProf:
    """Minimal active profiler stub: routes train_epoch onto the per-step
    (non-scan) path, like benchmarks/profile_epoch.py's span profiler."""

    active = True

    def step(self):
        pass


def _epoch_metrics_like(ms):
    loss = sum(float(m["loss"]) for m in ms)
    count = sum(float(m["count"]) for m in ms)
    return loss / max(count, 1.0)


def _assert_params_close(a, b):
    for la, lb in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=1e-6, atol=1e-7
        )


def _state_copy(state):
    """Fresh buffers (the donating steps may not see a buffer twice)."""
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.array, state)


# --------------------------------------------------------------------- parity
def pytest_piped_per_batch_train_matches_unpiped():
    """Per-step path: the piped epoch dispatches the SAME compiled train_step
    on the same batches in the same order as a hand-rolled unpiped loop.
    One driver, replayed from a saved initial state — the two runs share
    every compile, so the comparison is executable-for-executable."""
    ds = _dataset(np.random.default_rng(0))
    loader = GraphDataLoader(ds, batch_size=4, shuffle=False)
    loader.set_head_spec(("graph",), (1,))

    driver = _driver_for(loader)
    state0 = _state_copy(driver.state)
    loss_piped, _ = driver.train_epoch(loader, profiler=_ActiveProf())
    piped_params = driver.state.params

    state, ms = state0, []
    for b in loader:
        state, m = driver.train_step(state, b, driver.rng)
        ms.append(m)
    np.testing.assert_allclose(
        loss_piped, _epoch_metrics_like(ms), rtol=1e-6
    )
    _assert_params_close(piped_params, state.params)


def pytest_piped_scan_train_matches_unpiped():
    """Scan path: pipeline chunking + transfer-thread device_put reproduces
    the unpiped chunked epoch_scan dispatch batch for batch."""
    ds = _dataset(np.random.default_rng(1))
    loader = GraphDataLoader(ds, batch_size=4, shuffle=False)
    loader.set_head_spec(("graph",), (1,))

    driver = _driver_for(loader)
    driver.scan_chunk = 3  # multiple chunks + a remainder single-batch chunk
    state0 = _state_copy(driver.state)
    loss_piped, _ = driver.train_epoch(loader)
    piped_params = driver.state.params

    bufs, chunks = {}, []
    for b in loader:
        key = driver._shape_key(b)
        buf = bufs.setdefault(key, [])
        buf.append(b)
        if len(buf) == driver.scan_chunk:
            chunks.append(list(buf))
            buf.clear()
    for buf in bufs.values():
        if buf:
            chunks.append(list(buf))
    state, ms = state0, []
    for chunk in chunks:
        if len(chunk) == 1:
            state, m = driver.train_step(state, chunk[0], driver.rng)
        else:
            state, m = driver.epoch_scan(
                state, stack_batches(chunk, len(chunk)), driver.rng
            )
        ms.append(m)
    np.testing.assert_allclose(
        loss_piped, _epoch_metrics_like(ms), rtol=1e-6
    )
    _assert_params_close(piped_params, state.params)


def pytest_piped_evaluate_matches_unpiped():
    ds = _dataset(np.random.default_rng(2))
    train = GraphDataLoader(ds, batch_size=4, shuffle=True)
    train.set_head_spec(("graph",), (1,))
    ev = GraphDataLoader(ds, batch_size=4, shuffle=False)
    ev.set_head_spec(("graph",), (1,))
    driver = _driver_for(train)

    loss_piped, rmses_piped, tv, pv = driver.evaluate(ev, return_values=True)

    ms = []
    for b in ev:
        m, _ = driver.eval_step(driver.state, b)
        ms.append(m)
    np.testing.assert_allclose(loss_piped, _epoch_metrics_like(ms), rtol=1e-6)
    assert tv[0].shape == pv[0].shape and tv[0].shape[0] == len(ds)


# ------------------------------------------------- cancellation / exceptions
def pytest_pipeline_producer_exception_reaches_consumer():
    class Boom(RuntimeError):
        pass

    def gen():
        yield 1
        yield 2
        raise Boom("collation failed")

    feed = DeviceFeed(gen(), transfer=lambda x: x * 10)
    got = []
    with pytest.raises(Boom, match="collation failed"):
        for v in feed:
            got.append(v)
    assert got == [10, 20]  # items before the failure still delivered
    assert feed.join(5), "pipeline threads leaked after producer error"


def pytest_pipeline_transfer_exception_reaches_consumer():
    feed = DeviceFeed(
        iter(range(5)), transfer=lambda x: x if x < 2 else 1 // 0
    )
    got = []
    with pytest.raises(ZeroDivisionError):
        for v in feed:
            got.append(v)
    assert got == [0, 1]
    assert feed.join(5), "pipeline threads leaked after transfer error"


def pytest_pipeline_consumer_abandon_cancels_both_stages():
    feed = DeviceFeed(iter(range(100000)), transfer=lambda x: x)
    it = iter(feed)
    assert next(it) == 0
    it.close()  # consumer abandons mid-epoch
    assert feed.join(5), "pipeline threads leaked after abandoned iteration"


def pytest_driver_train_epoch_propagates_loader_error():
    """A loader raising mid-collation (producer thread) must surface at the
    train_epoch caller, and the driver must stay usable afterwards."""
    ds = _dataset(np.random.default_rng(3))
    loader = GraphDataLoader(ds, batch_size=4, shuffle=False)
    loader.set_head_spec(("graph",), (1,))
    driver = _driver_for(loader)

    class FlakyLoader:
        def __iter__(self):
            for i, b in enumerate(loader):
                if i == 2:
                    raise RuntimeError("loader died")
                yield b

    with pytest.raises(RuntimeError, match="loader died"):
        driver.train_epoch(FlakyLoader())
    loss, _ = driver.train_epoch(loader)  # clean epoch still trains
    assert np.isfinite(loss)


# --------------------------------------- generation counters / cache staleness
def pytest_scan_cache_generation_invalidation(monkeypatch):
    ds = _dataset(np.random.default_rng(4))
    loader = GraphDataLoader(ds, batch_size=4, shuffle=True, reshuffle="batch")
    loader.set_head_spec(("graph",), (1,))
    driver = _driver_for(loader)

    calls = {"n": 0}
    real_iter = GraphDataLoader.__iter__

    def counting(self):
        calls["n"] += 1
        return real_iter(self)

    monkeypatch.setattr(GraphDataLoader, "__iter__", counting)
    loader.set_epoch(0)
    driver.train_epoch(loader)
    entry = driver._scan_cache[id(loader)]
    assert entry["chunks"] is not None
    assert entry["generation"] == loader.generation
    loader.set_epoch(1)
    driver.train_epoch(loader)
    assert calls["n"] == 1  # steady epoch replayed the device cache

    # set_head_spec bumps the generation: the device cache baked the old
    # spec and must be treated as a miss (rebuilt from the loader).
    loader.set_head_spec(("graph",), (1,))
    loader.set_epoch(2)
    driver.train_epoch(loader)
    assert calls["n"] == 2, "stale device cache replayed after set_head_spec"
    assert driver._scan_cache[id(loader)]["generation"] == loader.generation


def pytest_eval_cache_generation_invalidation(monkeypatch):
    ds = _dataset(np.random.default_rng(5))
    train = GraphDataLoader(ds, batch_size=4, shuffle=True)
    train.set_head_spec(("graph",), (1,))
    ev = GraphDataLoader(ds, batch_size=4, shuffle=False)
    ev.set_head_spec(("graph",), (1,))
    driver = _driver_for(train)

    calls = {"n": 0}
    real_iter = GraphDataLoader.__iter__

    def counting(self):
        calls["n"] += 1
        return real_iter(self)

    monkeypatch.setattr(GraphDataLoader, "__iter__", counting)
    loss_a, _ = driver.evaluate(ev)
    assert calls["n"] == 1
    loss_b, _ = driver.evaluate(ev)
    assert calls["n"] == 1 and loss_a == loss_b  # cached replay

    ev.set_head_spec(("graph",), (1,))
    loss_c, _ = driver.evaluate(ev)
    assert calls["n"] == 2, "stale eval cache replayed after set_head_spec"
    assert driver._eval_cache[id(ev)]["generation"] == ev.generation
    assert np.isfinite(loss_c)


def pytest_driver_cache_skips_fixed_order_batch_loader():
    """shuffle=False + reshuffle='batch' takes the deterministic sample-mode
    plan (fixed order); the driver must NOT cache-and-permute it."""
    ds = _dataset(np.random.default_rng(6))
    loader = GraphDataLoader(
        ds, batch_size=4, shuffle=False, reshuffle="batch"
    )
    loader.set_head_spec(("graph",), (1,))
    driver = _driver_for(loader)
    driver.train_epoch(loader)
    assert id(loader) not in driver._scan_cache


# ------------------------------------------------ single-transfer cache build
def pytest_cache_build_single_transfer_per_chunk(monkeypatch):
    """The cache-building epoch must perform exactly ONE host->device
    transfer per chunk — the pipeline's device copy is fed to both the step
    and the cache sink (previously each chunk transferred twice)."""
    ds = _dataset(np.random.default_rng(7))
    loader = GraphDataLoader(ds, batch_size=4, shuffle=True, reshuffle="batch")
    loader.set_head_spec(("graph",), (1,))
    driver = _driver_for(loader)
    driver.scan_chunk = 3
    n_batches = len(loader)
    n_chunks = -(-n_batches // driver.scan_chunk)  # one shape bucket

    count = {"n": 0}
    real_put = jax.device_put

    def counting_put(x, *a, **k):
        # Count only BATCH payload transfers: jnp.asarray of small host
        # scalars/permutations also routes through jax.device_put internally.
        if isinstance(x, (GraphBatch, tuple)):
            count["n"] += 1
        return real_put(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", counting_put)
    loader.set_epoch(0)
    driver.train_epoch(loader)
    assert count["n"] == n_chunks, (
        f"cache build did {count['n']} transfers for {n_chunks} chunks"
    )
    assert driver._scan_cache[id(loader)]["chunks"] is not None
    # The pipeline's split instrumentation saw those same transfers.
    assert driver.feed_stats.h2d_transfers == n_chunks
    assert driver.feed_stats.h2d_bytes > 0
    assert driver.feed_stats.step_s > 0

    count["n"] = 0
    loader.set_epoch(1)
    driver.train_epoch(loader)
    assert count["n"] == 0, "steady cached epoch still transferred batches"


def pytest_eval_cache_build_single_transfer(monkeypatch):
    ds = _dataset(np.random.default_rng(8))
    train = GraphDataLoader(ds, batch_size=4, shuffle=True)
    train.set_head_spec(("graph",), (1,))
    ev = GraphDataLoader(ds, batch_size=4, shuffle=False)
    ev.set_head_spec(("graph",), (1,))
    driver = _driver_for(train)
    n_batches = len(ev)

    count = {"n": 0}
    real_put = jax.device_put

    def counting_put(x, *a, **k):
        if isinstance(x, (GraphBatch, tuple)):
            count["n"] += 1
        return real_put(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", counting_put)
    driver.evaluate(ev)
    assert count["n"] == n_batches
    count["n"] = 0
    driver.evaluate(ev)  # cached replay: zero transfers
    assert count["n"] == 0
