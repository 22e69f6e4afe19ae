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
    (non-scan) path."""

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


# ------------------------------------------------- the host's epoch timeline
class _PerStepProfiler(_ActiveProf):
    """Active for the whole run: every train epoch takes the per-step path."""

    def set_current_epoch(self, epoch):
        pass

    def stop(self):
        pass


def _timeline_run(path):
    """Four epochs of ``train_validate_test`` on ``path`` with collection on
    and a slow collation (50 ms a batch; a 512-graph batch costs the chip's
    host 8-11), so that an epoch is some 0.6 s here as it is seconds there:
    starting a feed's two threads, which no leaf covers, costs 1-2 ms a feed
    on this CPU. Returns (driver, collected spans)."""
    from hydragnn_tpu import telemetry
    from hydragnn_tpu.faults import FaultPlan
    from hydragnn_tpu.train.train_validate_test import train_validate_test

    telemetry.reset()
    telemetry.configure(collect=True)
    ds = _dataset(np.random.default_rng(5), count=64)

    def loader(graphs, shuffle):
        ld = GraphDataLoader(graphs, batch_size=4, shuffle=shuffle)
        ld.set_head_spec(("graph",), (1,))
        return ld

    train = loader(ds[:48], True)
    mesh = None
    if path == "mesh4":
        from hydragnn_tpu.parallel.distributed import make_mesh

        mesh = make_mesh(data_axis=4, graph_axis=1, devices=jax.devices()[:4])
    model = create_model("SAGE", 1, 8, (1,), ("graph",), HEADS, [1.0], 2)
    variables = init_model_variables(model, next(iter(train)))
    opt = select_optimizer("AdamW", 5e-3)
    driver = TrainingDriver(
        model, opt, create_train_state(model, variables, opt), mesh=mesh,
        fault_plan=FaultPlan("slow_collate:ms=50"),
    )
    try:
        train_validate_test(
            driver, train, loader(ds[48:56], False), loader(ds[56:], False), 4,
            profiler=_PerStepProfiler() if path == "per_step" else None,
        )
        spans = [r for r in telemetry.collected_records() if r["kind"] == "span"]
    finally:
        telemetry.reset()
    return driver, spans


@pytest.fixture(scope="module", params=["scan", "per_step", "mesh4"])
def timeline(request):
    return request.param, *_timeline_run(request.param)


def pytest_leaf_phases_cover_each_epoch(timeline):
    """The dispatching thread's epoch is PARTITIONED: the leaf phases
    (``EPOCH_LEAVES``) cover 95% of every ``epoch`` span's wall on the scan
    path, the per-step path and a mesh of four virtual devices, and each of
    them is a child of the epoch, its train epoch or an evaluation."""
    from hydragnn_tpu.train.train_validate_test import EPOCH_LEAVES

    path, _, spans = timeline
    epochs = [r for r in spans if r["name"] == "epoch"]
    assert [r["attrs"]["epoch"] for r in epochs] == [0, 1, 2, 3]
    by_id = {r["span_id"]: r for r in spans}
    for ep in epochs:
        lo, hi = ep["ts"], ep["ts"] + ep["dur_s"]
        leaves = [
            r for r in spans
            if r["name"] in EPOCH_LEAVES and r["thread"] == ep["thread"]
            and lo <= r["ts"] < hi
        ]
        assert {r["name"] for r in leaves} == set(EPOCH_LEAVES), path
        covered = sum(r["dur_s"] for r in leaves)
        assert covered >= 0.95 * ep["dur_s"], (path, ep["attrs"], covered, ep["dur_s"])
        for r in leaves:  # each hangs off this epoch, directly or by one container
            parent = by_id[r["parent_id"]]
            assert parent is ep or by_id[parent["parent_id"]] is ep, (path, r["name"])
        # The four cumulative jax/*_s counters as they stood at the opening.
        assert {"jax_trace_s", "jax_lower_s", "jax_compile_s", "jax_cache_load_s"} <= set(ep["attrs"])
    splits = [r["attrs"]["split"] for r in spans if r["name"] == "evaluate"]
    assert splits == ["val", "test"] * 4


def pytest_no_span_opens_inside_a_device_or_eval_step(timeline):
    """``trace_reduce`` books a program to the SHORTEST span open at its
    midpoint on the dispatching thread, so nothing may open inside
    ``device_step`` or ``eval_step`` there (a ``gc`` record is retroactive:
    no annotation, marked ``retro``)."""
    path, _, spans = timeline
    steps = {
        r["span_id"]: r for r in spans if r["name"] in ("device_step", "eval_step")
    }
    assert steps
    inside = [
        (r["name"], steps[r["parent_id"]]["name"]) for r in spans
        if r.get("parent_id") in steps and not r.get("retro")
    ]
    assert inside == [], path
    for r in spans:  # and none overlaps one in time on its thread either
        if r["name"] in ("device_step", "eval_step") or r.get("retro"):
            continue
        for s in steps.values():
            if s["thread"] == r["thread"]:
                assert not (s["ts"] < r["ts"] < s["ts"] + s["dur_s"]), (path, r["name"])


def pytest_feed_wait_is_credited_on_every_path(timeline):
    """``FeedStats.feed_wait_s`` is what the consumer really waited: non-zero
    under a slow collation on the scan path too (it read 0.0 there by
    construction), and equal to the ``feed_wait`` spans' seconds, which are
    its one clock. The scan path's first pull waits for the whole epoch's
    collation: no chunk is handed over before the loader is exhausted."""
    path, driver, spans = timeline
    last = [r for r in spans if r["name"] == "train_epoch"][-1]
    waits = [
        r for r in spans
        if r["name"] == "feed_wait" and r["parent_id"] == last["span_id"]
    ]
    # The driver's FeedStats were reset by the evaluations since: read the
    # train epoch's figure as the loop published it.
    assert waits and sum(r["dur_s"] for r in waits) > 0.05, path
    if path == "scan":
        collates = [
            r for r in spans
            if r["name"] == "collate" and r["parent_id"] == last["span_id"]
        ]
        assert len(collates) == 13  # 12 batches and the pull that ends them
        assert waits[0]["dur_s"] >= 0.9 * sum(r["dur_s"] for r in collates[:12])
        drains = [
            r for r in spans
            if r["name"] == "feed_drain" and r["parent_id"] == last["span_id"]
        ]
        assert len(drains) == 1


def pytest_scan_path_feed_wait_equals_its_spans():
    from hydragnn_tpu import telemetry
    from hydragnn_tpu.faults import FaultPlan

    telemetry.reset()
    telemetry.configure(collect=True)
    try:
        ds = _dataset(np.random.default_rng(2))
        loader = GraphDataLoader(ds, batch_size=4, shuffle=True)
        loader.set_head_spec(("graph",), (1,))
        driver = _driver_for(loader)
        driver.fault_plan = FaultPlan("slow_collate@3:ms=60")
        driver.train_epoch(loader)
        stats = driver.feed_stats.as_dict()
        spans = [r for r in telemetry.collected_records() if r["kind"] == "span"]
        waits = [r["dur_s"] for r in spans if r["name"] == "feed_wait"]
        steps = [r["dur_s"] for r in spans if r["name"] == "device_step"]
        assert stats["feed_wait_s"] >= 0.06
        assert stats["feed_wait_s"] == pytest.approx(sum(waits), abs=1e-3)
        assert stats["step_s"] == pytest.approx(sum(steps), abs=1e-3)
        # The totals are the same seconds again, with collection on or off.
        totals = telemetry.span_totals()
        assert totals["feed_wait"] == pytest.approx(sum(waits), abs=1e-9)
        assert totals["device_step"] == pytest.approx(sum(steps), abs=1e-9)
    finally:
        telemetry.reset()
