"""Observability + postprocess units: timers (reference time_utils.py:22-138),
the epoch-targeted profiler window (profile.py:9-68), denormalization
(postprocess.py:13-54), and verbosity-gated printing (print_utils.py:20-103)."""

import os
import time

import numpy as np
import pytest

from hydragnn_tpu.postprocess.postprocess import (
    output_denormalize,
    unscale_features_by_num_nodes,
    unscale_features_by_num_nodes_config,
)
from hydragnn_tpu.utils.print_utils import iterate_tqdm, print_distributed
from hydragnn_tpu.utils.profile import Profiler
from hydragnn_tpu.utils.time_utils import Timer, reduce_timers


def pytest_timer_accumulates_and_reduces():
    Timer.reset()
    t = Timer("unit_phase")
    t.start()
    time.sleep(0.01)
    t.stop()
    with Timer("unit_phase"):
        time.sleep(0.01)
    stats = reduce_timers()
    assert "unit_phase" in stats
    assert stats["unit_phase"]["min"] >= 0.02
    assert stats["unit_phase"]["min"] == stats["unit_phase"]["max"]  # 1 process
    Timer.reset()
    assert reduce_timers() == {}


def pytest_timer_credit_external_seconds():
    """Timer.credit folds seconds measured off the main thread (the input
    pipeline's H2D transfer thread) into the same registry print_timers
    reports from."""
    Timer.reset()
    Timer.credit("h2d_transfer", 0.25)
    Timer.credit("h2d_transfer", 0.75)
    Timer.credit("noop", 0.0)  # zero/negative credits are dropped
    Timer.credit("noop", -1.0)
    stats = reduce_timers()
    assert stats["h2d_transfer"]["max"] == pytest.approx(1.0)
    assert "noop" not in stats
    Timer.reset()


def pytest_timer_misuse_raises():
    t = Timer("misuse")
    with pytest.raises(RuntimeError):
        t.stop()
    t.start()
    with pytest.raises(RuntimeError):
        t.start()
    t.stop()


def pytest_profiler_epoch_window(tmp_path):
    # active: 0 = whole-epoch trace window (pre-schedule behavior).
    prof = Profiler(str(tmp_path))
    prof.setup({"enable": 1, "target_epoch": 1, "active": 0})
    assert prof.enabled and not prof.active
    prof.set_current_epoch(0)
    assert not prof.active
    from hydragnn_tpu import telemetry

    assert not telemetry.jax_annotations()
    prof.set_current_epoch(1)
    assert prof.active
    # While the trace is open graftel's TraceAnnotation bridge is on, and an
    # annotation IS a graftel span (one path, docs/OBSERVABILITY.md).
    assert telemetry.jax_annotations()
    with prof.annotate("span") as sp:
        pass
    assert isinstance(sp, telemetry.span)
    prof.set_current_epoch(2)  # window closes
    assert not prof.active
    assert not telemetry.jax_annotations()
    assert os.path.isdir(prof.trace_dir)
    # trace files actually written
    found = any(files for _, _, files in os.walk(prof.trace_dir))
    assert found, "no profiler trace output"


def pytest_profiler_step_schedule(tmp_path, monkeypatch):
    """wait=1/warmup=1/active=3 (the reference's torch.profiler schedule,
    profile.py:23): trace opens after wait+warmup steps, captures exactly
    ``active`` steps, then closes — all within the target epoch."""
    events = []
    monkeypatch.setattr(
        "jax.profiler.start_trace", lambda d: events.append("start")
    )
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: events.append("stop")
    )
    prof = Profiler(str(tmp_path))
    prof.setup(
        {"enable": 1, "target_epoch": 0, "wait": 1, "warmup": 1, "active": 3}
    )
    prof.set_current_epoch(0)
    transitions = {}
    for i in range(8):
        prof.step()
        transitions[i + 1] = tuple(events)
    assert transitions[1] == ()  # wait
    assert transitions[2] == ("start",)  # trace opens after wait+warmup
    assert transitions[4] == ("start",)  # active steps 3,4,5 captured
    assert transitions[5] == ("start", "stop")  # closes after 3 active steps
    assert transitions[8] == ("start", "stop")  # no re-open
    prof.set_current_epoch(1)
    assert events == ["start", "stop"]


def pytest_profiler_bridge_restored_to_what_it_was(tmp_path, monkeypatch):
    """The profiler switches graftel's annotation bridge on for its trace
    and puts back what it found: off after a plain run, still on where the
    caller (graftbench's traced run) had it on already."""
    from hydragnn_tpu import telemetry

    monkeypatch.setattr("jax.profiler.start_trace", lambda d: None)
    monkeypatch.setattr("jax.profiler.stop_trace", lambda: None)
    for before in (False, True):
        telemetry.configure(jax_annotations=before)
        prof = Profiler(str(tmp_path))
        prof.setup({"enable": 1, "target_epoch": 0, "active": 0})
        prof.set_current_epoch(0)
        assert telemetry.jax_annotations()
        prof.stop()
        assert telemetry.jax_annotations() is before
    telemetry.configure(jax_annotations=False)


def pytest_profiler_spans_in_trace(tmp_path):
    """Drive a real train epoch and an evaluation under a "Profile"-armed
    profiler and assert that the program's graftel spans are host events of
    the written trace, under the names every other reader uses: the ONE
    annotation path (the loop opens no TraceAnnotation of its own)."""
    import jax
    import numpy as np

    from hydragnn_tpu.graphs import GraphSample, collate_graphs
    from hydragnn_tpu.models import create_model, init_model_variables
    from hydragnn_tpu.preprocess.dataloader import GraphDataLoader
    from hydragnn_tpu.train.train_validate_test import TrainingDriver
    from hydragnn_tpu.train.trainer import create_train_state
    from hydragnn_tpu.utils.optimizer import select_optimizer

    rng = np.random.default_rng(0)
    samples = []
    for _ in range(8):
        n = 6
        x = rng.normal(size=(n, 1)).astype(np.float32)
        senders = np.repeat(np.arange(n), 2)
        receivers = (senders + 1 + np.arange(senders.size) % (n - 1)) % n
        samples.append(
            GraphSample(
                x=x,
                pos=rng.random((n, 3)).astype(np.float32),
                y=np.array([x.sum()], np.float32),
                y_loc=np.array([[0, 1]], np.int64),
                edge_index=np.stack([senders, receivers]).astype(np.int64),
            )
        )
    loader = GraphDataLoader(samples, batch_size=4, shuffle=False)
    loader.set_head_spec(("graph",), (1,))
    heads = {
        "graph": {
            "num_sharedlayers": 1,
            "dim_sharedlayers": 4,
            "num_headlayers": 1,
            "dim_headlayers": [4],
        }
    }
    model = create_model("SAGE", 1, 8, (1,), ("graph",), heads, [1.0], 2)
    batch = next(iter(loader))
    variables = init_model_variables(model, batch)
    opt = select_optimizer("AdamW", 1e-3)
    state = create_train_state(model, variables, opt)
    driver = TrainingDriver(model, opt, state)

    # Whole-epoch window (active: 0) keeps the trace open across the eval
    # pass too, so all three span names must land in the written trace.
    prof = Profiler(str(tmp_path))
    prof.setup({"enable": 1, "target_epoch": 0, "active": 0})
    prof.set_current_epoch(0)
    from hydragnn_tpu import telemetry

    driver.train_epoch(loader, prof)
    driver.evaluate(loader)
    prof.stop()
    assert not telemetry.jax_annotations(), "bridge left on after stop()"

    from graftbench import xplane_scopes
    from graftbench.trace_reduce import find_xplane

    names = {
        name for name, _, _ in
        xplane_scopes.host_events(xplane_scopes.parse(find_xplane(prof.trace_dir)))
    }
    wanted = {
        "train_epoch", "collate", "h2d", "feed_wait", "device_step",
        "evaluate", "eval_step",
    }
    assert wanted <= names, f"missing from the trace: {wanted - names}"
    # The second annotation path is gone: its names are not in the trace.
    assert not {"train_step", "feed"} & names


def pytest_profiler_disabled_noop(tmp_path):
    prof = Profiler(str(tmp_path))
    prof.setup(None)
    prof.set_current_epoch(0)
    assert not prof.active and not prof.enabled


def pytest_output_denormalize_roundtrip():
    rng = np.random.default_rng(0)
    raw_t = [rng.random((10, 1)) * 7 - 3, rng.random((20, 1)) * 2]
    raw_p = [v + 0.1 for v in raw_t]
    y_minmax = [
        [np.array([-3.0]), np.array([4.0])],
        [np.array([0.0]), np.array([2.0])],
    ]
    norm_t = [
        (v - mm[0]) / (mm[1] - mm[0]) for v, mm in zip(raw_t, y_minmax)
    ]
    norm_p = [
        (v - mm[0]) / (mm[1] - mm[0]) for v, mm in zip(raw_p, y_minmax)
    ]
    got_t, got_p = output_denormalize(y_minmax, norm_t, norm_p)
    for g, r in zip(got_t, raw_t):
        np.testing.assert_allclose(g, r, rtol=1e-12)
    for g, r in zip(got_p, raw_p):
        np.testing.assert_allclose(g, r, rtol=1e-12)


def pytest_unscale_by_num_nodes():
    nodes = [2, 4]
    heads = [np.array([[1.0], [1.0]]), np.array([[3.0], [5.0]])]
    (out,) = unscale_features_by_num_nodes([heads], [1], nodes)
    np.testing.assert_allclose(out[0], [[1.0], [1.0]])  # untouched head
    np.testing.assert_allclose(out[1], [[6.0], [20.0]])  # scaled by node count

    config = {
        "NeuralNetwork": {
            "Variables_of_interest": {
                "output_names": ["energy", "mag_scaled_num_nodes"],
                "denormalize_output": True,
            }
        }
    }
    heads2 = [np.array([[1.0], [1.0]]), np.array([[3.0], [5.0]])]
    (out2,) = unscale_features_by_num_nodes_config(config, [heads2], nodes)
    np.testing.assert_allclose(out2[1], [[6.0], [20.0]])


def pytest_unscale_requires_denormalize():
    config = {
        "NeuralNetwork": {
            "Variables_of_interest": {
                "output_names": ["mag_scaled_num_nodes"],
                "denormalize_output": False,
            }
        }
    }
    with pytest.raises(AssertionError):
        unscale_features_by_num_nodes_config(
            config, [[np.array([[1.0]])]], [2]
        )


def pytest_verbosity_gating(capsys):
    print_distributed(0, "hidden")
    assert capsys.readouterr().out == ""
    print_distributed(2, "shown")
    assert "shown" in capsys.readouterr().out
    # iterate_tqdm passes items through at any verbosity
    assert list(iterate_tqdm(range(3), 0)) == [0, 1, 2]
    assert list(iterate_tqdm(range(3), 2)) == [0, 1, 2]


def pytest_prefetcher_sentinel_not_dropped_when_queue_full():
    """Regression: the producer used put_nowait for the end-of-iteration
    sentinel; with >= depth items queued and a slow consumer the sentinel hit
    queue.Full and was silently dropped, leaving the consumer blocked on
    get() forever (reproduced via run_training with 8 train batches)."""
    import threading
    import time as _time

    from hydragnn_tpu.train.train_validate_test import _Prefetcher

    pf = _Prefetcher(iter(range(6)), depth=2)
    _time.sleep(0.3)  # producer fills the queue and finishes its iterable
    got = []
    t = threading.Thread(target=lambda: got.extend(pf), daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "consumer deadlocked waiting for sentinel"
    assert got == list(range(6))
