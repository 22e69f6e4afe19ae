"""The host-side readers (``graftbench/host_phases.py`` and the eight
``layer_metrics`` files that read it): on hand-built span lists whose answers
are known, on the entries of ``BENCHMARK.json``, and on a tiny traced CPU
rehearsal (``tiny.py``; no number of it is a device number)."""

import importlib
import json
import os
import types

import pytest

import tiny
from graftbench import host_phases

NEW = (
    "host_phase_coverage", "first_batch_wait_ms", "eval_step_ms",
    "eval_span_share", "eval_feed_wait_share", "setup_init_span_s",
    "setup_trace_lower_s", "setup_cache_load_s",
)
T0 = 1_000.0


class Spans:
    """A span list built by hand: ``add`` returns the new record's id."""

    def __init__(self):
        self.rows = []

    def add(self, name, start, dur, parent=None, thread="MainThread", **extra):
        rec = dict(
            kind="span", name=name, ts=T0 + start, dur_s=dur, thread=thread,
            span_id=f"s{len(self.rows):04d}", parent_id=parent,
        )
        attrs = extra.pop("attrs", None)
        if attrs:
            rec["attrs"] = attrs
        rec.update(extra)
        self.rows.append(rec)
        return rec["span_id"]


def _known():
    """A 10 s window of two 5 s epochs on the main thread. An epoch: head
    0.1, train epoch 3.4 (waits 0.4 and 0.1, two steps of 1.4, drain 0.1),
    two evaluations of 0.5 (a wait of 0.1 and two steps of 0.2 each), tail
    0.2, and 0.3 s under no leaf. So coverage is 94%."""
    s = Spans()
    s.add("warm_up.device_step", -5.0, 1.0)  # before the window: not counted
    w = s.add("graftbench.window", 0.0, 10.0)
    for e in (0, 1):
        t = 5.0 * e
        outer = s.add("graftbench.epoch", t, 5.0, w)
        ep = s.add("epoch", t, 5.0, outer, attrs=dict(
            epoch=e, jax_trace_s=3.0 + e, jax_lower_s=2.0 + e,
            jax_compile_s=20.0, jax_cache_load_s=7.5 + e,
        ))
        s.add("epoch_head", t, 0.1, ep)
        tr = s.add("train_epoch", t + 0.1, 3.4, ep)
        s.add("feed_wait", t + 0.1, 0.4 + 0.2 * e, tr)
        step = s.add("device_step", t + 0.5 + 0.2 * e, 1.4 - 0.2 * e, tr)
        s.add("gc", t + 1.0, 0.05, step, retro=True)  # nobody's child phase
        s.add("feed_wait", t + 1.9, 0.1, tr)
        s.add("device_step", t + 2.0, 1.4, tr)
        s.add("feed_drain", t + 3.4, 0.1, tr)
        s.add("collate", t + 0.1, 0.4, tr, thread="hydragnn-prefetch")
        for k, split in enumerate(("val", "test")):
            ev = s.add("evaluate", t + 3.5 + 0.5 * k, 0.5, ep, attrs=dict(split=split))
            s.add("feed_wait", t + 3.5 + 0.5 * k, 0.1, ev)
            s.add("eval_step", t + 3.6 + 0.5 * k, 0.2, ev)
            s.add("eval_step", t + 3.8 + 0.5 * k, 0.2, ev)
        s.add("epoch_tail", t + 4.5, 0.2, ep)
    return s.rows


def _run(spans, eval_seconds=0.96):
    return types.SimpleNamespace(
        spans=spans, trace={"by_span": {"eval_step": {"runs": 8, "seconds": eval_seconds}}},
        facts={}, setup={},
    )


def _read(name, run):
    return importlib.import_module(f"graftbench.layer_metrics.{name}").read(run)


def pytest_timeline_is_cut_to_the_window_and_sorted_by_thread():
    spans = _known()
    threads = host_phases.by_thread(spans)
    assert set(threads) == {"MainThread", "hydragnn-prefetch"}
    names = [r["name"] for r in threads["MainThread"]]
    assert "warm_up.device_step" not in names and "graftbench.window" not in names
    assert "gc" not in names  # retroactive: never open on the thread
    assert len(host_phases.dispatching(spans, "epoch")) == 2
    assert host_phases.seconds(host_phases.dispatching(spans, "evaluate")) == pytest.approx(2.0)
    assert host_phases.by_thread([r for r in spans if r["name"] != "graftbench.window"]) == {}


def pytest_each_reader_on_the_known_list(monkeypatch):
    run = _run(_known())
    assert _read("host_phase_coverage", run) == pytest.approx(94.0)
    # First wait of each train epoch: 0.4 and 0.6 s.
    assert _read("first_batch_wait_ms", run) == pytest.approx(500.0)
    # 0.96 device seconds over the window's 8 eval_step spans.
    assert _read("eval_step_ms", run) == pytest.approx(120.0)
    assert _read("eval_span_share", run) == pytest.approx(100.0 * 2.0 / 10.0)
    assert _read("eval_feed_wait_share", run) == pytest.approx(100.0 * 0.4 / 2.0)
    # The counters as they stood when the window's FIRST epoch opened.
    assert _read("setup_trace_lower_s", run) == pytest.approx(5.0)
    assert _read("setup_cache_load_s", run) == pytest.approx(7.5)
    from hydragnn_tpu import telemetry

    monkeypatch.setattr(
        telemetry, "counters_snapshot",
        lambda prefix="": {"span_s/setup.init_variables": 21.5,
                           "span_s/setup.create_state": 1.25,
                           "span_s/setup.driver": 0.5},
    )
    assert _read("setup_init_span_s", run) == pytest.approx(22.75)


def pytest_coverage_of_a_list_with_a_30_percent_hole_reads_70():
    s = Spans()
    w = s.add("graftbench.window", 0.0, 10.0)
    ep = s.add("epoch", 0.0, 10.0, w)
    tr = s.add("train_epoch", 0.0, 6.0, ep)
    s.add("device_step", 0.0, 4.0, tr)
    s.add("feed_drain", 5.0, 1.0, tr)  # 1 s of the train epoch under no leaf
    s.add("epoch_tail", 8.0, 2.0, ep)  # and 2 s of the epoch
    assert _read("host_phase_coverage", _run(s.rows)) == pytest.approx(70.0)
    # A leaf that runs past the window's end counts up to it only.
    s.rows[-1]["dur_s"] = 5.0
    assert _read("host_phase_coverage", _run(s.rows)) == pytest.approx(70.0)


def pytest_readers_return_nothing_for_a_program_without_the_spans(monkeypatch):
    """The parent of PR 35 opens ``train_epoch``, ``device_step``,
    ``evaluate``, ``eval_step`` (no ``epoch``, no ``feed_wait`` on the scan
    path, no ``setup.*`` totals): no reader raises, each leaves its metric
    out, except ``eval_step_ms``, whose two sources that program has."""
    s = Spans()
    w = s.add("graftbench.window", 0.0, 4.0)
    outer = s.add("graftbench.epoch", 0.0, 4.0, w)
    tr = s.add("train_epoch", 0.0, 3.0, outer)
    s.add("device_step", 0.5, 2.5, tr)
    ev = s.add("evaluate", 3.0, 1.0, outer)
    s.add("eval_step", 3.0, 0.9, ev)
    from hydragnn_tpu import telemetry

    monkeypatch.setattr(telemetry, "counters_snapshot", lambda prefix="": {})
    run = _run(s.rows, eval_seconds=0.5)
    read = {name: _read(name, run) for name in NEW}
    assert read.pop("eval_step_ms") == pytest.approx(500.0)
    assert set(read.values()) == {None}
    empty = types.SimpleNamespace(spans=[], trace={"by_span": {}}, facts={}, setup={})
    assert {_read(name, empty) for name in NEW} == {None}


def pytest_new_entries_have_readers_and_name_cells_that_exist():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(entries)
    for name in NEW:
        m = entries[name]
        assert os.path.exists(
            os.path.join(tiny.BENCH_DIR, "layer_metrics", name + ".py")
        ), name
        assert m["workloads"] and set(m["workloads"]) <= cells, name
        assert m["moves"] in e2e
        # Every cell named reports the end-to-end metric the entry moves.
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells)), name
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert entries["eval_feed_wait_share"]["workloads"] == four


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_tiny")))
    name = tiny.cell(root, "train_epochs", model="GAT")
    rc, line, text = tiny.run_cell(root, name, seconds=0.5, trace=1)
    assert rc == 0 and line["correct"], text[-3000:]
    return line


def pytest_tiny_traced_rehearsal_reports_the_host_metrics(traced):
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    # No device plane on a CPU, no feed under a one-chip evaluation.
    assert not {"eval_step_ms", "eval_feed_wait_share"} & set(m)
    assert set(NEW) - {"eval_step_ms", "eval_feed_wait_share"} <= set(m)
    assert 80.0 < m["host_phase_coverage"] <= 100.0
    assert m["first_batch_wait_ms"] > 0.0
    # The scan path's wait is credited now: the share is what was waited.
    assert m["feed_wait_share"] > 0.0
    assert abs(m["eval_span_share"] - m["eval_wall_share"]) < 5.0
    assert abs(m["setup_init_span_s"] - m["setup_init_s"]) < 0.5
    assert m["setup_trace_lower_s"] > 0.0 and m["setup_cache_load_s"] == 0.0
