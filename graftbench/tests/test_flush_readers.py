"""The readers of the time between two device programs: the engine's
``serve/flush`` records and the real spans beside them (six ``serve_*``
readers) and the train loop's ``dispatch_s`` attribute (two readers). On hand-built
span lists whose answers are known, on a program without the records (the
parent), on the entries of ``BENCHMARK.json``, and on tiny traced CPU
rehearsals (``tiny.py``; no number of them is a device number)."""

import importlib
import json
import os
import types

import pytest

import tiny
from test_host_phases import T0, Spans

SERVE = (
    "serve_turnaround_ms_per_flush", "serve_await_ms_per_flush",
    "serve_d2h_ms_per_flush", "serve_resolve_ms_per_flush",
    "serve_handoff_ms_per_flush", "serve_cycle_coverage",
)
TRAIN = ("dispatch_ms_per_chunk", "eval_dispatch_ms_per_step")
MARKS = (
    "first_queued", "taken", "collated", "h2d_start", "h2d_end", "exec_start",
    "launch_start", "launch_end", "ready", "d2h_end", "resolved",
)


def _read(name, run):
    return importlib.import_module(f"graftbench.layer_metrics.{name}").read(run)


def _run(spans):
    return types.SimpleNamespace(spans=spans, trace={}, facts={}, setup={})


def _flush(s, flush_id, start, offsets, previous=None, spans=True):
    """One ``serve/flush`` record starting ``start`` s into the window
    (``t0`` on the marks' clock is the same number): ``offsets`` are the
    eleven marks from that start, ``previous`` the (start, offsets) of the
    flush before it, from which the engine's two derived seconds follow.
    With it (``spans``) the real spans the engine's threads open round those
    marks: ``serve/await`` from the last replies' being set to the first
    request, ``fill``, ``collate``, ``h2d``, ``device`` (the dispatcher has
    the work -> the copy to the host is done) and ``resolve``; the two queues
    of the feed lie under none of them."""
    marks = dict(zip(MARKS, offsets))
    waited = turnaround = None
    if previous is not None:
        p_start, p = previous[0], dict(zip(MARKS, previous[1]))
        waited = max(start - (p_start + p["resolved"]), 0.0)
        turnaround = max(start + marks["launch_end"] - (p_start + p["ready"]), 0.0)
    s.add(
        "serve/flush", start, marks["resolved"], thread="hydragnn-serve-dispatch",
        retro=True, attrs=dict(
            flush_id=flush_id, rung="32x64", requests=4, t0=500.0 + start,
            marks=marks, await_s=waited, turnaround_s=turnaround,
        ),
    )
    if spans:
        if waited:
            s.add("serve/await", start - waited, waited, thread="hydragnn-serve-batch",
                  attrs=dict(flush_id=flush_id))
        for name, a, b, thread in (
            ("fill", "first_queued", "taken", "hydragnn-serve-batch"),
            ("collate", "taken", "collated", "hydragnn-serve-batch"),
            ("h2d", "h2d_start", "h2d_end", "hydragnn-transfer"),
            ("device", "exec_start", "d2h_end", "hydragnn-serve-dispatch"),
            ("resolve", "d2h_end", "resolved", "hydragnn-serve-dispatch"),
        ):
            s.add("serve/" + name, start + marks[a], marks[b] - marks[a], thread=thread,
                  attrs=dict(flush_id=flush_id))
    return start, offsets


# One flush of a SERIAL cycle, in ms from its first request's queueing: fill
# 4, collate 5, a 1 ms queue, h2d 3, a 2 ms queue, lookup 1, launch 2, the
# forward 180, d2h 6, resolve 8.
SERIAL = (0.0, 0.004, 0.009, 0.010, 0.013, 0.015, 0.016, 0.018, 0.198, 0.204, 0.212)


def _serial(cycles=4, turn_s=0.020, spans=True):
    """Flushes whose first request queues ``turn_s`` after the last one's
    replies were set (the callers' turn)."""
    s = Spans()
    s.add("graftbench.window", 0.0, 10.0)
    previous, start = None, 0.1
    for k in range(cycles):
        previous = _flush(s, k + 1, start, SERIAL, previous, spans)
        start += SERIAL[-1] + turn_s
    return s.rows


def pytest_a_serial_cycle_reads_its_parts_and_the_queues_as_its_holes():
    run = _run(_serial())
    # ready(k-1) -> launch_end(k): d2h 6 + resolve 8 + the callers' 20 + fill
    # .. launch 18.
    assert _read("serve_turnaround_ms_per_flush", run) == pytest.approx(52.0)
    # await 20 on three of four flushes (the first has none) + fill 4.
    assert _read("serve_await_ms_per_flush", run) == pytest.approx(0.75 * 20.0 + 4.0)
    assert _read("serve_d2h_ms_per_flush", run) == pytest.approx(6.0)
    assert _read("serve_resolve_ms_per_flush", run) == pytest.approx(8.0)
    # The two queues 1 + 2, lookup 1, launch 2.
    assert _read("serve_handoff_ms_per_flush", run) == pytest.approx(6.0)
    # Every second of the 52 under a real span but the two queues' 1 + 2.
    assert _read("serve_cycle_coverage", run) == pytest.approx(100.0 * 49.0 / 52.0)


def pytest_an_overlapped_cycle_clips_its_spans_to_the_turnaround():
    """An open loop: flush k+1 is collated and transferred WHILE flush k
    runs, and waits in the dispatcher's queue. Its spans before the earlier
    ``ready`` are outside the turnaround; the earlier flush's ``device`` and
    ``resolve`` and its own ``device`` cover all of it: 100, never more."""
    s = Spans()
    s.add("graftbench.window", 0.0, 10.0)
    first = _flush(s, 1, 0.1, SERIAL)
    # Queued 50 ms after flush 1, collated and on the device by 65 ms; the
    # dispatcher takes it when flush 1 has resolved (212 ms): 162 ms from its
    # own start. Lookup 1, launch 2.
    late = (0.0, 0.004, 0.009, 0.010, 0.013, 0.162, 0.163, 0.165, 0.345, 0.351, 0.359)
    _flush(s, 2, 0.15, late, first)
    run = _run(s.rows)
    # ready(1) = 0.298 on the window's clock, launch_end(2) = 0.315.
    assert _read("serve_turnaround_ms_per_flush", run) == pytest.approx(17.0)
    assert _read("serve_cycle_coverage", run) == pytest.approx(100.0)
    assert _read("serve_await_ms_per_flush", run) == pytest.approx(4.0)  # no await: fill alone
    # The dispatcher's queue is the handoff: (1 + 149) / 2 flushes + 1 + 2 + the first's 3.
    assert _read("serve_handoff_ms_per_flush", run) == pytest.approx((1 + 2 + 1 + 149) / 2 + 3.0)


def pytest_a_missing_span_or_a_missing_record_lowers_the_coverage():
    rows = _serial(cycles=4)
    # No span round the demux (the parent's dispatcher): each turnaround's 8
    # ms of ``resolve`` lie open beside the queues' 3.
    bare = [r for r in rows if r["name"] != "serve/resolve"]
    assert _read("serve_cycle_coverage", _run(bare)) == pytest.approx(100.0 * 41.0 / 52.0)
    # Only the records, no real span at all: nothing is covered.
    assert _read("serve_cycle_coverage", _run(_serial(spans=False))) == pytest.approx(0.0)
    # Without flush 2's record, flush 3's turnaround has no earlier half:
    # 49 of the 104 ms that turned are covered.
    gone = [
        r for r in rows
        if not (r["name"] == "serve/flush" and r["attrs"]["flush_id"] == 2)
    ]
    assert _read("serve_cycle_coverage", _run(gone)) == pytest.approx(100.0 * 49.0 / 104.0)
    assert _read("serve_turnaround_ms_per_flush", _run(gone)) == pytest.approx(52.0)


def pytest_records_outside_the_window_are_not_read():
    rows = _serial(cycles=4)
    window = next(r for r in rows if r["name"] == "graftbench.window")
    window["ts"], window["dur_s"] = T0 + 0.3, 0.5  # flushes 2 and 3 begin inside
    run = _run(rows)
    assert _read("serve_turnaround_ms_per_flush", run) == pytest.approx(52.0)
    assert _read("serve_await_ms_per_flush", run) == pytest.approx(24.0)


def _train_spans(with_attrs=True):
    s = Spans()
    w = s.add("graftbench.window", 0.0, 10.0)
    for e in (0, 1):
        t = 5.0 * e
        attrs = dict(epoch=e)
        if with_attrs:
            attrs.update(run_delay_s=None, nivcsw=3)  # a host that counts no run delay
        ep = s.add("epoch", t, 5.0, w, attrs=attrs)
        tr = s.add("train_epoch", t, 4.0, ep)
        for k, dispatch in enumerate((0.002, 0.004)):
            step = dict(index=k, steps=4)
            if with_attrs:
                step.update(dispatch_s=dispatch, wait_s=1.9, run_delay_s=None, nivcsw=0)
            s.add("device_step", t + 2.0 * k, 1.9 + dispatch, tr, attrs=step)
        ev = s.add("evaluate", t + 4.0, 1.0, ep)
        s.add("eval_step", t + 4.0, 0.9, ev, attrs=dict(
            index=0, **(dict(dispatch_s=0.001 + 0.002 * e, wait_s=0.8) if with_attrs else {})
        ))
        # Another thread's record of the same name is not the dispatching one's.
        s.add("device_step", t, 1.0, tr, thread="other", attrs=dict(dispatch_s=9.0))
    return s.rows


def pytest_the_train_readers_on_a_known_list():
    run = _run(_train_spans())
    assert _read("dispatch_ms_per_chunk", run) == pytest.approx(3.0)
    assert _read("eval_dispatch_ms_per_step", run) == pytest.approx(2.0)


def pytest_readers_return_nothing_for_a_program_without_the_records():
    """The parent opens ``serve/collate``, ``serve/h2d``, ``serve/device``,
    ``device_step``, ``eval_step`` and ``epoch`` without the new attributes
    and writes no ``serve/flush``: no reader raises, each leaves its metric
    out of the line."""
    s = Spans()
    w = s.add("graftbench.window", 0.0, 4.0)
    for k in range(3):
        s.add("serve/collate", 1.0 * k, 0.005, w, thread="hydragnn-prefetch",
              attrs=dict(request_ids=["r-1"]))
        s.add("serve/device", 1.0 * k + 0.01, 0.2, w, thread="hydragnn-serve-dispatch",
              attrs=dict(request_ids=["r-1"]))
    assert {_read(name, _run(s.rows)) for name in SERVE} == {None}
    assert {_read(name, _run(_train_spans(with_attrs=False))) for name in TRAIN} == {None}
    empty = _run([])
    assert {_read(name, empty) for name in SERVE + TRAIN} == {None}
    # A window, records, and a single flush: parts read, nothing turns.
    one = _serial(cycles=1)
    assert _read("serve_turnaround_ms_per_flush", _run(one)) is None
    assert _read("serve_cycle_coverage", _run(one)) is None
    assert _read("serve_d2h_ms_per_flush", _run(one)) == pytest.approx(6.0)


def pytest_new_entries_have_readers_and_name_cells_that_exist():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    # Appended, in this order, after every entry the benchmark had.
    assert tuple(m["name"] for m in bench["per_layer"][-8:]) == SERVE + TRAIN
    for name in SERVE + TRAIN:
        m = entries[name]
        assert os.path.exists(os.path.join(tiny.BENCH_DIR, "layer_metrics", name + ".py"))
        assert m["source"] == "program_span"
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"]), name
        kind = ".serve_" if name in SERVE else ".train_"
        assert set(m["workloads"]) == {c for c in cells if kind in c}, name
        assert m["layer"] == ("serving" if name in SERVE else "step")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_copy(str(tmp_path_factory.mktemp("graftbench_flush")))


def pytest_a_traced_serving_rehearsal_prints_the_six(root):
    name = tiny.cell(root, "serve_closed")
    rc, line, text = tiny.run_cell(root, name, seconds=0.5, trace=1)
    assert rc == 0 and line["correct"], text[-3000:]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SERVE) <= set(m), sorted(m)
    assert 50.0 <= m["serve_cycle_coverage"] <= 100.0 + 1e-6
    # The closed loop's cycle on the host's clock: the turnaround holds the
    # callers' turn, the copy out, the demux and the way to the launch.
    parts = sum(m[k] for k in SERVE[1:5])
    assert 0.0 < parts <= m["serve_turnaround_ms_per_flush"] * 1.5 + 1.0
    assert m["serve_turnaround_ms_per_flush"] > m["serve_resolve_ms_per_flush"] > 0.0


def pytest_a_traced_training_rehearsal_prints_the_two(root):
    name = tiny.cell(root, "train_epochs", model="GAT")
    rc, line, text = tiny.run_cell(root, name, seconds=0.5, trace=1)
    assert rc == 0 and line["correct"], text[-3000:]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(TRAIN) <= set(m), sorted(m)
    assert m["dispatch_ms_per_chunk"] > 0.0 and m["eval_dispatch_ms_per_step"] > 0.0
    # No span was added under the steps: the leaves still cover the epoch.
    assert 80.0 < m["host_phase_coverage"] <= 100.0
