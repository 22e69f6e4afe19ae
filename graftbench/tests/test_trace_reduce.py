"""``trace_reduce.py`` on ``testdata/small_v5e.xplane.pb``, a trace recorded
on one TPU v5 lite chip by ``testdata/record.py``: three runs of one program,
each followed by a 20 ms sleep under a ``host_pause`` annotation. The numbers
asserted here were read off the trace's events by hand (PR 22)."""

import os
import types

import pytest

from graftbench import trace_reduce

TRACE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "testdata", "small_v5e.xplane.pb",
)
SPANS = ("device_step", "host_pause")


@pytest.fixture(scope="module")
def data():
    return trace_reduce.load(TRACE)


def pytest_whole_trace_three_runs_of_the_named_program(data):
    r = trace_reduce.reduce(data, SPANS, use_window=False)
    assert r["chips"] == 1
    # XLA Modules: jit_step ran for 361,827 + 361,895 + 361,871 ns.
    assert r["programs"]["jit_step"]["runs"] == 3
    assert r["programs"]["jit_step"]["seconds"] == pytest.approx(1_085_593e-9, rel=1e-6)
    # Its last fusion, f32[2048,2048]: 91,882 + 91,951 + 91,927 ns.
    top = dict(r["top_ops"])
    assert top["fusion f32[2048,2048]"] == pytest.approx(275_760e-9, rel=1e-3)
    assert list(top)[0] == "fusion f32[2048,2048]"
    # Busy is the union of the operations, a little under the programs.
    assert 1.07e-3 < r["busy_s"] < 1.0856e-3
    assert r["collective_s"] == 0.0 and r["collective_exposed_s"] == 0.0


def pytest_window_busy_plus_idle_is_the_window(data):
    r = trace_reduce.reduce(data, SPANS)
    # The host's graftbench.window annotation lasted 65,412,836 ns.
    assert r["window_s"] == pytest.approx(65_412_836e-9, rel=1e-9)
    idle = sum(seconds for _, seconds in r["idle_gaps"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"], rel=1e-9)
    assert r["idle_share_worst"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    # On the trace's clocks the first run began 0.9 ms before the window's
    # annotation (the device's clock leads the host's by about that), so two
    # of the three runs lie inside it.
    assert r["programs"]["jit_step"]["runs"] == 2
    assert r["busy_s"] == pytest.approx(723_738e-9, rel=1e-6)
    # The chip idles while the host sleeps: the gaps go to that span.
    assert r["idle_gaps"][0][0] == "host_pause"
    assert r["idle_gaps"][0][1] > 0.06


def pytest_short_names_and_nesting():
    assert trace_reduce.short_op(
        "%fusion.987 = f32[524288,256]{1,0:T(8,128)} fusion(f32[32768,256]{1,0} %x)"
    ) == "fusion.987 f32[524288,256]"
    assert trace_reduce.short_op("while.3") == "while.3"
    # A container's self time is its own less its children's.
    rows = trace_reduce._self_times(
        [("while", 0, 100), ("a", 10, 40), ("b", 50, 90), ("c", 120, 130)]
    )
    assert {n: s for n, s, _, _ in rows} == {"while": 30, "a": 30, "b": 40, "c": 10}


def _plane(name, lines):
    """A stand-in for ``ProfileData``'s planes: {line name: [(event name,
    start ns, duration ns)]}; a list of pairs where line names repeat."""
    rows = lines.items() if isinstance(lines, dict) else lines
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=line, events=[
            types.SimpleNamespace(name=n, start_ns=a, duration_ns=d)
            for n, a, d in events
        ])
        for line, events in rows
    ])


def pytest_a_program_belongs_to_the_dispatching_threads_span():
    # Two 180 ms train steps under device_step on the thread that holds the
    # window; on the feed's thread (also a line named "python3") a 20 ms
    # collate covers the middle of the second. The shorter span of the other
    # thread must not take the program; an idle gap may go to it.
    ms = 1_000_000
    pd = types.SimpleNamespace(planes=[
        _plane("/device:TPU:0", {
            "XLA Modules": [("jit_step(1)", 10 * ms, 180 * ms),
                            ("jit_step(1)", 200 * ms, 180 * ms)],
            "XLA Ops": [("%fusion.1 = f32[8]{0} fusion()", 10 * ms, 180 * ms),
                        ("%fusion.1 = f32[8]{0} fusion()", 200 * ms, 180 * ms)],
        }),
        _plane("/host:CPU", [
            ("python3", [("graftbench.window", 0, 400 * ms),
                         ("device_step", 5 * ms, 187 * ms),
                         ("device_step", 195 * ms, 187 * ms)]),
            ("python3", [("collate", 280 * ms, 20 * ms),
                         ("collate", 385 * ms, 14 * ms)]),
        ]),
    ])
    r = trace_reduce.reduce(pd, ("device_step", "collate"))
    assert r["by_span"] == {
        "device_step": {"runs": 2.0, "seconds": pytest.approx(0.36)}
    }
    gaps = dict(r["idle_gaps"])
    # 380-400 ms: no device_step open, the feed thread collating.
    assert gaps["collate"] == pytest.approx(0.02)
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(r["window_s"])
