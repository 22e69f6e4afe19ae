"""From the profiler's ``.xplane.pb`` to numbers.

What a TPU trace looks like (looked at by hand, PR 22, see PERF.md): one
plane a chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event an
executed HLO operation (nested where an operation contains others: a
``while`` holds its body), named by the instruction's whole HLO text, and
whose line ``XLA Modules`` holds one event an executed program, named
``jit_<function>(<fingerprint>)``; the plane ``/host:CPU`` has one line a host
thread, and a ``jax.profiler.TraceAnnotation`` -- which is what a graftel span
becomes with ``telemetry.configure(jax_annotations=True)`` -- is an event of
that thread's line under the span's name. The events carry no
``jax.named_scope``: the ``hydragnn.*`` scopes of ``train/trainer.py`` are
nowhere in what ``ProfileData`` shows, and the train and the evaluation
step are both ``jit_step``. So a program is told by the host span it ran
under. The planes share one clock (``start_ns``) to about a millisecond: in
the recorded trace a program starts on the device's clock 0.9 ms before the
host's call to run it. That is not corrected; windows are seconds long.

The window is the host annotation ``graftbench.window``; every interval is
clipped to it. Reported:

* ``busy_s``: seconds in which an operation ran on the device (union of the
  ``XLA Ops`` intervals), averaged over the chips; ``idle_share`` per chip.
* ``programs``: executions and device seconds of each program by name, and
  ``by_span``: the same by the span open ON THE DISPATCHING THREAD (the host
  line that holds ``graftbench.window``) at the middle of each execution, the
  shortest such span: the program's ``device_step`` span holds a train step or
  scan chunk up to its blocking readback, ``eval_step`` an evaluation step. A
  span of another thread (the feed's ``collate``, 12-54 ms against a 181 ms
  step) says nothing about which program the device runs and is not looked
  at. Both tables are means over the chips.
* ``collective_s`` / ``collective_exposed_s``: seconds between the start and
  the end of each collective, and the part of them in which that chip ran
  nothing else (the self time of the collective's own events: the ops line is
  one stream, so while a ``-done`` or a synchronous collective is the running
  event, no compute is).
* ``top_ops``: operations by self time (a container's time less its
  children's), summed over chips, under the names XLA gives them.
* ``idle_gaps``: the idle seconds of the busiest-gapped chip, summed by the
  host span open at the middle of each gap (the shortest such span over all
  host threads: the most specific thing the host was doing).
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "graftbench.window"
SHORT_GAP_NS = 10_000
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
)
_HLO = re.compile(r"^%?([\w.\-]+) = (\S+?)(?:\{| )")


def find_xplane(trace_dir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and plane_name[12:].isdigit()


def _union(intervals):
    """Merged, sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _clip(a, b, lo, hi):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _self_times(events):
    """[(name, self seconds, start, end)] for nested events of one line."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [name, start, end, child seconds]

    def pop():
        name, a, b, child = stack.pop()
        out.append((name, max((b - a) - child, 0.0), a, b))
        if stack:
            stack[-1][3] += b - a

    for name, a, b in events:
        while stack and a >= stack[-1][2]:
            pop()
        stack.append([name, a, min(b, stack[-1][2]) if stack else b, 0.0])
    while stack:
        pop()
    return out


def short_op(name: str) -> str:
    """``%fusion.9 = f32[8,128]{1,0:T(8,128)} fusion(...)`` -> ``fusion.9
    f32[8,128]``: XLA's own name and result shape, without the operands."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def _innermost(spans, t):
    """Name of the shortest of ``spans`` [(name, start, end)] open at ``t``."""
    covering = [(e - s, n) for n, s, e in spans if s <= t < e]
    return min(covering)[1] if covering else "(no span open)"


def describe(pd) -> list:
    """Planes and lines with their event counts: for reading a trace by hand."""
    rows = []
    for plane in pd.planes:
        for line in plane.lines:
            events = list(line.events)
            rows.append((plane.name, line.name, len(events),
                         events[0].name if events else None))
    return rows


def reduce(pd, span_names=(), use_window: bool = True) -> dict:
    span_names = set(span_names) | {WINDOW}
    host = []  # (name, start, end, thread) of program spans on any host thread
    devices = {}  # plane name -> {"ops": [...], "modules": [...]}
    for plane in pd.planes:
        if _is_device(plane.name):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        d["ops"].append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        d["modules"].append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        )
        elif plane.name.startswith("/host:"):
            # Python's threads all come as lines named "python3": a thread is
            # its line's place in the plane, not its name.
            for number, line in enumerate(plane.lines):
                thread = (plane.name, number)
                for e in line.events:
                    if e.name in span_names:
                        host.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns, thread)
                        )
    windows = [h for h in host if h[0] == WINDOW]
    spans = sorted(h[:3] for h in host if h[0] != WINDOW)
    # The thread that opened the window is the one that dispatches programs.
    # A recorded trace without the annotation has one annotated thread.
    dispatching = {h[3] for h in windows}
    dispatch_spans = sorted(
        h[:3] for h in host
        if h[0] != WINDOW and (not dispatching or h[3] in dispatching)
    )
    if not use_window:
        windows = []
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    else:  # a recorded trace without the annotation: everything seen
        every = [
            t for d in devices.values() for _, a, b in d["ops"] + d["modules"]
            for t in (a, b)
        ]
        lo, hi = (min(every), max(every)) if every else (0.0, 0.0)
    window_s = (hi - lo) * 1e-9

    busy, op_self, programs, by_span = {}, {}, {}, {}
    coll_s = coll_exposed_s = 0.0
    gaps_by_chip = {}
    for name, d in sorted(devices.items()):
        clipped = [
            (n, *c) for n, a, b in d["ops"] if (c := _clip(a, b, lo, hi))
        ]
        merged = _union([(a, b) for _, a, b in clipped])
        busy[name] = sum(b - a for a, b in merged) * 1e-9
        edges = [lo] + [t for ab in merged for t in ab] + [hi]
        gaps_by_chip[name] = [
            (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]
        open_collectives = {}
        for op, self_s, a, b in _self_times(clipped):
            op_self[op] = op_self.get(op, 0.0) + self_s * 1e-9
            if op.lstrip("%").startswith(_COLLECTIVES):
                coll_exposed_s += self_s * 1e-9
                base = re.sub(r"-(start|done)(\.\d+)?$", r"\2", op)
                if "-start" in op:
                    open_collectives[base] = a
                elif "-done" in op and base in open_collectives:
                    coll_s += (b - open_collectives.pop(base)) * 1e-9
                else:
                    coll_s += (b - a) * 1e-9
        for n, a, b in d["modules"]:
            if not (c := _clip(a, b, lo, hi)):
                continue
            seconds = (c[1] - c[0]) * 1e-9
            for table, key in (
                (programs, re.sub(r"\(\d+\)$", "", n)),
                (by_span, _innermost(dispatch_spans, (c[0] + c[1]) / 2)),
            ):
                row = table.setdefault(key, {"runs": 0, "seconds": 0.0})
                row["runs"] += 1
                row["seconds"] += seconds

    chips = max(len(devices), 1)
    # Idle gaps of the chip that idled most, by the host span open then.
    worst = max(gaps_by_chip, key=lambda k: window_s - busy[k], default=None)
    idle_by_span = {}
    for a, b in gaps_by_chip.get(worst, ()):
        # Between two operations of one program the device pauses for
        # nanoseconds, hundreds of thousands of times: those are the
        # program's, not the host's.
        label = (
            _innermost(spans, (a + b) / 2) if b - a >= SHORT_GAP_NS
            else "(gaps under 10 us)"
        )
        idle_by_span[label] = idle_by_span.get(label, 0.0) + (b - a) * 1e-9
    for table in (programs, by_span):
        for row in table.values():
            row["runs"] /= chips
            row["seconds"] /= chips
    return {
        "window_s": window_s,
        "chips": len(devices),
        "busy_s": sum(busy.values()) / chips,
        "busy_s_per_chip": busy,
        "idle_share_worst": (
            max(1.0 - v / window_s for v in busy.values())
            if busy and window_s > 0 else None
        ),
        "programs": programs,
        "by_span": by_span,
        "collective_s": coll_s / chips,
        "collective_exposed_s": coll_exposed_s / chips,
        "top_ops": [
            [short_op(n), s]
            for n, s in sorted(op_self.items(), key=lambda kv: -kv[1])[:20]
        ],
        "idle_gaps": [
            [n, s]
            for n, s in sorted(idle_by_span.items(), key=lambda kv: -kv[1])[:20]
        ],
    }


def reduce_dir(trace_dir: str, span_names=()) -> dict:
    return reduce(load(find_xplane(trace_dir)), span_names)


def summary(reduced: dict) -> dict:
    """The part worth a line of output."""
    return {
        k: reduced[k] for k in (
            "window_s", "chips", "busy_s_per_chip", "idle_share_worst",
            "programs", "by_span", "collective_s", "collective_exposed_s",
        )
    }


if __name__ == "__main__":  # python3 -m graftbench.trace_reduce <trace dir or .xplane.pb> [span ...]
    import json
    import sys

    target = sys.argv[1]
    data = load(target if target.endswith(".pb") else find_xplane(target))
    for row in describe(data):
        print(*row, sep="\t")
    print(json.dumps(reduce(data, sys.argv[2:]), indent=1))
