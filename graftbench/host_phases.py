"""The host's timeline inside the window, from the program's own spans.

``run.spans`` holds every graftel span the program collected (a record a
span: ``name``, ``ts`` on the wall clock, ``dur_s``, ``thread``, ``span_id``,
``parent_id``, ``attrs``). This module cuts them to the ``graftbench.window``
record and sorts them by thread; the host-side readers under
``layer_metrics/`` are each a few lines over it. Nesting is read from
``parent_id``, never from the clock. A record marked ``retro`` (a garbage
collection, written after the fact) was never open on its thread: it is no
phase and nobody's child here.

Every function returns None (or an empty list) where the program has no such
span, as a program from before these spans has not: a reader then leaves its
metric out of the line.
"""

from __future__ import annotations

WINDOW = "graftbench.window"


def window(spans):
    """The window's record: the last ``graftbench.window``, or None."""
    found = [r for r in spans if r["name"] == WINDOW]
    return found[-1] if found else None


def by_thread(spans) -> dict:
    """{thread name: its live spans that began inside the window, by start},
    the window's own record left out. Empty without a window."""
    w = window(spans)
    if w is None:
        return {}
    lo, hi = w["ts"], w["ts"] + w["dur_s"]
    threads = {}
    for r in spans:
        if r is not w and not r.get("retro") and lo <= r["ts"] < hi:
            threads.setdefault(r["thread"], []).append(r)
    for rows in threads.values():
        rows.sort(key=lambda r: r["ts"])
    return threads


def dispatching(spans, name=None) -> list:
    """The spans of the thread that opened the window (the one that
    dispatches the programs), all of them or those called ``name``."""
    w = window(spans)
    rows = by_thread(spans).get(w["thread"], []) if w else []
    return [r for r in rows if name is None or r["name"] == name]


def seconds(rows) -> float:
    return sum(r["dur_s"] for r in rows)


def children(rows, parents, name) -> list:
    """Those of ``rows`` called ``name`` whose parent is one of ``parents``."""
    ids = {p["span_id"] for p in parents}
    return [r for r in rows if r["name"] == name and r.get("parent_id") in ids]


def at_first_epoch(spans, *names):
    """Sum of the named attributes of the window's first ``epoch`` span (the
    program's cumulative ``jax/*_s`` counters as they stood when it opened);
    None where that span, or one of the attributes, is not there."""
    epochs = dispatching(spans, "epoch")
    attrs = (epochs[0].get("attrs") or {}) if epochs else {}
    if not all(name in attrs for name in names):
        return None
    return sum(attrs[name] for name in names)


def leaf_coverage(spans):
    """Share of the window's wall, on the window's thread, under a LEAF span:
    one with no child on that thread. Leaves of one thread do not overlap, so
    their seconds add up; each is cut to the window."""
    w = window(spans)
    rows = dispatching(spans)
    if w is None or not rows or not w["dur_s"]:
        return None
    parents = {r.get("parent_id") for r in rows}
    hi = w["ts"] + w["dur_s"]
    covered = sum(
        min(r["ts"] + r["dur_s"], hi) - r["ts"]
        for r in rows if r["span_id"] not in parents
    )
    return covered / w["dur_s"]
