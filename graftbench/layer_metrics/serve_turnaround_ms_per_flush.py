"""Host milliseconds the chip sees as idle between two flushes: the engine's
``turnaround_s`` (the executable call's return less the previous flush's
``ready``, not under 0, on ``time.perf_counter()``), mean over the window's
``serve/flush`` records that have one (a pipeline's first flush has none).
Host-clock, where ``serve_device_idle_share`` is the device's: the two differ
by what the device does after the call returns. None for a program that
writes no ``serve/flush`` record.

``flushes`` / ``parts`` / ``mean_ms`` are what the readers of the flush's
other parts share: the record's ``marks`` are offsets in seconds from its
start (``first_queued`` 0), its ``t0`` that start on the marks' own clock."""

from graftbench import host_phases

FLUSH = "serve/flush"


def flushes(run) -> list:
    """The window's ``serve/flush`` records by ``flush_id`` (retroactive
    records: ``host_phases.by_thread`` leaves them out, so cut here)."""
    w = host_phases.window(run.spans)
    if w is None:
        return []
    lo, hi = w["ts"], w["ts"] + w["dur_s"]
    rows = [
        r for r in run.spans
        if r["name"] == FLUSH and lo <= r["ts"] < hi and "marks" in (r.get("attrs") or {})
    ]
    return sorted(rows, key=lambda r: r["attrs"]["flush_id"])


def parts(record) -> dict:
    """Seconds of each part of one flush from its marks, by subtraction."""
    m = record["attrs"]["marks"]
    return {
        "fill": m["taken"] - m["first_queued"],
        "collate": m["collated"] - m["taken"],
        "handoff": (m["h2d_start"] - m["collated"]) + (m["exec_start"] - m["h2d_end"]),
        "h2d": m["h2d_end"] - m["h2d_start"],
        "lookup": m["launch_start"] - m["exec_start"],
        "launch": m["launch_end"] - m["launch_start"],
        "device_wait": m["ready"] - m["launch_end"],
        "d2h": m["d2h_end"] - m["ready"],
        "resolve": m["resolved"] - m["d2h_end"],
        "await": record["attrs"].get("await_s") or 0.0,
    }


def mean_ms(run, *names):
    """Mean over the window's flushes of the named parts' sum, in ms."""
    rows = flushes(run)
    if not rows:
        return None
    return 1e3 * sum(sum(parts(r)[n] for n in names) for r in rows) / len(rows)


def read(run):
    turns = [
        r["attrs"]["turnaround_s"] for r in flushes(run)
        if r["attrs"].get("turnaround_s") is not None
    ]
    return 1e3 * sum(turns) / len(turns) if turns else None
