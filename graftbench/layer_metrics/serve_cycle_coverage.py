"""Share of the window's turnarounds during which the engine had a REAL span
open, in percent. A flush's turnaround is ``[ready(k-1), launch_end(k)]``,
placed on the spans' clock by the two ``serve/flush`` records (a record's
``ts`` + a mark's offset). What may cover it are the live spans the engine's
own threads open, whichever flush they belong to: ``serve/await`` and
``serve/fill`` (the batcher), ``serve/collate``, ``serve/h2d`` (the transfer
thread), ``serve/device`` (with ``serve/d2h`` inside it) and ``serve/resolve``
(the dispatcher); their union is clipped to the turnaround. These are the
spans the profiler holds as annotations, so what they leave open is what
``breakdown.idle_gaps`` can only call ``_no_span_open_``: the batcher between
a fill's end and its collation's start (the ladder's snapshot, the bins'
plan), a work item in one of the ``DeviceFeed``'s two queues while the batcher
is filling, the dispatcher between two spans. A turnaround whose earlier
record is not there (a flush that failed, a record lost) counts as not
covered at all. None where no flush of the window has a turnaround."""

from graftbench.layer_metrics.serve_turnaround_ms_per_flush import FLUSH, flushes

ENGINE_SPANS = (
    "serve/await", "serve/fill", "serve/collate", "serve/h2d", "serve/device",
    "serve/resolve",
)


def _union_s(intervals, lo, hi):
    """Seconds of ``[lo, hi]`` under the union of ``intervals`` (sorted)."""
    covered, edge = 0.0, lo
    for a, b in intervals:
        a, b = max(a, edge), min(b, hi)
        if b > a:
            covered, edge = covered + (b - a), b
    return covered


def read(run):
    by_id = {
        r["attrs"]["flush_id"]: r for r in run.spans
        if r["name"] == FLUSH and "marks" in (r.get("attrs") or {})
    }
    open_spans = sorted(
        (r["ts"], r["ts"] + r["dur_s"]) for r in run.spans
        if r["name"] in ENGINE_SPANS and not r.get("retro")
    )
    covered = turned = 0.0
    for after in flushes(run):
        if after["attrs"].get("turnaround_s") is None:
            continue
        before = by_id.get(after["attrs"]["flush_id"] - 1)
        if before is None:
            turned += after["attrs"]["turnaround_s"]
            continue
        lo = before["ts"] + before["attrs"]["marks"]["ready"]
        hi = after["ts"] + after["attrs"]["marks"]["launch_end"]
        covered += _union_s(open_spans, lo, hi)
        turned += max(hi - lo, 0.0)
    return 100.0 * covered / turned if turned else None
