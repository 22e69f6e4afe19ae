"""Share of the evaluations' wall in which the consumer waited on the feed:
``feed_wait`` spans whose parent is an ``evaluate`` span over the ``evaluate``
spans of the window. None where the evaluation batches are device-resident
(the one-chip cells: no feed to wait on)."""

from graftbench import host_phases


def read(run):
    rows = host_phases.dispatching(run.spans)
    evaluations = [r for r in rows if r["name"] == "evaluate"]
    waits = host_phases.children(rows, evaluations, "feed_wait")
    if not waits:
        return None
    return 100.0 * host_phases.seconds(waits) / host_phases.seconds(evaluations)
