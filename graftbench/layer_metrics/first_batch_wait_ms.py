"""Milliseconds the consumer waited for the FIRST payload of a train epoch
(the first ``feed_wait`` span under each ``train_epoch`` of the window),
mean over the epochs: on the scan path the whole epoch's collation, since no
chunk is handed over before the loader is exhausted; on the mesh path one
group of batches. What ROADMAP S7 would shorten. None where a train epoch
waits on no feed (a program without the span there, a device-resident
replay)."""

from graftbench import host_phases


def read(run):
    rows = host_phases.dispatching(run.spans)
    firsts = []
    for epoch in (r for r in rows if r["name"] == "train_epoch"):
        waits = host_phases.children(rows, [epoch], "feed_wait")
        if waits:
            firsts.append(waits[0]["dur_s"])  # rows come sorted by start
    return 1e3 * sum(firsts) / len(firsts) if firsts else None
