"""Milliseconds the consumer waited for the FIRST payload of a train epoch
(the first ``feed_wait`` span under each ``train_epoch`` of the window),
mean over the epochs. Since PR 36 on the scan path that is ONE chunk's wait:
``TrainingDriver._host_chunks`` hands a shape's chunk on once ``SCAN_CHUNK``
= 4 of its batches are collated (a tail as the same stack with a smaller
count), where before it the loader had to run dry first and the wait was the
whole epoch's collation; on the mesh path it is one group of batches. What
ROADMAP S7 would shorten. None where a train epoch waits on no feed (a
program without the span there, a device-resident replay)."""

from graftbench import host_phases


def read(run):
    rows = host_phases.dispatching(run.spans)
    firsts = []
    for epoch in (r for r in rows if r["name"] == "train_epoch"):
        waits = host_phases.children(rows, [epoch], "feed_wait")
        if waits:
            firsts.append(waits[0]["dur_s"])  # rows come sorted by start
    return 1e3 * sum(firsts) / len(firsts) if firsts else None
