"""Share of the window's wall, on the thread that dispatches the programs,
that lies under a leaf span of the program (``epoch_head``, ``feed_wait``,
``device_step``, ``feed_drain``, ``eval_step``, ``epoch_tail``: a span with no
child on that thread). The honesty figure of every host-side metric, as
``scope_coverage`` is the device's: what it leaves out, no span names. None
for a program that opens no ``epoch`` span (its leaves were never meant to
cover the epoch)."""

from graftbench import host_phases


def read(run):
    if not host_phases.dispatching(run.spans, "epoch"):
        return None
    share = host_phases.leaf_coverage(run.spans)
    return None if share is None else 100.0 * share
