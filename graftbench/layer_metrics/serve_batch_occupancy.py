"""Graphs a flush over ``max_batch_graphs``, over the window's flushes: the
engine's own counts (``batches_total``, ``graphs_total``). 1.0 is every
flush full and fired on its size; under it some flush fired on the deadline
(``facts["graphs_short_of_full"]`` says by how many graphs), which in a
closed loop is a second cycle. None where no flush was taken."""


def read(run):
    flushes = run.facts.get("flushes")
    if not flushes:
        return None
    return run.facts["graphs"] / (flushes * run.facts["max_batch_graphs"])
