"""Share of the padded node rows of the window's flushes that held no real
node: 1 - the answered requests' atoms over the rows of the rungs the engine
counted a flush into (``per_bucket``), in percent. None where no flush was
taken."""


def read(run):
    f = run.facts
    if not f.get("pad_nodes"):
        return None
    return 100.0 * (1.0 - f["real_nodes"] / f["pad_nodes"])
