"""Share of padded node rows that held no real node, over the window's
train batches: an exact count from the loader's own accounting."""


def read(run):
    f = run.facts
    if not f.get("pad_nodes"):
        return None
    return 100.0 * (1.0 - f["real_nodes"] / f["pad_nodes"])
