"""One reader a per-layer metric: ``<metric>.py`` with ``read(run)``."""


def span_ms_per_batch(run, name: str):
    """Milliseconds a loader batch of the program's graftel spans ``name``
    under the window's train epochs; None where there were none."""
    epochs = {r["span_id"] for r in run.spans if r["name"] == "train_epoch"}
    total = sum(
        r["dur_s"] for r in run.spans
        if r["name"] == name and r.get("parent_id") in epochs
    )
    batches = run.facts.get("batches")
    return 1e3 * total / batches if batches and total else None
