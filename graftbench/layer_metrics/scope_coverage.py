"""Share of ALL device self time in the window on operations whose own path
holds a root scope (``hydragnn.train_step``, ``train_epoch_scan``,
``eval_step``) and a leaf scope or a flax module
(``graftbench/xplane_scopes.py``). The honesty figure of the four
``*_step_ms``: a stale cached executable, a fusion that swallowed its scope
or a step nobody scoped shows here as a fall. None where no operation holds a
root (a trace of another program)."""

from graftbench import xplane_scopes


def read(run):
    result = xplane_scopes.table(run)
    if result is None or not any(r["rooted"] for r in result["rows"]):
        return None
    return 100.0 * result["coverage"]
