"""Device self time a train step of the operations whose innermost scope is
``hydragnn.geom``: PaiNN's edge geometry (edge vectors, lengths, the radial
basis, the cutoff; once a step) and each block's filter Dense over the basis,
forward and backward (``graftbench/xplane_scopes.py``), mean over the chips.
A PART of ``model_dense_step_ms``, where ``xplane_scopes.bucket`` books these
rows (they sit under the model and under no ``agg``/``gather``/``pool``
scope): the six-way partition of the train root stands. The two position
gathers inside the scope carry ``hydragnn.gather`` and are
``gather_step_ms``'s. None on a program that opens no such scope."""

from graftbench import xplane_scopes

SCOPE = "hydragnn.geom"


def read(run):
    result = xplane_scopes.table(run)
    steps = run.facts.get("steps")
    if result is None or not steps:
        return None
    seconds = sum(
        r["seconds"] for r in result["rows"]
        if r["root"] == "train" and r["rooted"] and r["scope"] == SCOPE
    )
    return 1e3 * seconds / steps or None
