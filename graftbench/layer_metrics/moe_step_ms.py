"""Device self time a train step of the operations whose innermost scope is
``hydragnn.moe.experts``: the routed experts' grouped matmuls and the SwiGLU
between them, forward and backward (``graftbench/xplane_scopes.py``), mean
over the chips. A PART of ``model_dense_step_ms``, where
``xplane_scopes.bucket`` books these rows (they sit under the model and under
no ``agg``/``gather``/``pool`` scope), as ``moe_route_step_ms`` and
``seqmix_step_ms`` are. None on a program that opens no such scope."""

from graftbench import xplane_scopes

SCOPES = ("hydragnn.moe.experts",)


def scoped_ms(run, scopes):
    """Milliseconds a train step under any of ``scopes`` (innermost)."""
    result = xplane_scopes.table(run)
    steps = run.facts.get("steps")
    if result is None or not steps:
        return None
    seconds = sum(
        r["seconds"] for r in result["rows"]
        if r["root"] == "train" and r["rooted"] and r["scope"] in scopes
    )
    return 1e3 * seconds / steps or None


def read(run):
    return scoped_ms(run, SCOPES)
