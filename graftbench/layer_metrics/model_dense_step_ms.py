"""Device self time a train step of the operations under a flax module path
and under no ``hydragnn.agg.*``, ``hydragnn.gather`` or ``hydragnn.pool``
scope: Dense layers, batch norms, heads, activations
(``graftbench/xplane_scopes.py``), mean over the chips."""

from graftbench import xplane_scopes


def read(run):
    return xplane_scopes.step_ms(run, "model_dense")
