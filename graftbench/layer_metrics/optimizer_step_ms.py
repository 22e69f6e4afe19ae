"""Device self time a train step of the train-root operations outside the
model: under ``hydragnn.loss``, ``hydragnn.optimizer``, ``hydragnn.grad_sync``
or under no module at all (``graftbench/xplane_scopes.py``), mean over the
chips."""

from graftbench import xplane_scopes


def read(run):
    return xplane_scopes.step_ms(run, "optimizer")
