"""1 - union of device-operation intervals over the window (trace), of the
chip that idled most."""


def read(run):
    v = run.trace["idle_share_worst"]
    return None if v is None else 100.0 * v
