"""Host milliseconds a flush spent in the engine's transfer stage
(``InferenceEngine._transfer``: one blocking ``device_put`` of the padded
batch), from the engine's own ``h2d`` clock pair over the window. None where
no flush was taken."""


def read(run):
    n = run.facts.get("h2d_n")
    return 1e3 * run.facts["h2d_s"] / n if n else None
