"""Host milliseconds a flush spent in the engine's collation stage
(``InferenceEngine._collate``: the arena over the flush's graphs, its sort of
the edges by receiver, the padded batch), from the engine's own ``collate``
clock pair over the window. In a closed loop it is in series with the
forward. None where no flush was taken."""


def read(run):
    n = run.facts.get("collate_n")
    return 1e3 * run.facts["collate_s"] / n if n else None
