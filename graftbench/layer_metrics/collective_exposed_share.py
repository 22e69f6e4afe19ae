"""Share of the window in which a chip ran a collective and nothing else
(trace; mean over the chips)."""


def read(run):
    t = run.trace
    if t["chips"] < 2 or not t["window_s"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
