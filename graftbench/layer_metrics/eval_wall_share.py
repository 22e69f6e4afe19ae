"""Share of an epoch's wall time spent in the two evaluations (validation
and test): the benchmark's span round the program's epoch less the program's
own ``train/epoch_wall_s``."""


def read(run):
    f = run.facts
    if not f.get("epoch_wall_s"):
        return None
    return 100.0 * f["eval_wall_s"] / f["epoch_wall_s"]
