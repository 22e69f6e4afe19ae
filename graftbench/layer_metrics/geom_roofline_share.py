"""The bytes a train step needs under ``hydragnn.geom`` (the family file's
``geom_bytes`` at the real nodes and edges of a step and a chip, by
``graftbench/flops.py``'s convention: the ``[E, 3]`` reads, the ``[E, 20]``
basis, and a block the filter Dense with its ``[E, 3F]`` write, forward and
backward) over what the chip's HBM peak (``peaks.json``) could move in
``geom_step_ms``. Bytes bound it: the Dense is 20 deep. Not clamped. None
where ``geom_step_ms`` is, or where the family counts no such bytes."""

from graftbench import families
from graftbench.layer_metrics import geom_step_ms


def read(run):
    ms = geom_step_ms.read(run)
    steps = run.facts.get("steps")
    if not ms or not steps or not run.peaks:
        return None
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    count = getattr(families.load(arch["model_type"]), "geom_bytes", None)
    if count is None:
        return None
    rows = steps * run.facts.get("chips", 1)
    counted = count(arch, run.facts["real_nodes"] / rows, run.facts["real_edges"] / rows)
    return 100.0 * sum(counted.values()) / (ms * 1e-3 * run.peaks["hbm_bytes_per_s"])
