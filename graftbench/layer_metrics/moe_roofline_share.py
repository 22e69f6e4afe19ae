"""The least time the chip could take for a train step's grouped matmuls
over what they took (``moe_step_ms``). The least time is the larger of the
operations over the bf16 peak and the bytes over the HBM peak
(``peaks.json``), both from the family file's ``moe_counts`` over the rows
the PROGRAM counted as routed to held experts (``facts["moe_rows_held"]``),
a step and a chip, three forwards a train step. At 512 rows an expert the
two bounds are within a tenth of each other (float32 weights, 256
operations a byte against the chip's 240); at the 140-280 the router sends
in the cell, the weights' bytes bound it. Not clamped: over 100% means the count
is wrong. None where ``moe_step_ms`` is, or where the program
counted no rows (a program without the counter)."""

from graftbench import families
from graftbench.layer_metrics import moe_step_ms


def read(run):
    ms = moe_step_ms.read(run)
    steps, rows = run.facts.get("steps"), run.facts.get("moe_rows_held")
    if not ms or not steps or not rows or not run.peaks:
        return None
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    count = getattr(families.load(arch["model_type"]), "moe_counts", None)
    if count is None:
        return None
    counted = count(arch, rows / (steps * run.facts.get("chips", 1)))
    least_s = max(
        3 * counted["ops"] / run.peaks["flops_per_s_bf16"],
        3 * counted["bytes"] / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (ms * 1e-3)
