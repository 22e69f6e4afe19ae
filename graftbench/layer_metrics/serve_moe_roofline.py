"""The least time the chip could take for a flush's grouped matmuls over what
they took (``serve_moe_ms_per_flush``). The least time is the larger of the
operations over the bf16 peak and the bytes over the HBM peak
(``peaks.json``), both from the family file's ``moe_counts`` over the rows the
ENGINE counted as sent to held experts (``moe_rows_held_total`` as it moved
over the window, a flush), ONE forward: at the ~415 rows an expert of this
cell (an eighth of a deployment's) the held experts' float32 weights, read
once a layer and flush, bound it. Not clamped: over 100% means the count is
wrong. None where the time is, or where the engine counted no rows."""

from graftbench import families
from graftbench.layer_metrics import serve_moe_ms_per_flush


def read(run):
    ms = serve_moe_ms_per_flush.read(run)
    flushes, rows = run.facts.get("flushes"), run.facts.get("moe_rows_held")
    if not ms or not flushes or not rows or not run.peaks:
        return None
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    count = getattr(families.load(arch["model_type"]), "moe_counts", None)
    if count is None:
        return None
    counted = count(arch, rows / flushes)
    least_s = max(
        counted["ops"] / run.peaks["flops_per_s_bf16"],
        counted["bytes"] / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (ms * 1e-3)
