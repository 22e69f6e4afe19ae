"""The fullest held expert's rows over the mean of the held experts', a
routed layer and step, averaged over the window's train steps: the program's
own counters (``train/moe_load_max_per_epoch`` over
``train/moe_rows_held_per_epoch`` / experts held, summed by the driver). 1.0
is perfect balance; the grouped matmul's time follows the sum, a later
four-chip cell's exchange the maximum. None where the program counted
nothing."""


def read(run):
    f = run.facts
    if not f.get("moe_rows_held") or not f.get("moe_load_max"):
        return None
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    held = arch.get("num_experts_held", arch.get("num_experts"))
    return f["moe_load_max"] * held / f["moe_rows_held"]
