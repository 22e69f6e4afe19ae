"""The fullest held expert's rows over the mean of the held experts', a
routed layer and flush, over the window: the ENGINE's own counters
(``serve/metrics.py``: ``moe_load_max_total`` x experts held /
``moe_rows_held_total``, as they moved over the window; counted on the host
from the routing the executable returns). 1.0 is perfect balance; the grouped
matmul's time follows the sum, a deployment's exchange the maximum.
``facts["moe_fallback_layers"]`` beside it counts the layers whose rows passed
the compact path's capacity and took a further pass. None where the engine
counted nothing."""


def read(run):
    f = run.facts
    if not f.get("moe_rows_held") or not f.get("moe_load_max"):
        return None
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    held = arch.get("num_experts_held", arch.get("n_routed_experts"))
    return f["moe_load_max"] * held / f["moe_rows_held"]
