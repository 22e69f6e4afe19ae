"""Operations a train step needs (``graftbench/flops.py``, real shapes) over
what the chip's bf16 peak could do in the step's device time. Named for what
it is: a utilization of the whole step, not a kernel's roofline share."""

from graftbench.layer_metrics import device_step_ms


def read(run):
    step_ms = device_step_ms.read(run)
    if not step_ms or not run.peaks or not run.facts.get("step_ops"):
        return None
    per_chip = run.facts["step_ops"] / run.facts.get("chips", 1)
    return 100.0 * per_chip / (step_ms * 1e-3 * run.peaks["flops_per_s_bf16"])
