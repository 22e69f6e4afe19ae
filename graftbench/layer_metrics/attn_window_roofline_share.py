"""The least time the chip could take for a train step's band attention over
what it took (``attn_window_step_ms``: the time under the scope, so the
rotary and the gate's product count against the kernel). The least time is
the family file's ``attn_counts`` operations for the band's REAL pairs (a
token's own place and the 511 before it in its sequence, not the key blocks a
kernel pads them to) over the bf16 peak (``peaks.json``): three forwards a
train step, and a fourth where the block is rematerialized
(``Architecture.remat``), because the kernel then runs its forward twice.
Operations bound it (a pair costs 4 x 128 operations a head and no byte of
HBM). Not clamped: over 100% means the count is wrong. None where
``attn_window_step_ms`` is, or for a family without ``attn_counts``."""

from graftbench import families
from graftbench.layer_metrics import attn_window_step_ms


def read(run):
    ms = attn_window_step_ms.read(run)
    f = run.facts
    steps, graphs, nodes = f.get("steps"), f.get("real_graphs"), f.get("real_nodes")
    if not ms or not steps or not graphs or not nodes or not run.peaks:
        return None
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    count = getattr(families.load(arch["model_type"]), "attn_counts", None)
    if count is None:
        return None
    # Sequences of one length (the traffic's), a step and a chip.
    a_step = graphs / (steps * f.get("chips", 1))
    ops = count(arch, [nodes / graphs])["window"]["ops"] * a_step
    forwards = 4 if arch.get("remat") else 3
    return 100.0 * forwards * ops / run.peaks["flops_per_s_bf16"] / (ms * 1e-3)
