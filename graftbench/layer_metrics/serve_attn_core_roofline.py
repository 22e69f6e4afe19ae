"""The least time the chip could take for a flush's attention cores over what
they took (``serve_attn_core_ms_per_flush``). The least time is the family
file's ``attn_counts`` operations for the REAL causal pairs of the documents
the window answered (a token, its own place and those before it in its own
document: 4 x 128 operations a pair and head over 32 heads, and the
softmax's 5; not the key blocks a kernel pads them to, not the padding rows)
over the bf16 peak (``peaks.json``), ONE forward. Operations bound it (a pair
costs no byte of HBM). Not clamped: over 100% means the count is wrong. None
where the time is, or for a family without ``attn_counts``."""

from graftbench import families
from graftbench.layer_metrics import serve_attn_core_ms_per_flush


def read(run):
    ms = serve_attn_core_ms_per_flush.read(run)
    lengths, flushes = run.facts.get("doc_lengths"), run.facts.get("flushes")
    if not ms or not lengths or not flushes or not run.peaks:
        return None
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    count = getattr(families.load(arch["model_type"]), "attn_counts", None)
    if count is None:
        return None
    ops = count(arch, lengths)["full"]["ops"] / flushes
    return 100.0 * ops / run.peaks["flops_per_s_bf16"] / (ms * 1e-3)
