"""The least time the chip could take for a flush's band attention over what
it took (``serve_attn_window_ms_per_flush``: the time under the scope, so the
rotary counts against the kernel). The least time is the family file's
``attn_counts`` operations for the band's REAL pairs of the documents the
window answered (a token, its own place and the ``sliding_window - 1`` before
it in its own document: 4 x head_dim operations a pair and head, and the
softmax's 5; not the key blocks a kernel pads them to, not the padding rows),
all window layers together, over the bf16 peak (``peaks.json``), ONE forward.
Operations bound it (a pair costs no byte of HBM). Not clamped: over 100%
means the count is wrong. None where the time is, or for a family whose
``attn_counts`` has no ``window``."""

from graftbench import families
from graftbench.layer_metrics import serve_attn_window_ms_per_flush


def read(run):
    ms = serve_attn_window_ms_per_flush.read(run)
    lengths, flushes = run.facts.get("doc_lengths"), run.facts.get("flushes")
    if not ms or not lengths or not flushes or not run.peaks:
        return None
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    count = getattr(families.load(arch["model_type"]), "attn_counts", None)
    band = count(arch, lengths).get("window") if count else None
    if not band or not band["ops"]:
        return None
    return 100.0 * band["ops"] / flushes / run.peaks["flops_per_s_bf16"] / (ms * 1e-3)
