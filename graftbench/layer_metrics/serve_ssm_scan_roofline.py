"""The least time the chip could take for a flush's selective scans over what
they took (``serve_ssm_scan_ms_per_flush``). The least time is the LARGER of
the family file's ``scan_counts`` operations over the bf16 peak and its bytes
over the HBM's bandwidth (``peaks.json``), for the REAL tokens of the
documents the window answered (not the rung's padding rows), all Mamba layers
together, ONE forward. ``peaks.json`` has no row for the vector unit, where
every one of the scan's operations runs (7 a state element and step: an
``exp`` and six multiplications and additions, none a matmul): against the
matrix unit's 197 TFLOP/s they are a few milliseconds, so the BYTES bound the
least time (``u``, ``dt`` in and ``y`` out, 20,480 B each a token and layer)
and a share well under 100% is the expected reading: it says how far the
kernel is from streaming its rows at the memory's speed, not how busy the
vector unit is. Not clamped: over 100% means the count is wrong. None where
the time is, or for a family without ``scan_counts``."""

from graftbench import families
from graftbench.layer_metrics import serve_ssm_scan_ms_per_flush


def read(run):
    ms = serve_ssm_scan_ms_per_flush.read(run)
    lengths, flushes = run.facts.get("doc_lengths"), run.facts.get("flushes")
    if not ms or not lengths or not flushes or not run.peaks:
        return None
    arch = run.cell.config["NeuralNetwork"]["Architecture"]
    count = getattr(families.load(arch["model_type"]), "scan_counts", None)
    if count is None:
        return None
    counted = count(arch, float(sum(lengths)))
    least = max(
        counted["ops"] / run.peaks["flops_per_s_bf16"],
        counted["bytes"] / run.peaks["hbm_bytes_per_s"],
    ) / flushes
    return 100.0 * least / (ms * 1e-3)
