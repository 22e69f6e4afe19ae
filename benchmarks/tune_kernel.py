"""Edge-block-size sweep for the fused Pallas segment kernel.

The kernel's grid walks edge blocks of _BE columns (ops/pallas_segment.py);
larger blocks amortize grid overhead, smaller ones cut VMEM residency. The
right value is a hardware measurement, not a guess — this sweep re-runs
``certify_pallas`` (accuracy + timed sum/mean/std bundle vs the XLA path) for
each candidate in a FRESH subprocess (the module pins _BE at import from
HYDRAGNN_PALLAS_BE) and appends the winner to a JSONL artifact.

Run ON TPU (the CPU interpreter's timings are meaningless for block tuning):

    python benchmarks/tune_kernel.py --out TUNE_KERNEL_r04.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_CHILD = r"""
import json, os, sys
if os.environ.get("HYDRAGNN_TUNE_CPU"):
    import jax
    jax.config.update("jax_platforms", "cpu")
from hydragnn_tpu.ops.pallas_segment import certify_pallas, _BE
# contiguous (sorted) ids = the production collation pattern; also the only
# shape where the HYDRAGNN_PALLAS_SKIP arm can actually skip blocks.
r = certify_pallas(
    e=int(sys.argv[1]), f=int(sys.argv[2]), n=int(sys.argv[3]), contiguous=True,
    # The sorted arm does not read _BE/SKIP, so sweeping re-measures nothing:
    # only the first arm times it. The CSR run-walk
    # kernel DOES read _BE/_BN, so --csr re-measures it per candidate.
    sorted_arm=os.environ.get("HYDRAGNN_TUNE_SORTED") == "1",
    csr_arm=os.environ.get("HYDRAGNN_TUNE_CSR") == "1",
)
r["be"] = _BE
print("RESULT " + json.dumps(r))
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--candidates", default="256,512,1024,2048")
    ap.add_argument("--e", type=int, default=16384)
    ap.add_argument("--f", type=int, default=64)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--skip", choices=("off", "on", "both"), default="off",
        help="sweep the block-skip variant (HYDRAGNN_PALLAS_SKIP) per "
        "candidate: off / on / both arms",
    )
    ap.add_argument(
        "--csr", action="store_true",
        help="also sweep the CSR run-walk kernel (the row_ptr batch "
        "contract, ops/pallas_segment.csr_segment_sum_count) per candidate "
        "— the arm for the next hardware batch",
    )
    ap.add_argument(
        "--cpu", action="store_true",
        help="force the CPU interpreter in children (plumbing smoke test "
        "only — timings are meaningless off-TPU)",
    )
    args = ap.parse_args()

    try:
        candidates = [int(x) for x in args.candidates.split(",") if x.strip()]
    except ValueError:
        sys.exit(f"--candidates must be comma-separated integers, got {args.candidates!r}")
    if not candidates:
        sys.exit("--candidates is empty")

    skip_arms = {"off": ("0",), "on": ("1",), "both": ("0", "1")}[args.skip]
    rows = []
    first = True
    for be, skip in ((b, s) for b in candidates for s in skip_arms):
        env = dict(
            os.environ,
            HYDRAGNN_PALLAS_BE=str(be),
            HYDRAGNN_PALLAS="1",
            HYDRAGNN_PALLAS_SKIP=skip,
            HYDRAGNN_TUNE_SORTED="1" if first else "0",
            HYDRAGNN_TUNE_CSR="1" if args.csr else "0",
        )
        first = False
        if args.cpu:
            env["HYDRAGNN_TUNE_CPU"] = "1"
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _CHILD, str(args.e), str(args.f), str(args.n)],
                cwd=REPO,
                env=env,
                capture_output=True,
                text=True,
                timeout=900,
            )
        except subprocess.TimeoutExpired:
            # A hung child: record the row and keep sweeping.
            rows.append({"be": be, "skip": skip == "1", "error": "child timed out after 900s"})
            print(json.dumps(rows[-1]), flush=True)
            continue
        line = next(
            (l for l in proc.stdout.splitlines() if l.startswith("RESULT ")), None
        )
        if line is None:
            rows.append({"be": be, "skip": skip == "1", "error": (proc.stderr or proc.stdout)[-300:]})
            print(json.dumps(rows[-1]), flush=True)
            continue
        r = json.loads(line[len("RESULT ") :])
        rows.append(
            {
                "be": be,
                "skip": r.get("pallas_skip", skip == "1"),
                "ok": r["ok"],
                "pallas_ms": r["pallas_ms"],
                "xla_ms": r["xla_ms"],
                "speedup": r["speedup"],
                "backend": r["backend"],
                # Full certification error fields: an ok=false row without
                # magnitudes is undiagnosable afterwards (r05 lesson — three
                # ok=false rows, no way to tell a tolerance nit from a
                # broken kernel).
                "errs": {
                    k: r.get(k)
                    for k in (
                        "max_err_fwd", "max_err_grad", "wide_f",
                        "wide_err_fwd", "wide_err_grad",
                        "xla_err_fwd", "xla_err_grad", "tol",
                    )
                },
                # Third arm: the scatter-free sorted path (certify measures
                # it on contiguous ids alongside kernel + XLA).
                "sorted_ms": r.get("sorted_ms"),
                "sorted_ok": r.get("sorted_ok"),
                "sorted_speedup_vs_xla": r.get("sorted_speedup_vs_xla"),
                # Fourth arm (--csr): the CSR run-walk kernel, swept per
                # candidate — it reads the same _BE/_BN block geometry.
                "csr_ms": r.get("csr_ms"),
                "csr_ok": r.get("csr_ok"),
                "csr_errs": {
                    k: r.get(k) for k in ("csr_err_fwd", "csr_err_grad")
                }
                if args.csr
                else None,
                "csr_speedup_vs_xla": r.get("csr_speedup_vs_xla"),
            }
        )
        print(json.dumps(rows[-1]), flush=True)

    timed = [r for r in rows if r.get("ok")]
    best = min(timed, key=lambda r: r["pallas_ms"]) if timed else None
    summary = {
        "ts_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "workload": {"e": args.e, "f": args.f, "n": args.n},
        "rows": rows,
        "best": best and {"be": best["be"], "skip": best["skip"]},
    }
    print(json.dumps({"best": summary["best"]}))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(summary) + "\n")


if __name__ == "__main__":
    main()
