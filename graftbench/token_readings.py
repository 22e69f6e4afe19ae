"""The upper reading a token serving cell's limits stand under, on the host.

    python3 -m graftbench.token_readings --workload <cell> --seeds 1,2,3 [--tokens 2048]

For each seed: the cell's weights from the seed (``drivers/serve_tokens``'s,
drawn on whatever device JAX has), one seeded document of
``--tokens`` tokens, and the family file's ``logprobs`` of it three times, all
routed as the float32 reference routes: float32 throughout (``Exact``), the
stated precision emulated (``Operands``: matmul operands rounded to bf16, what
the engine does on the chip; the LOWER reading's emulation, beside the chip's
own), and the precision below (``Below``: the residual stream, kept
activations and probabilities rounded too; the UPPER reading, which
``compare`` has to call NOT correct). Arithmetic only, wherever it runs (the
readings in PERF.md are a host CPU's): no number here is a device metric. The benchmark's runs never run this;
``tests/test_mistral4_cell.py`` keeps the control. PERF.md section 2 has the
readings and the limits set from them.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from graftbench import families
from graftbench import run as bench_run
from graftbench.drivers import serve_tokens as drv


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m graftbench.token_readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tokens", type=int, default=2048)
    args = ap.parse_args(argv)
    _, entry, config, traffic = bench_run._load_cell(args.workload)

    import jax

    arch = drv.completed_arch(config)
    model, template, _ = drv.init_model(arch)
    family = families.load(model.conv_type)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        graphs = dict(traffic["graphs"], documents=[[args.tokens, 1]])
        doc = drv.make_pool(graphs, seed)[0]
        host, params = drv.reference_params(drv.seeded_weights(template, seed))
        graph = {"x": doc.x, "pos": doc.pos}
        with jax.default_device(host):
            want, report = family.logprobs(model, params, graph)
            routing = np.concatenate(report["chosen"], axis=1)
            row = {"seed": seed, "tokens": args.tokens}
            for name in ("Operands", "Below"):
                got, again = family.logprobs(
                    model, params, graph, routing, plain=getattr(family, name)
                )
                worst, rel, fail = family.compare(got, want)
                row[name] = {"rel_l2": rel, "max_diff": worst, "fail": fail,
                             "route_margin": again["route_margin"]}
        row["seconds"] = round(time.perf_counter() - t0, 1)
        rows.append(row)
        print("[readings] " + json.dumps(row), flush=True)
    print("[readings] " + json.dumps({
        "cell": entry["name"], "limit_rel_l2": family.rel_l2_limit(args.tokens),
        "stated_max": max(r["Operands"]["rel_l2"] for r in rows),
        "below_min": min(r["Below"]["rel_l2"] for r in rows),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
