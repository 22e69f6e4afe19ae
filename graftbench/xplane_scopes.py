"""Device time by named scope, from the ``.xplane.pb`` a traced run leaves.

What ``trace_reduce.py`` cannot see and why. ``jax.profiler.ProfileData``
yields each event's OWN stats (``device_offset_ps``, ``device_duration_ps``)
and never the stats of the event's METADATA. On a device plane's ``XLA Ops``
line that metadata holds, for each HLO operation: ``tf_op`` (JAX's
``op_name``: ``jit(step)/hydragnn.train_step/jvp(HydraGNN)/conv_1/
hydragnn.agg.pna/hydragnn.agg.stats.csr/gather:``), ``hlo_category``,
``flops``, ``bytes_accessed``, ``program_id``, ``source``. So the program's
``jax.named_scope`` names, flax's module path and the ``jvp``/``transpose``
wrappers of differentiation are all in the file. This module reads the file
as a raw ``XSpace`` (the protobuf wire format of the few messages needed:
neither TensorFlow, which holds the only ``xplane_pb2`` around, nor any other
dependency) and reduces, inside the ``graftbench.window`` annotation, to
device SELF seconds (a container's time less its children's, mean over the
chips) keyed by

* ``root``: ``train`` / ``eval`` / ``other``, from ``hydragnn.train_step``,
  ``hydragnn.train_epoch_scan`` or ``hydragnn.eval_step`` in the path; an
  operation whose path holds none (a copy or a parameter XLA made, without
  metadata) takes the root of its program (``program_id``) when that
  program's named operations agree on one, and counts as NOT covered;
* ``direction``: ``bwd`` if the path holds ``transpose(``, else ``fwd`` (a
  rematerialized forward runs in the backward pass and is ``bwd``);
* ``module``: the flax path component under the model, ``conv_1``, ``bn_0``,
  ``head_2`` (read before the first ``hydragnn.*`` leaf: modules enclose the
  scopes); ``(model)`` for an operation of the model's own ``__call__``; ``-``
  outside any module;
* ``scope``: the innermost ``hydragnn.*`` name below the root
  (``hydragnn.agg.stats.csr``), else ``(model)`` under a module, else
  ``(none)``.

A fused operation carries the metadata of its ROOT instruction alone, so
attribution is by root: a gather fused into the multiply that consumes it is
booked to the multiply's scope. ``coverage`` says how much device time sits
on a path with a root AND (a leaf scope or a module); what is left is listed
by ``hlo_category`` and shape under ``uncovered``.

``flops`` and ``bytes_accessed`` are XLA's own figures for each operation,
summed over the executions of LEAF events (an event that contained others, a
``while`` or a call, adds none: its children carry them). They are not the
traffic the algorithm needs: over padded rows and tiles they pass the chip's
HBM peak in places, and an in-place scatter-add is charged a fifth of the
rows it rewrites (PERF.md section 6, PR 25). So no metric reads them:
``gather_roofline_share`` and ``agg_roofline_share`` divide the bytes
``graftbench/flops.py`` counts by hand from real rows by the seconds found
here, and ``scopes.json`` keeps both figures side by side (``bytes_a_step``).

``table(run)`` is what the by-scope readers in ``layer_metrics/`` share: one
parse a trace (memoised by path), written whole to
``graftbench/out/<cell>/scopes.json``, its ten largest rows printed on a
``[graftbench]`` line. By hand, on any trace directory (a ``"Profile"`` run's
``logs/<name>/profiler_output`` too)::

    python3 -m graftbench.xplane_scopes <trace dir or .xplane.pb>
"""

from __future__ import annotations

import json
import os
import re
import struct
import time

from graftbench.layer_metrics import device_step_ms
from graftbench.trace_reduce import (
    WINDOW, _clip, _is_device, _self_times, _union, find_xplane,
)

ROOTS = {
    "hydragnn.train_step": "train",
    "hydragnn.train_epoch_scan": "train",
    "hydragnn.eval_step": "eval",
}
# Path components that are neither a module nor a scope: what JAX's
# transformations and control flow write into the name stack.
_PLUMBING = frozenset((
    "while", "body", "cond", "closed_call", "core_call", "shard_map", "pjit",
    "checkpoint", "rematted_computation", "remat", "custom_vjp_call",
    "custom_jvp_call", "custom_vjp_call_jaxpr", "custom_lin", "branch",
))
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_SHAPE = re.compile(r"= \(?(\w+\[[\d,]*\])")


# ------------------------------------------------------------- wire format
def _fields(buf):
    """(field number, wire type, value) of one protobuf message: an int for
    a varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0 or wire == 2:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if wire == 2:
                v, i = buf[i:i + v], i + v
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield key >> 3, wire, v


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """One XStat -> (name, value). ``ref_value`` names a stat's metadata."""
    name = value = None
    for no, wire, v in _fields(buf):
        if no == 1:
            name = stat_names.get(v, str(v))
        elif no == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif no == 5:
            value = _text(v)
        elif no == 6:
            value = bytes(v)
        elif no == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _event(buf):
    """One XEvent -> (metadata id, offset ps, duration ps)."""
    meta = offset = duration = 0
    for no, _, v in _fields(buf):
        if no == 1:
            meta = v
        elif no == 2:
            offset = v
        elif no == 3:
            duration = v
    return meta, offset, duration


def _plane(buf) -> dict:
    name, lines, metadata, stat_names = "", [], {}, {}
    raw_meta, raw_lines = [], []
    for no, _, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            raw_lines.append(v)
        elif no == 4:  # map<int64, XEventMetadata>: value is field 2
            raw_meta.extend(x for f, _, x in _fields(v) if f == 2)
        elif no == 5:  # map<int64, XStatMetadata>
            for f, _, x in _fields(v):
                if f == 2:
                    d = {a: c for a, _, c in _fields(x)}
                    stat_names[d.get(1, 0)] = _text(d.get(2, b""))
    for m in raw_meta:
        row = {"name": "", "display_name": "", "stats": {}}
        ident = 0
        for no, _, v in _fields(m):
            if no == 1:
                ident = v
            elif no == 2:
                row["name"] = _text(v)
            elif no == 4:
                row["display_name"] = _text(v)
            elif no == 5:
                k, value = _stat(v, stat_names)
                row["stats"][k] = value
        metadata[ident] = row
    for ln in raw_lines:
        line = {"name": "", "timestamp_ns": 0, "events": []}
        for no, _, v in _fields(ln):
            if no == 2:
                line["name"] = _text(v)
            elif no == 3:
                line["timestamp_ns"] = v
            elif no == 4:
                line["events"].append(_event(v))
        lines.append(line)
    return {"name": name, "lines": lines, "event_metadata": metadata}


_PARSED: dict = {}


def parse(path: str) -> list:
    """The planes of an ``.xplane.pb``; one parse a file."""
    key = os.path.abspath(path)
    if key not in _PARSED:
        with open(key, "rb") as f:
            buf = memoryview(f.read())
        _PARSED[key] = [_plane(v) for no, _, v in _fields(buf) if no == 1]
    return _PARSED[key]


def _intervals(line):
    """[(metadata id, start ns, end ns)] of a line, on the trace's clock, in
    whole nanoseconds as ``ProfileData`` cuts them: both reducers agree."""
    t0 = line["timestamp_ns"]
    return [
        (m, t0 + off // 1000, t0 + off // 1000 + dur // 1000)
        for m, off, dur in line["events"]
    ]


def host_events(planes) -> list:
    """[(name, start ns, end ns)] of every host-thread event."""
    out = []
    for plane in planes:
        if plane["name"].startswith("/host:"):
            names = plane["event_metadata"]
            for line in plane["lines"]:
                out.extend(
                    (names.get(m, {}).get("name", ""), a, b)
                    for m, a, b in _intervals(line)
                )
    return out


def device_ops(planes) -> dict:
    """{device plane: ([(metadata id, start ns, end ns)], its metadata)} of
    the ``XLA Ops`` lines."""
    out = {}
    for plane in planes:
        if _is_device(plane["name"]):
            events = [
                e for line in plane["lines"] if line["name"] == "XLA Ops"
                for e in _intervals(line)
            ]
            out[plane["name"]] = (events, plane["event_metadata"])
    return out


# ------------------------------------------------------------------- paths
def classify(tf_op: str) -> tuple:
    """``tf_op`` -> (root or None, direction, module, scope)."""
    path = tf_op.rsplit(":", 1)[0] if tf_op else ""
    root = next((r for name, r in ROOTS.items() if name in path), None)
    direction = "bwd" if "transpose(" in path else "fwd"
    parts = []
    for part in path.split("/")[:-1]:  # the last is the primitive's name
        while (m := _WRAPPER.match(part)):
            part = m.group(1)
        if part and part not in _PLUMBING and not part.startswith("jit("):
            parts.append(part)
    # Below the LAST root component: differentiation repeats the stack.
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in ROOTS:
            parts = parts[i + 1:]
            break
    leaves = [p for p in parts if p.startswith("hydragnn.")]
    # Modules enclose the scopes, never the reverse: a plain component after
    # the first leaf is a function inside the scoped code (``_prefix_open``).
    first = next((i for i, p in enumerate(parts) if p in leaves), len(parts))
    plain = parts[:first]
    while len(plain) > 1 and plain[1] == plain[0]:
        del plain[1]  # transpose(jvp(Model))/jvp(Model)/...: one model
    # plain[0] is the model (flax names the top module by its class), the
    # next one the module an operator would look for.
    module = (plain[1] if len(plain) > 1 else "(model)") if plain else "-"
    scope = leaves[-1] if leaves else ("(model)" if plain else "(none)")
    return root, direction, module, scope


def _short(meta: dict) -> str:
    shape = _SHAPE.search(meta["name"])
    return f"{meta['stats'].get('hlo_category', '?')} {shape.group(1) if shape else ''}".strip()


# ---------------------------------------------------------------- reduction
def by_scope(path: str, use_window: bool = True) -> dict:
    """The reduction the module's docstring describes."""
    planes = parse(path)
    devices = device_ops(planes)
    windows = [e for e in host_events(planes) if e[0] == WINDOW]
    if use_window and windows:
        lo, hi = windows[0][1], windows[0][2]
    else:  # a trace without the annotation: everything seen
        every = [t for ev, _ in devices.values() for _, a, b in ev for t in (a, b)]
        lo, hi = (min(every), max(every)) if every else (0.0, 0.0)
    chips = max(len(devices), 1)
    rows, uncovered = {}, {}
    busy = total = covered_s = 0.0
    for _, (events, metadata) in sorted(devices.items()):
        clipped = [(m, *c) for m, a, b in events if (c := _clip(a, b, lo, hi))]
        busy += sum(b - a for a, b in _union([(a, b) for _, a, b in clipped]))
        seconds, leaf_runs = {}, {}
        for m, self_ns, a, b in _self_times(clipped):
            seconds[m] = seconds.get(m, 0.0) + self_ns * 1e-9
            if self_ns >= (b - a) * (1 - 1e-9):  # contained nothing
                leaf_runs[m] = leaf_runs.get(m, 0) + 1
        kinds = {
            m: classify(metadata.get(m, {}).get("stats", {}).get("tf_op") or "")
            for m in seconds
        }
        # Operations with no root in their path take their program's.
        program_roots = {}
        for m, kind in kinds.items():
            if kind[0]:
                program = metadata[m]["stats"].get("program_id")
                program_roots.setdefault(program, set()).add(kind[0])
        for m, s in seconds.items():
            meta = metadata.get(m, {"name": str(m), "stats": {}})
            stats = meta["stats"]
            root, direction, module, scope = kinds[m]
            rooted = root is not None
            if not rooted:
                agreed = program_roots.get(stats.get("program_id"), ())
                root = next(iter(agreed)) if len(agreed) == 1 else "other"
            total += s
            if rooted and (scope != "(none)" or module != "-"):
                covered_s += s
            else:
                entry = uncovered.setdefault(
                    _short(meta), [0.0, stats.get("tf_op") or ""]
                )
                entry[0] += s
            row = rows.setdefault((root, rooted, direction, module, scope), {
                "seconds": 0.0, "flops": 0, "bytes_accessed": 0, "runs": 0,
            })
            row["seconds"] += s
            runs = leaf_runs.get(m, 0)
            row["runs"] += runs
            row["flops"] += runs * int(stats.get("flops") or 0)
            row["bytes_accessed"] += runs * int(stats.get("bytes_accessed") or 0)
    table = [
        dict(zip(("root", "rooted", "direction", "module", "scope"), key),
             **{k: v / chips for k, v in row.items()})
        for key, row in rows.items()
    ]
    table.sort(key=lambda r: -r["seconds"])
    return {
        "xplane": os.path.abspath(path),
        "window_s": (hi - lo) * 1e-9,
        "chips": len(devices),
        "busy_s": busy * 1e-9 / chips,
        "device_self_s": total / chips,
        "coverage": covered_s / total if total else None,
        "rows": table,
        # [category and shape, seconds, the tf_op of one such operation]
        "uncovered": sorted(
            ([k, v[0] / chips, v[1]] for k, v in uncovered.items()),
            key=lambda kv: -kv[1],
        )[:20],
    }


# ------------------------------------------------------- what the readers use
def bucket(row: dict) -> str:
    """The layer a row is booked to. The four ``*_step_ms`` metrics and the
    remainder (``pool`` + ``unattributed``) partition a root's device time."""
    scope = row["scope"]
    if not row["rooted"]:  # the root is its program's, not its path's
        return "unattributed"
    if scope.startswith("hydragnn.agg."):
        return "agg"
    if scope == "hydragnn.gather":
        return "gather"
    if scope == "hydragnn.pool":
        return "pool"
    # hydragnn.loss, .optimizer, .grad_sync, or no module at all
    return "model_dense" if row["module"] != "-" else "optimizer"


_TABLES: dict = {}


def table(run):
    """The by-scope table of a traced run, or None where there is no trace.
    The first call of a run writes ``scopes.json`` and prints the summary."""
    try:
        path = find_xplane(run.cell.trace_dir)
    except FileNotFoundError:
        return None
    if path not in _TABLES:
        t0 = time.perf_counter()
        steps = run.facts.get("steps") or 0
        result = by_scope(path)
        result = dict(result, steps=steps, step_ms=step_split(result, steps))
        result["bytes_a_step"] = bytes_split(result, run.facts)
        _TABLES[path] = result
        out = os.path.join(run.cell.out_dir, "scopes.json")
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print("[graftbench] scopes: " + json.dumps({
            "coverage": result["coverage"], "step_ms": result["step_ms"],
            "bytes_a_step": result["bytes_a_step"],
            "device_step_ms_by_host_span": device_step_ms.read(run),
            "top": [
                [r["root"], r["direction"], r["module"], r["scope"],
                 round(r["seconds"], 6)] for r in result["rows"][:10]
            ],
            "uncovered": result["uncovered"][:5], "file": out,
            "read_s": round(time.perf_counter() - t0, 1),
        }), flush=True)
    return _TABLES[path]


def step_split(result: dict, steps: int) -> dict:
    """Milliseconds a step of the train root by layer; they add up to the
    train root's device self time a step (``train_root``)."""
    out = dict.fromkeys(
        ("agg", "gather", "model_dense", "optimizer", "pool", "unattributed"), 0.0
    )
    if not steps:
        return {}
    for row in result["rows"]:
        if row["root"] == "train":
            out[bucket(row)] += 1e3 * row["seconds"] / steps
    out["train_root"] = sum(out.values())
    return out


def bytes_split(result: dict, facts: dict) -> dict:
    """For the gathers and the aggregation of the train root, forward and
    backward, a step and a chip: device milliseconds, the bytes
    ``graftbench/flops.py`` counts by hand (``facts["step_bytes"]``) and
    XLA's ``bytes_accessed``, each also as the GB/s it stands for. Side by
    side so that the two figures can be compared by eye; only the counted
    one is read by a metric."""
    steps, chips = facts.get("steps"), facts.get("chips", 1)
    counted = facts.get("step_bytes")
    out = {}
    if not steps or not counted:
        return out
    for row in result["rows"]:
        layer = bucket(row)
        if row["root"] != "train" or layer not in counted:
            continue
        cell = out.setdefault(layer, {}).setdefault(
            row["direction"], {"ms": 0.0, "xla_bytes": 0.0}
        )
        cell["ms"] += 1e3 * row["seconds"] / steps
        cell["xla_bytes"] += row["bytes_accessed"] / steps
    for layer, directions in out.items():
        for direction, cell in directions.items():
            cell["counted_bytes"] = counted[layer][direction] / chips
            for key in ("counted", "xla"):
                cell[key + "_gb_s"] = cell[key + "_bytes"] / cell["ms"] / 1e6
    return out


def step_ms(run, layer: str):
    """What ``<layer>_step_ms`` reads; None where the trace holds nothing of
    that layer (a program without the scopes, as before PR 23)."""
    result = table(run)
    return None if result is None else result["step_ms"].get(layer) or None


def roofline_share(run, layer: str):
    """What ``<layer>_roofline_share`` reads: the bytes a train step of
    ``layer`` needs (``graftbench/flops.py``: counted by hand from real rows,
    forward and backward, a chip) over what the chip's HBM could move in the
    device time the layer took (``<layer>_step_ms``). Bytes bound both
    layers: a gather has no operations and a reduction one an element. None
    where ``<layer>_step_ms`` is. Not clamped: a reading over 100% means the
    count is wrong, not the chip fast."""
    ms = step_ms(run, layer)
    counted = (run.facts.get("step_bytes") or {}).get(layer)
    if not ms or not counted or not run.peaks:
        return None
    per_chip = sum(counted.values()) / run.facts.get("chips", 1)
    return 100.0 * per_chip / (ms * 1e-3 * run.peaks["hbm_bytes_per_s"])


if __name__ == "__main__":
    import sys

    target = sys.argv[1]
    reduced = by_scope(
        target if target.endswith(".pb") else find_xplane(target)
    )
    print(f"window {reduced['window_s']:.6f}s, {reduced['chips']} chip(s), "
          f"busy {reduced['busy_s']:.6f}s, coverage "
          f"{100 * (reduced['coverage'] or 0):.1f}%")
    print("seconds\troot\tdir\tmodule\tscope\tflops\tbytes_accessed")
    for r in reduced["rows"]:
        print(f"{r['seconds']:.6f}\t{r['root']}\t{r['direction']}\t{r['module']}"
              f"\t{r['scope']}\t{r['flops']:.4g}\t{r['bytes_accessed']:.4g}")
    for name, seconds, tf_op in reduced["uncovered"]:
        print(f"uncovered\t{seconds:.6f}\t{name}\t{tf_op}")
