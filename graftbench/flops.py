"""Operations and bytes the algorithm needs, from real shapes.

Counted from the REAL node, edge and graph counts of the batches a window
ran and the configuration's widths -- not from padded shapes and not from
XLA's ``cost_analysis``: work done on padding rows, recomputation and layout
copies are the system's choices and do not count as useful work. A multiply-
add is two operations. Elementwise work is counted at one operation an
element and pass.

What belongs to one model family (its conv layer, its encoder) is counted in
``graftbench/families/<model_type>.py``'s ``counts``; the pool and the heads,
which every family shares, are counted here.

**The byte convention** (written here once; the family files use the helpers
below and add no rule of their own). Float32, real rows, each operand read
once and each result written once by a plain implementation: a bound on the
memory roof from below. Bytes are kept apart by the classes the trace splits
device time into (``xplane_scopes.bucket``): ``gather``, ``agg`` and
``rest``, forward and backward.

* A row gather ``[N, w] -> [E, w]`` reads ``E`` rows and writes ``E`` rows;
  its backward, a scatter-add, reads ``E`` rows and writes ``N``.
* A segment reduction reads its ``[E, w]`` messages once a pass the algorithm
  needs and writes ``[N, w]`` a result; its backward reads the ``[N, w]``
  cotangent and writes ``[E, w]``. (What a minimum's or a variance's backward
  reads again of the forward is left out: the count stays a lower bound.)
* An index array counts 4 bytes a row each time it is used.
* ``rest`` (Dense layers, batch norm, elementwise passes, pool, heads): the
  backward of a matmul costs twice its forward (a gradient for the input and
  one for the weight), in operations and in bytes, so a train step is three
  forwards. The first conv layer's input needs no gradient; that is ignored
  for ``rest`` (its width is the input feature width, a handful) and honoured
  for a gather of the raw input (no scatter-add runs there).

``gather_roofline_share`` and ``agg_roofline_share`` read the ``gather`` and
``agg`` bytes of a step; ``model_flops_util`` reads the operations; nothing
reads ``rest`` yet.
"""

from __future__ import annotations

from graftbench import families

B = 4  # bytes a float32, and an index
SCOPES = ("gather", "agg", "rest")


def part(ops: int, fwd: int, bwd: int | None = None, scope: str = "rest") -> dict:
    """One counted piece: forward operations, forward and backward bytes,
    the class its device time is booked to."""
    return {"ops": ops, "scope": scope, "fwd": fwd,
            "bwd": 2 * fwd if bwd is None else bwd}


def dense(rows: int, fan_in: int, fan_out: int) -> dict:
    return part(
        2 * rows * fan_in * fan_out + rows * fan_out,
        B * (rows * fan_in + fan_in * fan_out + rows * fan_out),
    )


def mlp(rows: int, dims) -> list:
    return [dense(rows, a, b) for a, b in zip(dims[:-1], dims[1:])]


def batch_norm(rows: int, width: int) -> dict:
    return part(4 * rows * width, B * 2 * rows * width)


def gather(table_rows: int, rows: int, width: int, grad: bool = True) -> dict:
    """``table[index]``: ``[table_rows, width] -> [rows, width]``. ``grad`` is
    False where no gradient flows back into the table."""
    return part(
        0, B * (2 * rows * width + rows),
        B * (rows * width + rows + table_rows * width) if grad else 0,
        scope="gather",
    )


def segment_reduce(rows: int, segments: int, width: int, ops: int = 0,
                   grad: bool = True) -> dict:
    """One pass of a reduction ``[rows, width] -> [segments, width]`` by an
    index. ``grad`` is False for a pass under ``stop_gradient``."""
    return part(
        ops, B * (rows * width + rows + segments * width),
        B * (segments * width + rows + rows * width) if grad else 0,
        scope="agg",
    )


def _stack(arch: dict, nodes: int, edges: int, graphs: int) -> list:
    """The counted pieces of one forward pass of the stack ``arch``."""
    parts, enc = families.load(arch["model_type"]).counts(arch, nodes, edges)
    parts = list(parts)
    parts.append(part(nodes * enc, B * (nodes * enc + graphs * enc)))  # pool
    heads_cfg = arch["output_heads"]
    for head_kind, dim in zip(arch["output_type"], arch["output_dim"]):
        if head_kind == "graph":
            g = heads_cfg["graph"]
            shared = [enc] + [g["dim_sharedlayers"]] * g["num_sharedlayers"]
            own = [shared[-1]] + list(g["dim_headlayers"][: g["num_headlayers"]]) + [dim]
            parts += mlp(graphs, shared) + mlp(graphs, own)
        else:
            nd = heads_cfg["node"]
            parts += mlp(
                nodes, [enc] + list(nd["dim_headlayers"][: nd["num_headlayers"]]) + [dim]
            )
    return parts


def total(parts) -> dict:
    """``{"ops", "bytes": {class: {"fwd", "bwd"}}}`` of a list of pieces."""
    out = {"ops": 0, "bytes": {s: {"fwd": 0, "bwd": 0} for s in SCOPES}}
    for p in parts:
        out["ops"] += p["ops"]
        out["bytes"][p["scope"]]["fwd"] += p["fwd"]
        out["bytes"][p["scope"]]["bwd"] += p["bwd"]
    return out


def forward(arch: dict, nodes: int, edges: int, graphs: int) -> dict:
    """One forward pass of the stack ``arch`` (the completed ``Architecture``
    block) over ``nodes``/``edges``/``graphs`` real rows: operations, and
    bytes by class."""
    t = total(_stack(arch, nodes, edges, graphs))
    return {"ops": t["ops"], "bytes": {s: b["fwd"] for s, b in t["bytes"].items()}}


def train_step(arch: dict, nodes: int, edges: int, graphs: int) -> dict:
    """Forward and backward: three forwards in operations (see the module
    docstring); bytes by class and direction."""
    t = total(_stack(arch, nodes, edges, graphs))
    return {"ops": 3 * t["ops"], "bytes": t["bytes"]}
