"""Programs compiled HERE for a described TPU v5e (no chip attached): what the
chip's compiler makes of the main path's shapes, which the CPU compiler of
the other tests cannot show (tiled layouts, what gets written out between
fusions). Shapes and instruction counts, never a time.

Every compile for the described chip lives in this one file, and the topology
is described inside a fixture: only the worker that runs this file loads the
TPU's library (the `on-chip-measurement` guide, section 2)."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sorts(text, scope):
    """The ``sort`` instructions whose ``op_name`` holds ``scope``."""
    return [
        line for line in text.splitlines()
        if re.search(r"\ssort\(", line.split("metadata=")[0]) and scope in line
    ]


def _backward_scatters(text, scope):
    """The ``scatter`` instructions of the backward pass under ``scope``."""
    return [
        line for line in text.splitlines()
        if re.search(r"\sscatter\(", line.split("metadata=")[0])
        and re.search(rf"transpose\([^\"]*{re.escape(scope)}", line)
    ]


def pytest_gatv2_conv_at_cell_size_has_no_rank3_edge_array(one_chip, monkeypatch):
    """One ``GATv2Conv``, forward and backward, at the shapes of the cell
    ``gatv2_h64x6_md17like.train_b512`` (11264 × 215552, six heads of 64) on
    the sorted/CSR route the chip takes: the row gathers come out as
    ``f32[215552,384]``, the backward holds exactly two scatter-adds into
    ``f32[11264,384]``, and no array with a ``[6,64]`` row or a transposed
    ``[215552,384]{0,1}`` is written anywhere (PERF.md §6, PR 24: a reshape
    inside the per-head reduce makes the chip's compiler write both).

    PR 46, the receiver-side gathers' backward (``aggregate.gather_sorted``):
    of those two scatter-adds only the senders' has its indices sorted for it
    (ONE ``sort`` under ``hydragnn.gather``; ``x_dst[receivers]`` goes back as
    a scatter-add told its ids are sorted), and no scatter into
    ``f32[11264,6]`` is left under that scope: ``denom[receivers]`` goes back
    down the prefix sums, as the denominators came."""
    from hydragnn_tpu.models.convs import GATv2Conv

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    n, e, h, f = 11264, 215552, 6, 64
    conv = GATv2Conv(out_dim=f, heads=h)

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    small = (
        jnp.zeros((8, h * f)), jnp.zeros((16,), jnp.int32),
        jnp.zeros((16,), jnp.int32), None, jnp.ones((16,), bool),
        jnp.ones((8,), bool),
    )
    params = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: conv.init(jax.random.PRNGKey(0), *small)),
    )

    def loss(params, x, senders, receivers, edge_mask, node_mask, row_ptr, key):
        out = conv.apply(
            params, x, senders, receivers, None, edge_mask, node_mask,
            train=True, row_ptr=row_ptr, rngs={"dropout": key},
        )
        return (out * out).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, shaped((n, h * f)), shaped((e,), jnp.int32),
        shaped((e,), jnp.int32), shaped((e,), jnp.bool_),
        shaped((n,), jnp.bool_), shaped((n + 1,), jnp.int32),
        shaped((2,), jnp.uint32),
    ).compile().as_text()

    rank3 = re.search(rf"\[\d+,{h},{f}\]", text)
    assert not rank3, f"an array with [6,64] rows is back: {rank3.group(0)}"
    assert f"[{e},{h * f}]{{0,1" not in text, "a transposed copy of the edge rows"
    entry = text[text.index("ENTRY"):]
    moved = [
        (m.group(1), m.group(2))
        for m in re.finditer(
            r"= f32\[([\d,]+)\]\S* fusion\(.*op_name=\"[^\"]*hydragnn\.gather/([\w-]+)\"",
            entry,
        )
    ]
    wide = [(shape, op) for shape, op in moved if shape.endswith(f",{h * f}")]
    assert sorted(wide) == sorted(
        [(f"{e},{h * f}", "gather")] * 2 + [(f"{n},{h * f}", "scatter-add")] * 2
    ), moved
    # PR 32, the in-cell bypass: the 384-wide weighted sum is one scatter-add
    # told the ids are sorted; the 6-wide denominators keep the prefix sums
    # (a reduce-window over [., ., 6]-wide chunks is still in the program).
    summed = [
        line for line in text.splitlines()
        if re.search(rf"= f32\[{n},{h * f}\]\S* scatter\(", line)
        and "hydragnn.agg.sum.scatter_sorted" in line
    ]
    assert len(summed) == 1, summed
    assert len(_sorts(text, "hydragnn.gather")) == 1, _sorts(text, "hydragnn.gather")
    back = [
        re.search(r"= (f32\[[\d,]+\])", line).group(1)
        for line in _backward_scatters(text, "hydragnn.gather")
    ]
    assert back == [f"f32[{n},{h * f}]"] * 2, back
    assert not _sorts(text, "hydragnn.agg.sum.scatter_sorted")
    assert "hydragnn.agg.sum.csr" in text
    assert not re.search(rf"f32\[\d+,\d+,{h * f}\]\S* reduce-window\(", text)
    assert re.search(r"\sreduce-window\(", text), "the narrow sum's cumsum is gone"


def pytest_painn_block_at_cell_size_keeps_the_vector_state_flat(one_chip, monkeypatch):
    """One ``PaiNNBlock`` (message + update), forward and backward, at the
    shapes of the cell ``painn_f128.train_b512`` (11264 × 215552, F 128, 20
    basis functions) on the sorted/CSR route the chip takes: the two sources
    come out as ``f32[215552,384]`` row gathers with two scatter-adds into
    ``f32[11264,384]`` behind them, the two states are summed as ONE
    ``[215552,512]`` array, and no array with a ``[3,128]`` row or a
    transposed ``[215552,384]{0,1}`` is written anywhere: ``v`` stays flat
    ``[·, 3F]``, xyz-major, from the gather to what the backward saves."""
    from hydragnn_tpu.models.painn import EdgeGeometry, PaiNNBlock

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    n, e, f, radial = 11264, 215552, 128, 20
    block = PaiNNBlock(f)

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    small = (
        jnp.zeros((8, f)), jnp.zeros((8, 3 * f)),
        EdgeGeometry(jnp.zeros((16, radial)), jnp.zeros((16, 1)), jnp.zeros((16, 3))),
        jnp.zeros((16,), jnp.int32), jnp.zeros((16,), jnp.int32),
    )
    params = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), *small)),
    )

    def loss(params, s, v, basis, cutoff, unit, senders, receivers, row_ptr):
        s, v = block.apply(
            params, s, v, EdgeGeometry(basis, cutoff, unit), senders, receivers,
            row_ptr,
        )
        return (s * s).sum() + (v * v).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        params, shaped((n, f)), shaped((n, 3 * f)), shaped((e, radial)),
        shaped((e, 1)), shaped((e, 3)), shaped((e,), jnp.int32),
        shaped((e,), jnp.int32), shaped((n + 1,), jnp.int32),
    ).compile().as_text()

    rank3 = re.search(rf"\[\d+,3,{f}\]", text)
    assert not rank3, f"an array with [3,128] rows: {rank3.group(0)}"
    assert f"[{e},{3 * f}]{{0,1" not in text, "a transposed copy of the edge rows"
    entry = text[text.index("ENTRY"):]
    moved = [
        (m.group(1), m.group(2))
        for m in re.finditer(
            r"= f32\[([\d,]+)\]\S* fusion\(.*op_name=\"[^\"]*hydragnn\.gather/([\w-]+)\"",
            entry,
        )
    ]
    assert sorted(moved) == sorted(
        [(f"{e},{3 * f}", "gather")] * 2 + [(f"{n},{3 * f}", "scatter-add")] * 2
    ), moved
    # PR 32: that 512-wide sum is ONE scatter-add told the ids are sorted,
    # under the arm's own name, and the prefix route is gone from the block:
    # no reduce-window (the chunked cumsum's lowering) over [E, 512] rows in
    # any form, no [512, 512, 4, 128] chunks, nothing under ``agg.sum.csr``.
    wide = "hydragnn.agg.sum.scatter_sorted"
    sums = [
        line for line in text.splitlines()
        if re.search(rf"= f32\[{n},{4 * f}\]\S* scatter\(", line)
    ]
    assert len(sums) == 1 and wide in sums[0], sums
    assert "unique_indices=true" not in sums[0]  # a run is many rows of one id
    # Told the ids are sorted: the chip's compiler sorts the indices of every
    # OTHER scatter itself (and writes ``indices_are_sorted=true`` on all of
    # them afterwards), so what the flag shows as is NO sort under the sum's
    # scope, where the senders' scatter-adds have theirs.
    assert _sorts(text, "hydragnn.gather") and not _sorts(text, wide)
    assert re.search(rf"f32\[{e},{4 * f}\]\S* fusion\(.*{re.escape(wide)}", entry)
    assert "hydragnn.agg.sum.csr" not in text
    windows = [
        line for line in text.splitlines()
        if re.search(r"\sreduce-window\(", line.split("metadata=")[0])
    ]
    assert not windows, windows[:2]
    assert f"f32[{e // 512},512,4,128]" not in text


def _combiners(text):
    """{computation name: its body} of an HLO module's text."""
    return {
        m.group(1): m.group(2)
        for m in re.finditer(r"^%?([\w.-]+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M)
    }


@pytest.mark.parametrize("f", [256, 1], ids=["hidden_256", "input_layer_1"])
def pytest_pna_conv_at_cell_size_scans_its_extrema_in_one_kernel(one_chip, monkeypatch, f):
    """One ``PNAConv``, forward and backward, at the large bucket of the cell
    ``pna_multihead_h256.train_b512`` (14336 × 268288 since PR 49: a rung of
    the worst case 18944 × 401920, whole kernel blocks like it; a hidden
    layer's 256 columns, the input layer's one) on the CSR route the chip takes: min and
    max come from ONE Mosaic kernel in the forward and their cotangents go
    down the rows in ONE in the backward, both under
    ``hydragnn.agg.extrema.pallas_csr``; no ``[E, f]`` row gather is left
    under that scope; and no scatter that combines by minimum or maximum is
    left anywhere (the one scatter into ``f32[14336,f]`` that stays is the
    centered sum of squares of ``std``).

    PR 46: of the two backward scatter-adds into ``f32[14336,256]`` under
    ``hydragnn.gather`` only the senders' is undeclared (``x[receivers]``
    goes back told its ids are sorted; at the shape before PR 49 the compiler
    sorted the senders' indices itself, at this one it does not); the input
    layer's one column goes back down the prefix sums, so one scatter is left
    there, the senders'."""
    from hydragnn_tpu.models.convs import PNAConv
    from hydragnn_tpu.ops import segment as seg
    from hydragnn_tpu.telemetry import scopes

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    n, e = 14336, 268288
    conv = PNAConv(out_dim=256, deg_avg_log=2.5, deg_avg_lin=14.0, edge_dim=1)

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    small = (
        jnp.zeros((8, f)), jnp.zeros((16,), jnp.int32),
        jnp.zeros((16,), jnp.int32), jnp.zeros((16, 1)), jnp.ones((16,), bool),
        jnp.ones((8,), bool),
    )
    params = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: conv.init(jax.random.PRNGKey(0), *small)),
    )

    def loss(params, x, senders, receivers, edge_attr, edge_mask, node_mask, row_ptr):
        out = conv.apply(
            params, x, senders, receivers, edge_attr, edge_mask, node_mask,
            row_ptr=row_ptr,
        )
        return (jnp.where(node_mask[:, None], out, 0.0) ** 2).sum()

    # The kernel is interpreted wherever the step's platform is not the TPU:
    # this program is for the described chip.
    with seg.platform_override("tpu"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, shaped((n, f)), shaped((e,), jnp.int32),
            shaped((e,), jnp.int32), shaped((e, 1)), shaped((e,), jnp.bool_),
            shaped((n,), jnp.bool_), shaped((n + 1,), jnp.int32),
        ).compile().as_text()

    kernels = [
        re.search(r'op_name="([^"]*)"', line).group(1)
        for line in text.splitlines() if "tpu_custom_call" in line
    ]
    scope = scopes.agg("extrema", "pallas_csr")
    assert len(kernels) == 2 and all(scope in k for k in kernels), kernels
    assert sorted("transpose(" in k for k in kernels) == [False, True], kernels
    assert scopes.agg("extrema", "xla") not in text
    gathered = [
        line for line in text.splitlines()
        if re.search(rf"= f32\[{e}(,{f})?\]\S* gather\(", line) and scope in line
    ]
    assert not gathered, gathered[:2]
    bodies = _combiners(text)
    scatters = [
        (m.group(1), bodies[m.group(2)])
        for m in re.finditer(
            r"= (\w+\[[\d,]*\])\S* scatter\(.*to_apply=%?([\w.-]+)", text
        )
    ]
    assert scatters, "no scatter compiled: nothing was checked"
    assert not [s for s, body in scatters if re.search(r"(min|max)imum\(", body)], scatters
    kept = f"f32[{n},{f}]" if f > 1 else f"f32[{n}]"  # one column comes out rank 1
    assert [s for s, body in scatters if s == kept and " add(" in body], scatters
    adds = [  # the bundle's own (the gathers' backward scatter-adds beside them)
        line for line in text.splitlines()
        if re.search(r"\sscatter\(", line.split("metadata=")[0])
        and "hydragnn.agg.stats" in line
    ]
    # PR 32: 256 columns are wide, so the sums are a scatter-add too, beside
    # the squares', under ``stats.scatter_sorted`` and with no prefix sums
    # left; the input layer's one column keeps ``stats.csr`` and its cumsum.
    # Neither scatter-add has its indices sorted for it (both are told).
    wide, narrow = scopes.agg("stats", "scatter_sorted"), scopes.agg("stats", "csr")
    # (float32: the extrema's backward has an int32 cumsum of its own)
    windows = re.search(r"= f32\[[\d,]+\]\S* reduce-window\(", text)
    if f > 1:
        assert len(adds) == 2 and wide in text and narrow not in text, adds
        assert not windows, windows.group(0)
    else:
        assert len(adds) == 1 and narrow in text and wide not in text, adds
        assert windows, "the one-column sum's cumsum is gone"
    assert not _sorts(text, "hydragnn.agg.stats")
    # At 18944 × 401920 (the shape before PR 49) the senders' undeclared
    # scatter-add had its indices sorted for it (one ``sort`` under the
    # gather's scope); at this shape the chip's compiler sorts for none.
    assert not _sorts(text, scopes.GATHER), _sorts(text, scopes.GATHER)
    back = _backward_scatters(text, scopes.GATHER)
    assert len(back) == (2 if f > 1 else 1), back
    assert all(re.search(rf"= f32\[{n}(,{f})?\]", line) for line in back), back


def pytest_lfm2_attention_at_cell_size_has_no_n_by_n_array(one_chip):
    """LFM2's attention core, forward and backward, at the shapes of the cell
    ``lfm2_8b_a1b_ep4.train_seq1k_b4`` (4 sequences of 1024 tokens in a bucket
    of 4160 nodes, 32 query and 8 key-value heads of 64) on the route the chip
    takes: the complete causal graph of a sequence is 524,800 edges and is
    never materialised -- no array of the lowered program has two axes of the
    node count (4160, or the 4608 it is padded to for the kernel's blocks),
    and the scores live inside the flash kernel's calls."""
    from hydragnn_tpu.models.token_attention import ATTN_BLOCK, segment_causal_attention
    from hydragnn_tpu.ops.segment import platform_override

    n, h, kv, hd = 4160, 32, 8, 64
    padded = n + -n % ATTN_BLOCK

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, node_graph):
        out = segment_causal_attention(q, k, v, node_graph)
        return (out * out).sum()

    with platform_override("tpu"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shaped((n, h, hd)), shaped((n, kv, hd)), shaped((n, kv, hd)),
            shaped((n,), jnp.int32),
        ).compile().as_text()
    shapes = set(re.findall(r"\w+\[([\d,]+)\]", text))
    square = [
        s for s in shapes
        if sum(int(d) in (n, padded) for d in s.split(",")) >= 2
    ]
    assert not square, f"an array with two node axes: {square}"
    assert text.count("tpu_custom_call") >= 3  # forward, dq, dkv
    assert f"[1,{h},{padded},{hd}]" in text  # the kernel's rows, per query head


def pytest_served_attention_core_at_cell_size_is_the_block_range_kernel(one_chip):
    """The attention core at the shape of the cell
    ``mistral_small4_ep8.serve_score_docs_c4``'s commonest rung (15,872 rows,
    32 heads of 128, float32 rows): a call that is not differentiated is ONE
    Mosaic call, ``block_range_attention``, fed the rows a head at a time
    with nothing repeated, and no array with two row axes; under
    ``jax.grad`` the program holds the library's three kernels and not the
    new one."""
    from hydragnn_tpu.models.token_attention import segment_causal_attention
    from hydragnn_tpu.ops.segment import platform_override

    n, h, hd = 15872, 32, 128

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = (shaped((n, h, hd)),) * 3 + (shaped((n,), jnp.int32),)

    def loss(q, k, v, node_graph):
        out = segment_causal_attention(q, k, v, node_graph)
        return (out * out).sum()

    with platform_override("tpu"):
        forward = jax.jit(segment_causal_attention).lower(*rows).compile().as_text()
        backward = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*rows).compile().as_text()
    calls = [line for line in forward.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and "%block_range_attention" in calls[0]
    assert f"f32[{h},{n},{hd}]" in calls[0] and f"[1,{h},{n},{hd}]" not in forward
    for text in (forward, backward):
        shapes = set(re.findall(r"\w+\[([\d,]+)\]", text))
        assert not [s for s in shapes if s.split(",").count(str(n)) >= 2]
    kernels = re.findall(r"(%[\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", backward)
    assert len(kernels) == 3 and "block_range_attention" not in backward
    assert sorted(k.split("_")[0].split(".")[0] for k in kernels) == ["%flash"] * 3
    assert f"[1,{h},{n},{hd}]" in backward  # the library kernel's rows, per head


@pytest.mark.parametrize("cell", ["lfm2", "laguna"])
def pytest_lfm2_routed_experts_at_cell_size_are_grouped_matmuls(one_chip, cell):
    """One ``RoutedFFN``, forward and backward, at the 4160 nodes of the two
    token cells: LFM2's (8 of 32 experts held, 4 a token, SwiGLU 1792) and
    Laguna's (32 of 256, 8 a token, 512). Every row array is ``[6400, .]``
    (the rank's uniform share of 4160 rows times 1.5, in row tiles): no array
    of a row's width has the ``K N`` = 16640 / 33280 rows, in the straight
    line or in the loops of a step that overflows, and no ``conditional``
    holds a second program. The rows are multiplied as ragged groups by the
    grouped-matmul kernel (three projections forward and six calls backward
    in the straight line; three, and those nine, in the two loops' bodies),
    never as a dense ``[experts, rows, .]`` array. The way in is a gather of
    6400 rows, the way back (and the way in's backward) a scatter-add of
    those 6400 rows into the ``[4160, 2048]`` nodes: no scatter of wider
    updates is compiled (the decision: PERF.md section 6, PR 34)."""
    from hydragnn_tpu.models.lfm2 import LFM2Config
    from hydragnn_tpu.models.token_routed import RoutedFFN, _capacity
    from hydragnn_tpu.ops.segment import platform_override

    n, d = 4160, 2048
    f, held, experts, k = (1792, 8, 32, 4) if cell == "lfm2" else (512, 32, 256, 8)
    cfg = LFM2Config(
        layer_types=("conv",), num_dense_layers=0, intermediate_size=7168,
        moe_intermediate_size=f, num_experts=experts, num_experts_per_tok=k,
        num_experts_held=held, experts_offset=0, num_attention_heads=32,
        num_key_value_heads=8, head_dim=64, vocab_size=16384,
        token_minmax=(0.0, 16383.0),
    )
    layer = RoutedFFN(d, cfg)

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((8, d)), jnp.ones((8,), bool))
        ),
    )

    def loss(params, x, mask):
        out = layer.apply(params, x, mask)
        return (out * out).sum()

    with platform_override("tpu"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, shaped((n, d)), shaped((n,), jnp.bool_)
        ).compile().as_text()
    rows, cap = k * n, _capacity(k * n, held, experts)
    assert cap == 6400 and "conditional(" not in text
    for width in (d, f):
        assert re.search(rf"(?:f32|bf16)\[{cap},{width}\]", text)
        wide = re.search(rf"(?:f32|bf16|pred)\[{rows},{width}\]", text)
        assert not wide, f"a row array of every assignment: {wide.group(0)}"
    assert f"f32[{n},{k},{d}]" not in text
    dense = re.search(rf"f32\[(?:{held}|{experts}),(?:{rows}|{cap}),\d+\]", text)
    assert not dense, f"the experts' rows as a dense batch: {dense.group(0)}"
    calls = sorted(
        body.group(0).count("tpu_custom_call")
        for body in re.finditer(r"(?ms)^(?:ENTRY )?%[\w.\-]+ \(.*?^}", text)
        if "tpu_custom_call" in body.group(0)
    )
    assert calls == [3, 9, 9], calls  # a further pass forward; one backward; the entry
    shapes = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]*\])", text))
    updates = [
        shapes[m.group(2)]
        for m in re.finditer(r"= f32\[(\d+),\d+\]\S* scatter\(%\S+, %\S+, (%[\w.\-]+)\)", text)
    ]
    assert updates and set(updates) == {f"f32[{cap},{d}]"}, updates


def pytest_laguna_window_layers_at_cell_size_call_the_band_kernel(one_chip):
    """A sliding layer's attention core (64 query and 8 key-value heads of
    128, window 512) and a full layer's (48 heads), forward and backward, at
    the shapes of the cell ``laguna_xs2_ep8.train_seq4k_b1`` (one sequence of
    4096 tokens in a bucket of 4160 nodes) on the routes the chip takes: no
    array has two axes of the node count (4160, or what the kernels pad it
    to), so neither ``[N, N]`` nor ``[H, N, N]`` scores exist; the band runs
    the splash kernel (one call a key-value head, batched: its rows are
    ``[8, 8, padded, 128]``) and the triangle the flash kernel, and
    the band's kernels visit FEWER key blocks than the triangle's would: the
    grid of the band's forward is the 3 key blocks of 256 a query block that
    the window touches, not the 17 the sequence has."""
    from hydragnn_tpu.models.token_attention import ATTN_BLOCK, segment_causal_attention
    from hydragnn_tpu.ops.segment import platform_override

    n, kv, hd, window = 4160, 8, 128, 512

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def lowered(heads, w):
        def loss(q, k, v, node_graph):
            out = segment_causal_attention(q, k, v, node_graph, window=w)
            return (out * out).sum()

        with platform_override("tpu"):
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                shaped((n, heads, hd)), shaped((n, kv, hd)), shaped((n, kv, hd)),
                shaped((n,), jnp.int32),
            ).compile().as_text()

    block = ATTN_BLOCK
    padded = n + -n % block
    for heads, w in ((64, window), (48, None)):
        text = lowered(heads, w)
        shapes = set(re.findall(r"\w+\[([\d,]+)\]", text))
        square = [
            s for s in shapes
            if sum(int(d) in (n, padded) for d in s.split(",")) >= 2
        ]
        assert not square, f"an array with two node axes: {square}"
        assert text.count("tpu_custom_call") >= 3  # forward, dq, dkv
        if w is None:
            assert f"f32[1,{heads},{padded},{hd}]" in text  # the flash kernel's rows
            assert "splash" not in text
        else:
            assert f"f32[{kv},{heads // kv},{padded},{hd}]" in text
            assert "splash" in text and "flash_attention" not in text.replace(
                "splash_attention", ""
            )
            # The kernels' schedules (the mask is static, so they are
            # constants of the program): a query block visits the 512 / 512 +
            # 1 = 2 key blocks the band touches of the 9 the sequence has,
            # forward and dq; a key block as many query blocks, dkv.
            blocks, touched = padded // block, window // block + 1
            assert f"s8[1,{blocks},{touched}]" in text and f"s8[1,{touched},{blocks}]" in text
            assert f"s8[1,{blocks},{blocks}]" not in text and touched < blocks


def pytest_grouped_matmul_tiles_follow_the_weights(one_chip):
    """``RoutedFFN`` at the two cells' shapes after ``GMM_TILING`` follows the
    weights: Laguna's fine-grained layer (32 of 256 experts held, 8 a token,
    SwiGLU 512 wide: ``[6400, .]`` row arrays of 33280 assignments) compiles,
    forward and backward, to the grouped-matmul kernel's calls with no tile
    wider than a matrix; LFM2's (1792 wide) still calls the same kernel with
    the tiles it had."""
    from hydragnn_tpu.models import laguna, token_routed
    from hydragnn_tpu.ops.segment import platform_override

    assert token_routed._gmm_tiles(6400, 2048, 1792) == token_routed.GMM_TILING == (256, 1024, 1024)
    assert token_routed._gmm_tiles(6400, 1792, 2048) == token_routed.GMM_TILING
    assert token_routed._gmm_tiles(6400, 2048, 512) == (256, 1024, 512)
    assert token_routed._gmm_tiles(6400, 512, 2048) == (256, 512, 1024)
    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "graftbench", "configs", "laguna_xs2_ep8.json",
    )) as f:
        import json

        arch = json.load(f)["NeuralNetwork"]["Architecture"]
    cfg = laguna.LagunaConfig.from_arch(dict(arch, token_minmax=[0.0, 12543.0]), 5)
    n, d, f_, held, k = 4160, 2048, 512, 32, 8
    layer = token_routed.RoutedFFN(d, cfg)

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((8, d)), jnp.ones((8,), bool))
        ),
    )
    assert "expert_bias" not in params["params"]

    def loss(params, x, mask):
        out = layer.apply(params, x, mask)
        return (out * out).sum()

    with platform_override("tpu"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, shaped((n, d)), shaped((n,), jnp.bool_)
        ).compile().as_text()
    rows = token_routed._capacity(k * n, held, 256)  # [6400, .], not every assignment's 33280
    assert f"f32[{rows},{d}]" in text and f"f32[{rows},{f_}]" in text
    dense = re.search(rf"f32\[(?:{held}|256),{rows},\d+\]", text)
    assert not dense, f"the experts' rows as a dense batch: {dense.group(0)}"
    assert text.count("tpu_custom_call") >= 9
    assert f"f32[{held},{d},{f_}]" in text and f"f32[{held},{f_},{d}]" in text


def pytest_mellum_engine_at_the_guard_rung_fits_beside_every_expert(one_chip):
    """The serving engine's executable for Mellum2's block at the published
    widths and the cell ``mellum2_12b_l4.serve_score_docs_c4_v98k``'s GUARD
    rung (25,088 tokens: ``K N`` = 200,704 rows through a layer that holds
    all 64 experts, in ONE pass), two layers, one of each kind (the
    temporaries are a layer's, not the stack's: four layers read 4.02 GB):
    the compiler's buffer assignment stays under 4.5 GB beside the weights,
    the band is the splash kernel under its static mask (3 key blocks a query
    block), the triangle the block-range kernel, the experts the grouped
    matmul at tiles that divide 2304 (1152) and fit 896, and no loop over row
    passes is compiled."""
    import json

    import numpy as np

    from graftbench.drivers import serve_tokens
    from hydragnn_tpu.graphs.collate import GraphArena
    from hydragnn_tpu.graphs.sample import GraphSample
    from hydragnn_tpu.models import token_attention, token_routed
    from hydragnn_tpu.ops.segment import platform_override
    from hydragnn_tpu.serve.engine import _token_forward

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "graftbench", "configs", "mellum2_12b_l4.json",
    )) as f:
        config = json.load(f)
    arch = serve_tokens.completed_arch(config)
    arch.update(num_conv_layers=2, layer_types=["sliding_attention", "full_attention"])
    model, template, _ = serve_tokens.init_model(arch)
    n, tokens = 25088, 6144
    pos = np.zeros((tokens, 3), np.float32)
    pos[:, 0] = np.arange(tokens)
    document = GraphSample(x=np.zeros((tokens, 1), np.float32), pos=pos)
    batch = GraphArena([document] * 4).collate(
        np.arange(4), num_nodes_pad=n, num_edges_pad=8, num_graphs_pad=5, with_positions=True,
    )

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    with platform_override("tpu"):
        compiled = _token_forward(model).lower(
            shaped(template["params"]), shaped(template.get("batch_stats", {})), shaped(batch)
        ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 4.5e9, memory.temp_size_in_bytes
    text = compiled.as_text()
    rows, blocks = 8 * n, n // token_attention.ATTN_BLOCK
    assert text.count("tpu_custom_call") == 2 * 4  # a core and three grouped matmuls a layer
    assert f"f32[{rows},2304]" in text and f"f32[{rows},896]" in text
    # One pass: no loop carries a row array (the head's row blocks and the
    # top-k's are the loops there are).
    assert not [l for l in text.splitlines() if " while(" in l and f"[{rows}," in l]
    assert f"s8[1,{blocks},3]" in text and f"s8[1,{blocks},{blocks}]" not in text  # the band's schedule
    assert token_routed._gmm_tiles(rows, 2304, 896) == (256, 1152, 896)
    assert token_attention.band_key_blocks(n, 1024) == 1 + 2 + 3 * (blocks - 2)


def pytest_jamba_engine_at_the_guard_rung_holds_no_state_history(one_chip):
    """The serving engine's executable for AI21-Jamba2-3B WHOLE (28 layers,
    every width as published, the head tied) at the cell
    ``jamba2_3b.serve_score_pages_c4``'s GUARD rung (16,896 tokens, four
    pages of 4096 and the padding rows' block): no ``[N, 5120, 16]`` array of
    any type anywhere in the optimized program (the state history: 5.5 GB an
    array), one scan kernel a Mamba layer and one block-range kernel an
    attention layer, the class head's logits a block of 512 rows, and the
    compiler's buffer assignment under 2.0 GB beside the 12.12 GB of weights
    (ISSUE 45's trigger is 15.0 GB for the two together)."""
    import json

    import numpy as np

    from graftbench.drivers import serve_tokens
    from hydragnn_tpu.graphs.collate import GraphArena
    from hydragnn_tpu.graphs.sample import GraphSample
    from hydragnn_tpu.ops import selective_scan
    from hydragnn_tpu.ops.segment import platform_override
    from hydragnn_tpu.serve.engine import _token_forward

    with open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "graftbench", "configs", "jamba2_3b.json",
    )) as f:
        config = json.load(f)
    arch = serve_tokens.completed_arch(config)
    model, template, _ = serve_tokens.init_model(arch)
    assert model.num_conv_layers == 28 and "head_0" not in template["params"]
    n, tokens = 16896, 4096
    pos = np.zeros((tokens, 3), np.float32)
    pos[:, 0] = np.arange(tokens)
    document = GraphSample(x=np.zeros((tokens, 1), np.float32), pos=pos)
    batch = GraphArena([document] * 4).collate(
        np.arange(4), num_nodes_pad=n, num_edges_pad=8, num_graphs_pad=5, with_positions=True,
    )

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    with platform_override("tpu"):
        compiled = _token_forward(model).lower(
            shaped(template["params"]), shaped(template.get("batch_stats", {})), shaped(batch)
        ).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 12.1e9
    assert memory.temp_size_in_bytes < 2.0e9, memory.temp_size_in_bytes
    text = compiled.as_text()
    assert not re.search(rf"\[{n},5120,16\]|\[{n},16,5120\]|\[{n},81920\]", text)
    assert text.count("tpu_custom_call") == 26 + 2
    assert text.count('"selective_scan"') + text.count("selective_scan") >= 26
    assert n % selective_scan.SCAN_CHUNK == 0 and 5120 % selective_scan.CHANNEL_BLOCK == 0
    assert "f32[512,65536]" in text and f"f32[{n},65536]" not in text


def _gatv2_cell(one_chip):
    """(model, batch, shaped) of ``gatv2_h64x6_md17like.train_b512``: the whole
    GATv2 model and ``batch(lead, make)``, the cell's one shape (11264 ×
    215552, 513 graph slots) under ``lead`` leading dimensions, each array
    made by ``make(shape, dtype)``."""
    import json

    import numpy as np

    from hydragnn_tpu.graphs.batch import GraphBatch
    from hydragnn_tpu.models.create import create_model_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "graftbench/configs/gatv2_h64x6_md17like.json")) as f:
        arch = json.load(f)["NeuralNetwork"]["Architecture"]
    # What config completion reads off the cell's data (one node feature, one
    # graph target, 21 atoms a molecule).
    arch.update(input_dim=1, output_dim=[1], output_type=["graph"], num_nodes=21)
    model = create_model_config(config=arch)
    n, e, g = 11264, 215552, 513

    def batch(lead, make):
        def arr(shape, dtype=np.float32):
            return make(lead + shape, dtype)

        return GraphBatch(
            node_features=arr((n, 1)), edge_features=arr((e, 1)),
            senders=arr((e,), np.int32), receivers=arr((e,), np.int32),
            node_graph=arr((n,), np.int32), node_mask=arr((n,), np.bool_),
            edge_mask=arr((e,), np.bool_), graph_mask=arr((g,), np.bool_),
            targets=(arr((g, 1)),), row_ptr=arr((n + 1,), np.int32),
            graph_ptr=arr((g + 1,), np.int32), num_graphs_pad=g,
        )

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return model, batch, shaped


def pytest_gatv2_initializer_at_cell_size_is_one_program_of_draws(one_chip, monkeypatch):
    """``init_model_variables`` for the whole GATv2 model at the cell's shape,
    for the chip: ONE program, and the draws alone. The forward that
    ``model.init`` traces is dead code in it: no kernel call, no matrix
    product, no scatter or gather over the 215,552 edges, and next to no
    temporary memory (op by op it was some two hundred programs, the forward
    run as well)."""
    from hydragnn_tpu.models import create
    from hydragnn_tpu.ops.segment import platform_override

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    model, batch, shaped = _gatv2_cell(one_chip)
    with platform_override("tpu"):  # the library's own program, the seed an argument
        compiled = create._init_program.lower(
            model, batch((), shaped), shaped((), jnp.int32)
        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert not re.search(r"\s(dot|convolution|scatter|gather|sort)\(", text)
    assert "215552" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6


def pytest_gatv2_counted_scan_at_cell_size_loops_on_its_argument(one_chip, monkeypatch):
    """The scan path's program (``make_train_epoch_scan``) for the whole GATv2
    model of ``gatv2_h64x6_md17like.train_b512`` over a stack of
    ``SCAN_CHUNK`` batches of the cell's one shape (11264 × 215552, 513 graph
    slots): ONE ``while`` whose condition compares the induction variable
    with the ``count`` PARAMETER, no constant (so no trip count is known to
    the compiler and a tail needs no program of its own), whose body holds
    the step (the two backward scatter-adds a layer into ``f32[11264,384]``),
    within the chip's memory."""
    from hydragnn_tpu.models.create import init_model_variables
    from hydragnn_tpu.ops.segment import platform_override
    from hydragnn_tpu.train.train_validate_test import SCAN_CHUNK
    from hydragnn_tpu.train.trainer import create_train_state, make_train_epoch_scan
    from hydragnn_tpu.utils.optimizer import select_optimizer

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    model, batch, shaped = _gatv2_cell(one_chip)
    opt = select_optimizer("AdamW", 1e-3)
    n = 11264
    state = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(lambda: create_train_state(
            model, init_model_variables(model, batch((), jnp.zeros)), opt
        )),
    )
    with platform_override("tpu"):
        lowered = make_train_epoch_scan(model, opt).lower(
            state, batch((SCAN_CHUNK,), shaped), shaped((), jnp.int32),
            shaped((2,), jnp.uint32),
        )
    compiled = lowered.compile()
    text = compiled.as_text()

    entry = text[text.index("ENTRY "):]
    assert re.search(r"%count\S* = s32\[\]\S* parameter\(", entry), (
        "no s32[] parameter named count: the trip count is not an argument"
    )
    loops = [
        line.split(", metadata=")[0] for line in entry.splitlines()
        if re.search(r"\swhile\(", line.split(", metadata=")[0])
    ]
    assert len(loops) == 1, len(loops)  # the steps; sorts and sums loop inside
    assert "known_trip_count" not in loops[0]
    name = re.search(r"condition=(%[\w.\-]+)", loops[0]).group(1)
    cond = text[text.index("\n" + name + " ("):]
    cond = cond[:cond.index("\n}")]
    assert "compare(" in cond and "direction=LT" in cond
    assert "constant(" not in cond, cond
    scatters = re.findall(rf"f32\[{n},384\]\S* scatter\(", text)
    assert len(scatters) >= 6, len(scatters)
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9


# What PR 39 left as it was: the programs of the configurations that share
# code with the new token family (``RoutedFFN``'s new score function, the
# attention core's new ``scale`` argument, the serving engine's token path).
# Each is compiled here for the described chip and its optimized HLO compared
# with the PARENT's (commit 2ffcd66) as a digest (``_hlo_digest``), computed
# by running ``_unchanged_programs`` against a checkout of the parent. PR 40
# holds the same digests: a differentiated call of the attention core is the
# library's three kernels as they were, kernel names included.
# PR 45 (which moved ``same_graph_shift``, gave ``base.py`` a tied class head
# and the engine two counters) holds them again and adds the two SERVED token
# programs it shares ``score_tokens`` and the engine's forward with, digests
# taken the same way from a checkout of ITS parent (a0116c7).
PARENT_HLO = {
    "lfm2_train": "11d9fe82695b0b73",
    "laguna_train": "f5a701e3addc01d1",
    "pna_serve": "6878f6b8702ee02c",
    "mistral_serve": "8efa33521d065d1a",
    "mellum_serve": "4706551e93fa6f41",
}


def _hlo_digest(text):
    """A digest of an optimized HLO text with what depends on WHERE the
    source stands taken out: the tables of file names and stack frames, each
    instruction's ``metadata={...}`` and a Pallas call's serialized body
    (its MLIR carries source locations)."""
    import hashlib

    kept, table = [], False
    for line in text.splitlines():
        if re.match(r"^(FileNames|FunctionNames|FileLocations|StackFrames)", line):
            table = True
        elif table and not line.strip():
            table = False
        elif not table:
            if '"custom_call_config"' in line:
                line = line[: line.index("backend_config=")]
            kept.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return hashlib.sha256("\n".join(kept).encode()).hexdigest()[:16]


def _unchanged_programs(one_chip):
    """{name: digest} of: a whole LFM2 and a whole Laguna model's loss and
    gradient (attention kernels, routed experts, the sigmoid router), and the
    serving engine's forward executable for a PNA model, each at small sizes
    the chip's kernels take (hidden 256, heads of 64 / 128, 1024 nodes)."""
    import numpy as np

    from hydragnn_tpu.graphs.collate import GraphArena
    from hydragnn_tpu.graphs.sample import GraphSample
    from hydragnn_tpu.models import create_model
    from hydragnn_tpu.models.create import init_model_variables
    from hydragnn_tpu.models.loss import multihead_rmse_loss
    from hydragnn_tpu.ops.segment import platform_override
    from hydragnn_tpu.serve import InferenceEngine

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    v, d, n = 512, 256, 1024
    heads = {"node": {"num_headlayers": 0, "dim_headlayers": [], "type": "mlp"}}
    token = dict(head_loss=("cross_entropy",), class_minmax=([0.0, v - 1.0],))
    models = {
        "lfm2_train": create_model(
            "LFM2", 1, d, (v,), ("node",), heads, [1.0], 2, token_arch=dict(
                layer_types=["conv", "full_attention"], num_dense_layers=0,
                intermediate_size=512, moe_intermediate_size=256, num_experts=8,
                num_experts_per_tok=2, num_experts_held=4, experts_offset=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                vocab_size=v, token_minmax=[0.0, v - 1.0],
            ), **token,
        ),
        "laguna_train": create_model(
            "LAGUNA", 1, d, (v,), ("node",), heads, [1.0], 2, token_arch=dict(
                layer_types=["full_attention", "sliding_attention"],
                mlp_layer_types=["dense", "sparse"],
                num_attention_heads_per_layer=[2, 4], num_key_value_heads=2, head_dim=128,
                intermediate_size=512, moe_intermediate_size=256,
                shared_expert_intermediate_size=256, num_experts=8,
                num_experts_per_tok=2, num_experts_held=4, experts_offset=0,
                sliding_window=512, moe_routed_scaling_factor=2.5, vocab_size=v,
                token_minmax=[0.0, v - 1.0], rope_parameters={
                    "full_attention": {
                        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                        "original_max_position_embeddings": 4096, "beta_slow": 1,
                        "beta_fast": 64, "partial_rotary_factor": 0.5,
                    },
                    "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
                },
            ), **token,
        ),
    }
    pos = np.zeros((n - 1, 3), np.float32)
    pos[:, 0] = np.arange(n - 1)
    sequence = GraphSample(
        x=np.zeros((n - 1, 1), np.float32), pos=pos, y=np.zeros(n - 1, np.float32),
        y_loc=np.array([[0, n - 1]], np.int64),
    )
    batch = GraphArena([sequence]).collate(
        np.array([0]), ("node",), (1,), num_nodes_pad=n, num_edges_pad=8,
        num_graphs_pad=2, with_positions=True,
    )
    out = {}
    for name, model in models.items():
        params = jax.eval_shape(lambda m=model: init_model_variables(m, batch))["params"]

        def loss(p, b, m=model):
            got = m.apply({"params": p}, b, train=True)
            return multihead_rmse_loss(
                got, b, m.output_type, m.task_weights,
                head_loss=m.head_loss, class_minmax=m.class_minmax,
            )[0]

        with platform_override("tpu"):
            out[name] = _hlo_digest(
                jax.jit(jax.grad(loss)).lower(shaped(params), shaped(batch)).compile().as_text()
            )
    pna = create_model(
        "PNA", 1, 64, (1, 3), ("graph", "node"), {
            "graph": {"num_sharedlayers": 1, "dim_sharedlayers": 32, "num_headlayers": 1,
                      "dim_headlayers": [32]},
            "node": {"num_headlayers": 1, "dim_headlayers": [32], "type": "mlp"},
        }, [1.0, 1.0], 2, max_neighbours=8, pna_deg=[0, 2, 4, 8, 4, 2, 1, 1, 1],
    )
    lattice = GraphSample(
        x=np.zeros((4, 1), np.float32), pos=np.zeros((4, 3), np.float32),
        edge_index=np.array([[0, 1, 2, 3], [1, 2, 3, 0]], np.int32),
    )
    small = GraphArena([lattice]).collate(
        np.array([0]), num_nodes_pad=8, num_edges_pad=16, num_graphs_pad=2
    )
    variables = init_model_variables(pna, small)
    engine = InferenceEngine(pna, variables, max_batch_graphs=8, autostart=False)
    served = engine._dummy_batch(512, 8192)
    os.environ["HYDRAGNN_SEGMENT_SORTED"] = "1"
    try:
        with platform_override("tpu"):
            out["pna_serve"] = _hlo_digest(
                engine._jit.lower(
                    shaped(variables["params"]), shaped(variables.get("batch_stats", {})),
                    shaped(served),
                ).compile().as_text()
            )
    finally:
        del os.environ["HYDRAGNN_SEGMENT_SORTED"]
        engine.close()
    out.update(_served_token_programs(one_chip))
    return out


def _served_token_programs(one_chip):
    """{name: digest} of the engine's ``score_tokens`` executable for the two
    served token stacks that came before PR 45, at their published widths,
    two layers (Mellum's one of each kind) and 2,048 rows of two documents."""
    import json

    import numpy as np

    from graftbench.drivers import serve_tokens
    from hydragnn_tpu.graphs.collate import GraphArena
    from hydragnn_tpu.graphs.sample import GraphSample
    from hydragnn_tpu.ops.segment import platform_override
    from hydragnn_tpu.serve.engine import _token_forward

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    configs = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "graftbench", "configs"
    )
    n, tokens = 2048, 768
    pos = np.zeros((tokens, 3), np.float32)
    pos[:, 0] = np.arange(tokens)
    document = GraphSample(x=np.zeros((tokens, 1), np.float32), pos=pos)
    batch = GraphArena([document] * 2).collate(
        np.arange(2), num_nodes_pad=n, num_edges_pad=8, num_graphs_pad=3, with_positions=True,
    )
    out = {}
    for name, file, cut in (
        ("mistral_serve", "mistral_small4_ep8.json", {}),
        ("mellum_serve", "mellum2_12b_l4.json",
         {"layer_types": ["sliding_attention", "full_attention"]}),
    ):
        with open(os.path.join(configs, file)) as f:
            arch = serve_tokens.completed_arch(json.load(f))
        arch.update(cut, num_conv_layers=2)
        model, template, _ = serve_tokens.init_model(arch)
        with platform_override("tpu"):
            out[name] = _hlo_digest(_token_forward(model).lower(
                shaped(template["params"]), shaped(template.get("batch_stats", {})),
                shaped(batch),
            ).compile().as_text())
    return out


def pytest_programs_pr39_shares_code_with_are_text_identical_to_the_parents(one_chip):
    """LFM2's and Laguna's train programs, the PNA serving engine's executable
    and (PR 45) Mistral's and Mellum's served programs, optimized for the
    described chip: the same HLO text as the parent commit's
    (``PARENT_HLO``), metadata apart."""
    assert _unchanged_programs(one_chip) == PARENT_HLO
