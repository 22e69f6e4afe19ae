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


def pytest_gatv2_conv_at_cell_size_has_no_rank3_edge_array(one_chip, monkeypatch):
    """One ``GATv2Conv``, forward and backward, at the shapes of the cell
    ``gatv2_h64x6_md17like.train_b512`` (16384 × 262144, six heads of 64) on
    the sorted/CSR route the chip takes: the row gathers come out as
    ``f32[262144,384]``, the backward holds exactly two scatter-adds into
    ``f32[16384,384]``, and no array with a ``[6,64]`` row or a transposed
    ``[262144,384]{0,1}`` is written anywhere (PERF.md §6, PR 24: a reshape
    inside the per-head reduce makes the chip's compiler write both)."""
    from hydragnn_tpu.models.convs import GATv2Conv

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    n, e, h, f = 16384, 262144, 6, 64
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


def pytest_painn_block_at_cell_size_keeps_the_vector_state_flat(one_chip, monkeypatch):
    """One ``PaiNNBlock`` (message + update), forward and backward, at the
    shapes of the cell ``painn_f128.train_b512`` (16384 × 262144, F 128, 20
    basis functions) on the sorted/CSR route the chip takes: the two sources
    come out as ``f32[262144,384]`` row gathers with two scatter-adds into
    ``f32[16384,384]`` behind them, the two states are summed as ONE
    ``[262144,512]`` array, and no array with a ``[3,128]`` row or a
    transposed ``[262144,384]{0,1}`` is written anywhere: ``v`` stays flat
    ``[·, 3F]``, xyz-major, from the gather to what the backward saves."""
    from hydragnn_tpu.models.painn import EdgeGeometry, PaiNNBlock

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    n, e, f, radial = 16384, 262144, 128, 20
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
    assert re.search(rf"f32\[{e},{4 * f}\]\S* fusion\(.*hydragnn\.agg\.sum\.csr", entry)


def _combiners(text):
    """{computation name: its body} of an HLO module's text."""
    return {
        m.group(1): m.group(2)
        for m in re.finditer(r"^%?([\w.-]+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M)
    }


@pytest.mark.parametrize("f", [256, 1], ids=["hidden_256", "input_layer_1"])
def pytest_pna_conv_at_cell_size_scans_its_extrema_in_one_kernel(one_chip, monkeypatch, f):
    """One ``PNAConv``, forward and backward, at the large bucket of the cell
    ``pna_multihead_h256.train_b512`` (32768 × 524288; a hidden layer's 256
    columns, the input layer's one) on the CSR route the chip takes: min and
    max come from ONE Mosaic kernel in the forward and their cotangents go
    down the rows in ONE in the backward, both under
    ``hydragnn.agg.extrema.pallas_csr``; no ``[E, f]`` row gather is left
    under that scope; and no scatter that combines by minimum or maximum is
    left anywhere (the one scatter into ``f32[32768,f]`` that stays is the
    centered sum of squares of ``std``)."""
    from hydragnn_tpu.models.convs import PNAConv
    from hydragnn_tpu.ops import segment as seg
    from hydragnn_tpu.telemetry import scopes

    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    n, e = 32768, 524288
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
