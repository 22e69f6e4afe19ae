"""PNA's min and max over sorted receiver runs in one streamed pass each way
(``ops/extrema_scan.py``: ``_extrema_scan_kernel`` forward,
``_extrema_bwd_kernel`` backward, both interpreted here): the outputs are held
bit-equal to ``jax.ops.segment_min`` / ``segment_max``, and the backward to
the XLA route's four gathers (``aggregate._extrema_bwd``), alone and through
``pna_aggregate``.

CPU, small sizes: values and routes, never a time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.ops import aggregate
from hydragnn_tpu.ops import extrema_scan as scan

# Rows a run of each segment holds, in segment order. The kernel's block is
# scan._XB rows and its in-register chunk scan._XC; the names say what each
# layout puts across those.
LAYOUTS = {
    # E = 14: one partial block; empty segments first, in the middle, last.
    "empties_and_single_rows": [0, 0, 3, 1, 0, 1, 1, 6, 0, 2, 0, 0],
    # E = 2 * _XB exactly; a run of 40 rows over the boundary between blocks.
    "crosses_one_block_boundary": (
        [7] * 70 + [scan._XB - 490 + 18] + [9] * 54 + [scan._XB - 18 - 486]
    ),
    # The padding node's: real runs, empty nodes, then one run over the rest
    # of block 0, all of blocks 1 and 2 and part of 3. E not a multiple.
    "padding_run_spans_three_blocks": (
        [5, 1, 12, 1, 1, 30] * 6 + [0] * 9 + [3 * scan._XB + 77 - 300]
    ),
    # Every run one chunk long and aligned to it, then single rows.
    "chunk_aligned_runs": [scan._XC] * 20 + [1] * 37 + [0, 2 * scan._XC + 1],
    # An empty edge set: every segment comes back 0, as from segment_min/max.
    "no_edges": [0, 0, 0],
    # Ids with gaps wider than one of the backward's node windows, twice
    # before the padding run: the windows an edge block needs are not "its
    # first id's and the next".
    "gaps_wider_than_a_node_window": (
        [5, 1, 12, 1] * 10 + [0] * (2 * scan._NB + 37) + [3, 0, 2]
        + [0] * (scan._NB + 5) + [700]
    ),
    # A run that is blocks 1 and 2 and nothing else, neighbours either side.
    "run_of_whole_blocks": [100, scan._XB - 100, 2 * scan._XB, 7, 0, 50],
}
assert sum(LAYOUTS["crosses_one_block_boundary"]) == 2 * scan._XB
assert sum(LAYOUTS["crosses_one_block_boundary"][:70]) < scan._XB < sum(
    LAYOUTS["crosses_one_block_boundary"][:71]
)


def _problem(layout, f, values, seed=0):
    counts = np.asarray(LAYOUTS[layout])
    n, e = len(counts), int(counts.sum())
    ids = np.repeat(np.arange(n), counts).astype(np.int32)
    row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    # Rounded to a quarter: ties inside a run, so the backward's "every row
    # equal to its extremum" has rows to find.
    data = np.round(np.random.default_rng(seed).normal(size=(e, f)) * 4) / 4
    if values == "negative":
        data = -np.abs(data) - 0.25
    dtype = jnp.bfloat16 if values == "bf16" else jnp.float32
    return jnp.asarray(data, dtype), jnp.asarray(ids), jnp.asarray(row_ptr), counts


CASES = [(layout, f, "normal") for layout in LAYOUTS for f in (1, 6, 256)] + [
    ("padding_run_spans_three_blocks", f, values)
    for values in ("negative", "bf16") for f in (1, 6, 256)
]
BACKWARD_CASES = [
    (layout, f, values) for layout in LAYOUTS for f in (1, 6, 256)
    for values in ("normal", "negative", "bf16")
]


@pytest.mark.parametrize("layout,f,values", CASES)
def pytest_csr_extrema_bit_equal_to_segment_min_max(layout, f, values):
    data, ids, row_ptr, counts = _problem(layout, f, values)
    n = len(counts)
    assert data.shape[0] % scan._XB or layout in ("crosses_one_block_boundary", "no_edges")
    mn, mx = jax.jit(
        lambda d: aggregate.segment_extrema(d, ids, n, None, row_ptr)
    )(data)
    assert mn.dtype == data.dtype and mx.dtype == data.dtype
    filled = (counts > 0)[:, None]
    # The references and the XLA arm: ONE program of their own.
    seg_mn, seg_mx, (xla_mn, xla_mx) = jax.jit(lambda d: (
        jax.ops.segment_min(d, ids, n), jax.ops.segment_max(d, ids, n),
        aggregate.segment_extrema(d, ids, n),
    ))(data)
    want_mn = np.where(filled, seg_mn, 0)
    want_mx = np.where(filled, seg_mx, 0)
    assert np.array_equal(np.asarray(mn), want_mn)
    assert np.array_equal(np.asarray(mx), want_mx)
    assert np.isfinite(np.asarray(mn, np.float32)).all()
    if values == "negative":
        assert (np.asarray(mx)[counts > 0] < 0).all()  # no 0 fill leaked in
    # The XLA arm on the same rows (masked ids are its own convention).
    assert np.array_equal(np.asarray(mn), np.asarray(xla_mn))
    assert np.array_equal(np.asarray(mx), np.asarray(xla_mx))


@functools.partial(jax.jit, static_argnums=(2,))
def _pulled(data, ids, n, row_ptr, cotangents):
    """Forward and pull in ONE program, the ids and the boundaries its
    ARGUMENTS as a step has them: compiled once a layout, width, dtype and
    route, whatever the values (no test that calls it turns a trace-time
    switch, so one function object serves them all)."""
    _, pull = jax.vjp(
        lambda d: aggregate.segment_extrema(d, ids, n, None, row_ptr), data
    )
    return pull(cotangents)[0]


def _vjp(data, ids, n, row_ptr, cotangents):
    """``d_data`` of ``segment_extrema`` on the route ``row_ptr`` selects."""
    # To float32 on the host: inside the program the compiler may keep a
    # bfloat16 gradient's extra bits through the conversion.
    return np.asarray(_pulled(data, ids, n, row_ptr, cotangents)).astype(np.float32)


@pytest.mark.parametrize("layout,f,values", BACKWARD_CASES)
def pytest_csr_backward_bit_equal_to_the_four_gathers(layout, f, values):
    """The streamed backward (``row_ptr``) against ``_extrema_bwd``'s gathers
    (no ``row_ptr``) on the same residuals and cotangents. Every node's
    cotangent is non-zero, the padding node's and the empty nodes' too: a
    run is treated as any run, and a row read from the wrong node shows."""
    data, ids, row_ptr, counts = _problem(layout, f, values)
    n = len(counts)
    rng = np.random.default_rng(2)
    cotangents = tuple(
        jnp.asarray(rng.normal(size=(n, f)) + 3.0, data.dtype) for _ in range(2)
    )
    got = _vjp(data, ids, n, row_ptr, cotangents)
    want = _vjp(data, ids, n, None, cotangents)
    assert got.shape == data.shape and np.array_equal(got, want)
    if data.shape[0]:
        assert np.count_nonzero(got) >= 2 * f * np.count_nonzero(counts > 1)


def pytest_csr_backward_gives_every_tied_row_the_cotangent():
    """Ties: every row equal to its run's minimum gets ``d_mn`` and every row
    equal to its maximum ``d_mx``; a constant run (every row both) gets
    ``d_mn + d_mx`` on every row, across a block boundary too."""
    counts = np.asarray([6, scan._XB + 9, 0, 4, 1])
    n, e = len(counts), int(counts.sum())
    ids = jnp.asarray(np.repeat(np.arange(n), counts).astype(np.int32))
    row_ptr = jnp.asarray(np.concatenate([[0], np.cumsum(counts)]).astype(np.int32))
    data = np.zeros((e, 3), np.float32)
    data[:6, 0] = [2, -1, 5, -1, 5, 5]      # min tied twice, max three times
    data[:6, 1] = [0, 0, 0, 0, 0, -0.0]     # constant up to the zero's sign
    data[:6, 2] = np.arange(6)
    data[6:6 + counts[1]] = 1.5             # constant over a block boundary
    data[-5:-1, 0] = [7, 7, 8, 8]
    d_mn = np.arange(1, 3 * n + 1, dtype=np.float32).reshape(n, 3)
    d_mx = -10.0 * d_mn
    got = _vjp(jnp.asarray(data), ids, n, row_ptr, (jnp.asarray(d_mn), jnp.asarray(d_mx)))
    assert got[:6, 0].tolist() == [0, d_mn[0, 0], d_mx[0, 0], d_mn[0, 0], d_mx[0, 0], d_mx[0, 0]]
    assert (got[:6, 1] == d_mn[0, 1] + d_mx[0, 1]).all()
    assert got[:6, 2].tolist() == [d_mn[0, 2], 0, 0, 0, 0, d_mx[0, 2]]
    assert (got[6:6 + counts[1]] == (d_mn[1] + d_mx[1])[None, :]).all()
    assert got[-5:-1, 0].tolist() == [d_mn[3, 0]] * 2 + [d_mx[3, 0]] * 2
    assert (got[-1] == d_mn[4] + d_mx[4]).all()          # a run of one row
    want = _vjp(jnp.asarray(data), ids, n, None, (jnp.asarray(d_mn), jnp.asarray(d_mx)))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("f", [1, 256])
@pytest.mark.parametrize(
    "aggregators", [("min", "max"), ("mean", "min", "max", "std")]
)
def pytest_gradient_on_the_kernel_route_equals_the_xla_routes(
    aggregators, f, monkeypatch
):
    """``pna_aggregate`` with ``row_ptr`` takes the kernels, without it XLA's
    scatters and gathers; both backwards read the same residuals ``(data,
    ids, mn, mx)`` and only compare and select, so the gradients are equal to
    the bit. The last segment is the padding node: its run holds the masked
    edges and nothing reads its output, as in a collated batch."""
    monkeypatch.setenv("HYDRAGNN_SEGMENT_SORTED", "1")
    data, ids, row_ptr, counts = _problem("padding_run_spans_three_blocks", f, "normal")
    n = len(counts)
    mask = ids < n - 1
    real = jnp.asarray(counts > 0).at[n - 1].set(False)
    weights = jnp.asarray(
        np.random.default_rng(1).normal(size=(n, len(aggregators), f)), jnp.float32
    )

    def loss(d, ptr):
        agg, _ = aggregate.pna_aggregate(
            d, ids, n, aggregators, mask=mask, row_ptr=ptr
        )
        return jnp.sum(jnp.where(real[:, None, None], agg * weights, 0.0))

    def arms(ptr):
        text = jax.jit(jax.grad(loss)).lower(data, ptr).as_text(debug_info=True)
        return {a for a in ("xla", "pallas_csr") if f"agg.extrema.{a}" in text}

    assert arms(row_ptr) == {"pallas_csr"} and arms(None) == {"xla"}
    value, grad = jax.jit(jax.value_and_grad(loss))(data, row_ptr)
    want_value, want_grad = jax.jit(jax.value_and_grad(loss))(data, None)
    assert np.array_equal(np.asarray(grad), np.asarray(want_grad))
    assert np.asarray(grad)[np.asarray(mask)].any()
    assert not np.asarray(grad)[~np.asarray(mask)].any()
    if aggregators == ("min", "max"):
        assert float(value) == float(want_value)
