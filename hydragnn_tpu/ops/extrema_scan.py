"""Min and max over sorted receiver runs in one streamed pass: the one Pallas
kernel of the package (PR 27), reached through ``ops/aggregate.py``
``segment_extrema`` and from nowhere else.

PNA's ``min`` and ``max`` composed from XLA are two scatters over every padded
edge row a layer, paid by the row. The batch contract (``graphs/csr.py``:
receivers non-decreasing, ``row_ptr`` their run boundaries) makes them a
segmented scan down the rows that never leaves VMEM, then one row fetch a node.
Bit-equal to ``jax.ops.segment_min`` / ``segment_max`` on every run (min and
max do not round); ``ops/certify.py`` and ``tests/test_segment_extrema_csr.py``
hold it to that. Device times: PERF.md (PR 27's findings), PERF_LEDGER.jsonl.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# Edge rows a grid step streams through VMEM, and rows of them scanned in
# registers at a time ([_XC, 128] of min, of max and of ids are 2 vregs each).
_XB = 512
_XC = 16


def _extrema_scan_kernel(
    ids_ref, data_ref, mn_ref, mx_ref, idt_ref, cid_ref, cmn_ref, cmx_ref
):
    """One block of the inclusive SEGMENTED (min, max) scan down the rows.

    ids are non-decreasing, so ``ids[i - s] == ids[i]`` says rows ``i - s .. i``
    are one run: ``log2(_XC)`` shift-compare-select steps scan a chunk in
    registers (a shift that wraps round the chunk combines rows of ONE run
    only, so the run's last row still ends up with the run's extrema and
    nothing else is read), then the chunk joins the ``(id, min, max)`` row
    carried from the chunk before it, through the fori_loop inside a block and
    through scratch from block to block (the grid is sequential). The last
    row of a run holds the run's min and max; a run of any length, the
    padding node's included, costs what its rows cost."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f = data_ref.shape[1]
    lane = cid_ref.shape[1]
    slabs = [(c0, min(lane, f - c0)) for c0 in range(0, f, lane)]

    @pl.when(pl.program_id(0) == 0)
    def _():
        cid_ref[...] = jnp.full(cid_ref.shape, -1, jnp.int32)  # no id is < 0
        cmn_ref[...] = jnp.zeros(cmn_ref.shape, jnp.float32)
        cmx_ref[...] = jnp.zeros(cmx_ref.shape, jnp.float32)

    # The ids arrive lane-major (a [1, _XB] row: no padded [E, 1] copy in HBM)
    # and are wanted down the sublanes, the same id in every lane.
    idt_ref[...] = jnp.broadcast_to(ids_ref[...], (128, _XB)).T

    def chunk(c, carry):
        cid, cmns, cmxs = carry
        r0 = pl.multiple_of(c * _XC, _XC)
        ids = idt_ref[pl.ds(r0, _XC), :][:, :lane]
        steps = []
        s = 1
        while s < _XC:
            steps.append((s, pltpu.roll(ids, s, 0) == ids))
            s *= 2
        joined = ids == cid
        last_mn, last_mx = [], []
        for (c0, w), cmn, cmx in zip(slabs, cmns, cmxs):
            mn = mx = data_ref[pl.ds(r0, _XC), c0:c0 + w].astype(jnp.float32)
            for s, same in steps:
                same = same[:, :w]
                mn = jnp.where(same, jnp.minimum(mn, pltpu.roll(mn, s, 0)), mn)
                mx = jnp.where(same, jnp.maximum(mx, pltpu.roll(mx, s, 0)), mx)
            mn = jnp.where(joined[:, :w], jnp.minimum(mn, cmn), mn)
            mx = jnp.where(joined[:, :w], jnp.maximum(mx, cmx), mx)
            mn_ref[pl.ds(r0, _XC), c0:c0 + w] = mn.astype(mn_ref.dtype)
            mx_ref[pl.ds(r0, _XC), c0:c0 + w] = mx.astype(mx_ref.dtype)
            last_mn.append(mn[_XC - 1:, :])
            last_mx.append(mx[_XC - 1:, :])
        return ids[_XC - 1:, :], tuple(last_mn), tuple(last_mx)

    # The carried rows stay one array a 128-lane slab: Mosaic refuses a lane
    # slice of a loop-carried [1, f] value.
    cid, cmns, cmxs = jax.lax.fori_loop(
        0, _XB // _XC, chunk,
        (
            cid_ref[...],
            tuple(cmn_ref[:, c0:c0 + w] for c0, w in slabs),
            tuple(cmx_ref[:, c0:c0 + w] for c0, w in slabs),
        ),
    )
    cid_ref[...] = cid
    for (c0, w), cmn, cmx in zip(slabs, cmns, cmxs):
        cmn_ref[:, c0:c0 + w] = cmn
        cmx_ref[:, c0:c0 + w] = cmx


def _extrema_csr(data, ids, row_ptr, num_segments: int, interpret: bool):
    """(min, max) of each receiver's contiguous run of ``data`` rows from ONE
    streamed pass and no scatter: the scan kernel above, then the row at
    ``row_ptr[n + 1] - 1`` of each output for node ``n`` (one N-row gather
    each), 0 where the run is empty. Min and max do not round, so this is
    bit-equal to ``jax.ops.segment_min`` / ``segment_max`` on every run."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, f = data.shape
    if e == 0:
        zeros = jnp.zeros((num_segments, f), data.dtype)
        return zeros, zeros
    e_pad = _round_up(e, _XB)
    if e_pad != e:
        # Rows past the end form a run of their own that no node points into.
        data = jnp.pad(data, ((0, e_pad - e), (0, 0)))
        ids = jnp.pad(ids, (0, e_pad - e), constant_values=num_segments)
    lane = min(f, 128)
    rows = pl.BlockSpec((_XB, f), lambda j: (j, 0))
    scanned_mn, scanned_mx = pl.pallas_call(
        _extrema_scan_kernel,
        grid=(e_pad // _XB,),
        in_specs=[pl.BlockSpec((1, _XB), lambda j: (0, j)), rows],
        out_specs=[rows, rows],
        out_shape=[jax.ShapeDtypeStruct((e_pad, f), data.dtype)] * 2,
        scratch_shapes=[
            pltpu.VMEM((_XB, 128), jnp.int32),
            pltpu.VMEM((1, lane), jnp.int32),
            pltpu.VMEM((1, f), jnp.float32),
            pltpu.VMEM((1, f), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(ids.reshape(1, e_pad), data)
    row_ptr = row_ptr.astype(jnp.int32)
    last = jnp.maximum(row_ptr[1:] - 1, 0)
    filled = (row_ptr[1:] > row_ptr[:-1])[:, None]
    return (
        jnp.where(filled, jnp.take(scanned_mn, last, axis=0), 0),
        jnp.where(filled, jnp.take(scanned_mx, last, axis=0), 0),
    )
