"""Min and max over sorted receiver runs, and their backward, each in one
streamed pass: the two Pallas kernels of the package (PR 27 the forward, PR 30
the backward), reached through ``ops/aggregate.py`` ``segment_extrema`` and
from nowhere else.

PNA's ``min`` and ``max`` composed from XLA are two scatters over every padded
edge row a layer and, backward, four ``[N, F] -> [E, F]`` row gathers, all paid
by the row. The batch contract (``graphs/csr.py``: receivers non-decreasing,
``row_ptr`` their run boundaries) makes the forward a segmented scan down the
rows that never leaves VMEM, then one row fetch a node; and the backward a
pass down the same rows beside a forward-moving window of the node arrays,
each node's four rows copied once to its run's first row and spread down the
run in registers. Bit-equal to ``jax.ops.segment_min`` / ``segment_max`` on
every run and to ``aggregate._extrema_bwd``'s gathers on every row (min, max,
compare and select do not round); ``ops/certify.py`` and
``tests/test_segment_extrema_csr.py`` hold both to that. Device times:
PERF.md (PR 27's and PR 30's findings), PERF_LEDGER.jsonl.
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


def _whole_blocks(data, ids, num_segments: int):
    """``data`` and ``ids`` padded to whole blocks of ``_XB`` rows: the rows
    past the end form a run of their own that no node points into."""
    e = data.shape[0]
    e_pad = _round_up(e, _XB)
    if e_pad != e:
        data = jnp.pad(data, ((0, e_pad - e), (0, 0)))
        ids = jnp.pad(ids, (0, e_pad - e), constant_values=num_segments)
    return data, ids, e_pad


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
    data, ids, e_pad = _whole_blocks(data, ids, num_segments)
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


# Node rows a grid step of the backward holds in VMEM beside its edge block,
# and what a step of its staircase of (edge block, node window) pairs does.
_NB = 256
_IDLE, _PLACE, _PLACE_AND_WRITE, _ONE_RUN = 0, 1, 2, 3


def _extrema_bwd_kernel(
    eb_ref, nb_ref, mode_ref, nlo_ref, nhi_ref, ptr_ref,
    ids_ref, data_ref, mn_ref, mx_ref, dmn_ref, dmx_ref, out_ref,
    idt_ref, bmn_ref, bmx_ref, bdmn_ref, bdmx_ref,
):
    """One (edge block, node window) pair of the backward's staircase.

    ``d_data[i] = where(data[i] == mn[id], d_mn[id], 0) + where(data[i] ==
    mx[id], d_mx[id], 0)`` with ``id = ids[i]``, and no row gather: the four
    node rows are constant along a run, so each node of the window is copied
    ONCE to its run's first row of this block (``row_ptr``; a run that began
    in an earlier block starts at row 0 here) and spread down the run in
    registers. A row ``k`` rows below its run's first takes the row ``s``
    above it for each bit ``s`` of ``k``: ``log2(_XC)`` shift-select steps a
    chunk, ``k`` counted from the ids by as many; a run that continues from
    the chunk before takes the rows carried from it. An edge block whose
    rows span several node windows takes a step a window and is written on
    its last; one that is a single run (the padding node's, three quarters of
    all rows) reads its node's rows where they lie and only compares."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f = data_ref.shape[1]
    lane = min(f, 128)
    slabs = [(c0, min(lane, f - c0)) for c0 in range(0, f, lane)]
    t = pl.program_id(0)
    mode = mode_ref[t]
    base = eb_ref[t] * _XB
    node0 = nb_ref[t] * _NB
    wins = (mn_ref, mx_ref, dmn_ref, dmx_ref)
    bufs = (bmn_ref, bmx_ref, bdmn_ref, bdmx_ref)

    def d_data(data, mn, mx, dmn, dmx):
        return jnp.where(data == mn, dmn, 0.0) + jnp.where(data == mx, dmx, 0.0)

    @pl.when(mode == _ONE_RUN)
    def _():
        m = nlo_ref[t] - node0
        rows = [w[pl.ds(m, 1), :] for w in wins]
        out_ref[...] = d_data(
            data_ref[...].astype(jnp.float32), *rows
        ).astype(out_ref.dtype)

    @pl.when((mode == _PLACE) | (mode == _PLACE_AND_WRITE))
    def _():
        # An empty node's row lands where the next node's run starts and is
        # overwritten by it: the nodes go in ascending order, window after
        # window, and the block's last id has a run.
        def place(n, _):
            p = jnp.maximum(ptr_ref[n] - base, 0)
            for w, b in zip(wins, bufs):
                b[pl.ds(p, 1), :] = w[pl.ds(n - node0, 1), :]
            return 0

        jax.lax.fori_loop(nlo_ref[t], nhi_ref[t] + 1, place, 0)

    @pl.when(mode == _PLACE_AND_WRITE)
    def _():
        idt_ref[...] = jnp.broadcast_to(ids_ref[...], (128, _XB)).T
        row = jax.lax.broadcasted_iota(jnp.int32, (_XC, lane), 0)
        shifts = [1 << b for b in range(_XC.bit_length() - 1)]

        def chunk(c, carry):
            cid, carried = carry
            r0 = pl.multiple_of(c * _XC, _XC)
            ids = idt_ref[pl.ds(r0, _XC), :][:, :lane]
            # Rows of this chunk in this row's run, up to and with the row.
            count = jnp.ones((_XC, lane), jnp.int32)
            for s in shifts:
                same = (pltpu.roll(ids, s, 0) == ids) & (row >= s)
                count = count + jnp.where(same, pltpu.roll(count, s, 0), 0)
            takes = [(s, ((count - 1) & s) != 0) for s in shifts]
            joined = ids == cid
            last = [[] for _ in bufs]
            for j, (c0, w) in enumerate(slabs):
                spread = []
                for a, b in enumerate(bufs):
                    v = b[pl.ds(r0, _XC), c0:c0 + w]
                    for s, take in takes:
                        v = jnp.where(take[:, :w], pltpu.roll(v, s, 0), v)
                    v = jnp.where(joined[:, :w], carried[a][j], v)
                    spread.append(v)
                    last[a].append(v[_XC - 1:, :])
                data = data_ref[pl.ds(r0, _XC), c0:c0 + w].astype(jnp.float32)
                out_ref[pl.ds(r0, _XC), c0:c0 + w] = d_data(
                    data, *spread
                ).astype(out_ref.dtype)
            return ids[_XC - 1:, :], tuple(tuple(rows) for rows in last)

        # Row 0 of a block is its run's first row here: nothing to carry in.
        jax.lax.fori_loop(
            0, _XB // _XC, chunk,
            (
                jnp.full((1, lane), -1, jnp.int32),
                tuple(
                    tuple(jnp.zeros((1, w), jnp.float32) for _, w in slabs)
                    for _ in bufs
                ),
            ),
        )


def _extrema_csr_bwd(data, ids, row_ptr, mn, mx, d_mn, d_mx, interpret: bool):
    """``d_data`` of (min, max) over receiver runs from ONE streamed pass down
    the sorted rows and no ``[E, F]`` gather: the kernel above over a
    staircase of (edge block, node window) pairs read off ``ids`` (they are
    non-decreasing, so block ``j`` needs the windows from ``ids[j * _XB]``'s
    to ``ids[(j + 1) * _XB - 1]``'s and the pairs are at most ``E / _XB +
    N / _NB``; ids have gaps, so a window is never "the first id's and the
    next"). Node rows leave HBM once a node. Compare and select do not round:
    bit-equal to ``aggregate._extrema_bwd``'s four gathers."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, f = data.shape
    n = mn.shape[0]
    if e == 0:
        return jnp.zeros_like(data)
    # The rows past the end are computed on and sliced away.
    data, ids, e_pad = _whole_blocks(data, ids.astype(jnp.int32), n)
    n_pad = _round_up(n, _NB)
    nodes = [
        jnp.pad(a.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
        for a in (mn, mx, d_mn, d_mx)
    ]
    n_eb, n_nb = e_pad // _XB, n_pad // _NB
    # The staircase, from each block's first and last id (two n_eb-row
    # fetches: a strided slice of ids costs a pass over them).
    block0 = jnp.arange(n_eb, dtype=jnp.int32) * _XB
    first, last_raw = ids[block0], ids[block0 + (_XB - 1)]
    last = jnp.minimum(last_raw, n - 1)
    lo, hi = first // _NB, last // _NB
    pairs = hi - lo + 1
    start = jnp.cumsum(pairs) - pairs
    steps = n_eb + n_nb
    t = jnp.arange(steps, dtype=jnp.int32)
    eb = jnp.sum(start[None, :] <= t[:, None], axis=1, dtype=jnp.int32) - 1
    at = lo[eb] + t - start[eb]
    nb = jnp.minimum(at, hi[eb])
    mode = jnp.where(
        at > hi[eb], _IDLE,
        jnp.where(
            last_raw[eb] == first[eb], _ONE_RUN,
            jnp.where(at == hi[eb], _PLACE_AND_WRITE, _PLACE),
        ),
    ).astype(jnp.int32)
    n_lo = jnp.maximum(nb * _NB, first[eb])
    n_hi = jnp.minimum(nb * _NB + _NB - 1, last[eb])

    rows = pl.BlockSpec((_XB, f), lambda t, eb, *_: (eb[t], 0))
    window = pl.BlockSpec((_NB, f), lambda t, eb, nb, *_: (nb[t], 0))
    out = pl.pallas_call(
        _extrema_bwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((1, _XB), lambda t, eb, *_: (0, eb[t])),
                rows, window, window, window, window,
            ],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((_XB, 128), jnp.int32)]
            + [pltpu.VMEM((_XB, f), jnp.float32)] * 4,
        ),
        out_shape=jax.ShapeDtypeStruct((e_pad, f), data.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(
        eb, nb, mode, n_lo, n_hi, row_ptr.astype(jnp.int32),
        ids.reshape(1, e_pad), data, *nodes,
    )
    return out[:e]
