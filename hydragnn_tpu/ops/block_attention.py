"""Causal attention inside each run of a flat row array that visits, for a
block of query rows, only the key blocks from the one that holds the first
row of the block's EARLIEST run up to the diagonal: the forward Pallas kernel
``models/token_attention.py`` ``segment_causal_attention`` (every token
stack's attention entry point) takes on the TPU where no gradient is asked
for (the engine's ``score_tokens``, an evaluation step).

JAX's own flash kernel skips a key block only above the diagonal; a block
below it that belongs to another run (another document of the flush) is
fetched, multiplied and masked element by element. Runs are contiguous
(collation), so the blocks a query block can see are ONE range,
``block_range``'s ``lo[i] .. i``, read off ``node_graph`` inside the program
and handed to the kernel as scalar prefetch: a flat schedule of the visited
(query block, key block) pairs, so that a step that is skipped is not walked
either. On a visited block the arithmetic is the library kernel's own
(float32 rows in, its dots, its online softmax, its additive mask): a block
left out contributed exact zeros before (``exp(mask - m)`` is 0 at a row's
first real block), so the result is the library's bit for bit
(``benchmarks/attention_block_range.py`` holds it to that on the chip).
Device times: PERF.md (PR 40's findings).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The library kernel's own (jax.experimental.pallas.ops.tpu.flash_attention):
# what a masked score is given, and the tile a row's running max and sum are
# kept broadcast over.
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
LANES = 128
SUBLANES = 8
# Query heads a grid step takes (PERF.md section 6, PR 40: the kernel-alone
# table): a step's fixed cost and the mask are paid once for all of them.
HEADS_A_STEP = 8


def block_range(node_graph, block: int, xp=np):
    """``lo`` [N / block] int32: for each block of ``block`` query rows, the
    key block that holds the first row of the run its FIRST row belongs to (a
    run: consecutive rows of one id). A query block sees the key blocks
    ``lo[i] .. i`` and no others (its later rows belong to the same run or to
    later ones). A change-point cumulative maximum, taken in two levels (the
    latest run start inside each block, then over the blocks before) so that
    no scan runs down the rows. The same text for ``numpy`` (the engine's
    counters) and ``jax.numpy`` (the program); ``node_graph`` [N] is a whole
    number of blocks (``whole_blocks``)."""
    blocks = node_graph.shape[0] // block
    rows = xp.arange(node_graph.shape[0], dtype=xp.int32)
    change = xp.concatenate(
        [xp.ones((1,), dtype=bool), node_graph[1:] != node_graph[:-1]]
    )
    starts = xp.where(change, rows, 0)
    inside = xp.max(starts.reshape(blocks, block), axis=1)
    before = xp.max(
        xp.where(xp.tri(blocks, k=-1, dtype=bool), inside[None, :], 0), axis=1
    )
    return (xp.maximum(starts[::block], before) // block).astype(xp.int32)


def whole_blocks(node_graph: np.ndarray, block: int) -> np.ndarray:
    """The host's ``node_graph`` padded to a whole number of blocks as
    ``segment_causal_attention`` pads it in the program: the rows past the
    end are one more run, of id -1."""
    return np.pad(node_graph, (0, -node_graph.shape[0] % block), constant_values=-1)


def block_pairs(lo: np.ndarray):
    """(visited, causal): the (query block, key block) pairs the range
    ``lo[i] .. i`` holds, and the pairs of the whole triangle. Equal where
    the rows are one run."""
    blocks = lo.shape[0]
    return int(np.sum(np.arange(blocks) - lo + 1)), blocks * (blocks + 1) // 2


def _schedule(lo):
    """The visited pairs in order, query block by query block, as the two
    [steps] tables the index maps read, and their number. ``steps`` is the
    triangle's (one run filling the rows visits it all); the surplus steps at
    the END repeat the last pair's indices, so they fetch nothing."""
    blocks = lo.shape[0]
    counts = jnp.arange(blocks, dtype=jnp.int32) - lo + 1
    ends = jnp.cumsum(counts)
    step = jnp.arange(blocks * (blocks + 1) // 2, dtype=jnp.int32)
    qi = jnp.searchsorted(ends, step, side="right", method="compare_all")
    qi = jnp.minimum(qi, blocks - 1).astype(jnp.int32)
    ki = jnp.where(
        step < ends[-1], lo[qi] + step - (ends[qi] - counts[qi]), blocks - 1
    )
    return qi, ki.astype(jnp.int32), ends[-1:]


def _heads_a_step(heads: int, kv: int, most: int):
    """(query heads, key-value heads) a grid step holds: the most query heads
    up to ``most`` that are whole key-value groups or share one."""
    rep = heads // kv
    g = max(
        g for g in range(1, most + 1)
        if heads % g == 0 and (g % rep == 0 or rep % g == 0)
    )
    return g, max(1, g // rep)


def _kernel(
    qi_ref, ki_ref, lo_ref, total_ref,  # scalar prefetch
    q_ref, k_ref, v_ref, ids_q_ref, ids_k_ref,
    o_ref,
    m_ref, l_ref, acc_ref,
    *, scale: float, heads: int, rep: int, hd: int, block: int,
):
    import jax.experimental.pallas as pl

    step = pl.program_id(1)
    i, j = qi_ref[step], ki_ref[step]
    real = step < total_ref[0]

    def wide(x):  # a [block, LANES] running value over a head's columns
        return jnp.tile(x, (1, hd // LANES)) if hd % LANES == 0 else x[:, :hd]

    @pl.when(real & (j == lo_ref[i]))
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(real)
    def _():
        # same graph and key <= query, once for the step's heads
        shape = (block, block)
        rows = i * block + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = j * block + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        same = jnp.tile(ids_q_ref[...], (1, block // LANES)) == ids_k_ref[:1, :]
        masked = jnp.where(same & (cols <= rows), 0.0, MASK_VALUE)
        def head(h, _):
            at_kv = h // rep if rep > 1 else h
            m_prev, l_prev = m_ref[h], l_ref[h]
            s = jax.lax.dot_general(
                q_ref[h], k_ref[at_kv], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if scale != 1.0:
                s *= scale
            s = s + masked
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
            p = jnp.exp(s - jnp.tile(m_next, (1, block // LANES)))
            l_corr = jnp.exp(m_prev - m_next) * l_prev
            l_next = jnp.sum(p, axis=1)[:, None] + l_corr
            m_ref[h], l_ref[h] = m_next, l_next
            l_inv = jnp.where(l_next == 0.0, 1.0, 1.0 / l_next)
            acc_ref[h] = acc_ref[h] * wide(l_corr * l_inv) + jax.lax.dot(
                p, v_ref[at_kv], preferred_element_type=jnp.float32
            ) * wide(l_inv)

        # A loop, not ``heads`` copies of its body: the kernel is traced and
        # lowered once a shape in every process, on the host's clock.
        jax.lax.fori_loop(0, heads, head, None)

    @pl.when(real & (j == i))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block", "heads_a_step", "interpret")
)
def block_range_attention(q, k, v, node_graph, scale: float, block: int,
                          heads_a_step: int = HEADS_A_STEP, interpret: bool = False):
    """Softmax over ``same graph and j <= i`` of ``q`` [N, H, hd] against
    ``k``, ``v`` [N, KV, hd] (each key-value head shared by ``H / KV`` query
    heads, mapped in the index map: nothing is repeated), ``N`` a whole
    number of ``block`` rows, ``node_graph`` [N] made of contiguous runs.
    Returns [N, H, hd]. The kernel takes the rows a head at a time
    (``[H, N, hd]``, the library kernel's layout, which XLA folds into the
    operations round the call); ``hd`` is under the lanes' 128 or a multiple
    of them. Jitted, as the library kernel's entry is: a model's layers of
    one shape share ONE trace and ONE lowering of the kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, heads, hd = q.shape
    kv = k.shape[1]
    if hd > LANES and hd % LANES:
        raise NotImplementedError(f"a head of {hd} columns: under {LANES} or a multiple")
    g, g_kv = _heads_a_step(heads, kv, heads_a_step)
    rep = heads // kv
    ids = node_graph.astype(jnp.int32)
    qi, ki, total = _schedule(lo := block_range(ids, block, jnp))

    def kv_rows(hg, s, qi, ki, *_):
        return hg * g // (rep * g_kv), ki[s], 0

    def q_rows(hg, s, qi, *_):
        return hg, qi[s], 0

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, heads=g, rep=rep, hd=hd, block=block
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(heads // g, qi.shape[0]),
            in_specs=[
                pl.BlockSpec((g, block, hd), q_rows),
                pl.BlockSpec((g_kv, block, hd), kv_rows),
                pl.BlockSpec((g_kv, block, hd), kv_rows),
                pl.BlockSpec((block, LANES), lambda hg, s, qi, *_: (qi[s], 0)),
                pl.BlockSpec((SUBLANES, block), lambda hg, s, qi, ki, *_: (0, ki[s])),
            ],
            out_specs=pl.BlockSpec((g, block, hd), q_rows),
            scratch_shapes=[
                pltpu.VMEM((g, block, LANES), jnp.float32),
                pltpu.VMEM((g, block, LANES), jnp.float32),
                pltpu.VMEM((g, block, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((heads, n, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name="block_range_attention",
        interpret=interpret,
    )(
        qi, ki, lo, total,
        q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        jnp.broadcast_to(ids[:, None], (n, LANES)),
        jnp.broadcast_to(ids[None, :], (SUBLANES, n)),
    )
    return out.transpose(1, 0, 2)
