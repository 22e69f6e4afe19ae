"""The attention core's block range (``ops/block_attention.py``): the range
function in its numpy and jax forms, and the forward kernel, interpreted on
the CPU, through the dispatch a TPU takes (``segment_causal_attention`` under
``platform_override("tpu")``, undifferentiated) against the loop over query
blocks the CPU takes and against a dense masked softmax on every row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hydragnn_tpu.models import token_attention
from hydragnn_tpu.ops import block_attention
from hydragnn_tpu.ops.segment import platform_override
from tests.conftest import program

BLOCK = token_attention.ATTN_BLOCK


def _ids(rows, documents):
    """Documents end to end from row 0, then the padding graph's id."""
    ids = np.full(rows, len(documents), np.int32)
    ids[: sum(documents)] = np.repeat(np.arange(len(documents)), documents)
    return ids


def _dense(q, k, v, ids, scale):
    """Every row's softmax over ``same id and j <= i``, one head at a time in
    float64: the library kernel's semantics, the padding rows' included."""
    n, heads, _ = q.shape
    rep = heads // k.shape[1]
    out = np.empty(q.shape, np.float64)
    # A block of rows at a time against every key: the same numbers as the
    # whole [n, n] table, whose 134 MB temporaries are the seconds here.
    for lo in range(0, n, BLOCK):
        rows = slice(lo, lo + BLOCK)
        keep = (ids[rows, None] == ids[None, :]) & (
            np.arange(n)[None, :] <= np.arange(n)[rows, None]
        )
        for h in range(heads):
            s = np.where(
                keep, q[rows, h].astype(np.float64) @ k[:, h // rep].T * scale, -np.inf
            )
            p = np.exp(s - s.max(axis=1, keepdims=True))
            out[rows, h] = (p / p.sum(axis=1, keepdims=True)) @ v[:, h // rep]
    return out.reshape(n, -1)


CASES = {
    # rows, documents, query heads, key-value heads, head width
    "four_documents_of_whole_blocks_and_a_tail": (4096, (512, 1024, 512, 1536), 2, 2, 128),
    "lfm2s_4160_rows_padded_to_4608": (4160, (1024, 1024, 1024, 1024), 4, 1, 64),
    "ends_inside_a_block": (2048, (1300, 300, 200), 2, 2, 128),
    "one_document_fills_the_rows": (1536, (1536,), 2, 2, 128),
    "three_query_heads_a_key_value_head": (1536, (700, 600), 6, 2, 128),
}


@pytest.mark.parametrize("case", CASES)
def pytest_block_range_kernel_is_the_blockwise_path_and_the_dense_softmax(case, monkeypatch):
    rows, documents, heads, kv, hd = CASES[case]
    ids = _ids(rows, documents)
    rng = np.random.default_rng(len(case))
    q, k, v = (
        rng.standard_normal((rows, h, hd), dtype=np.float32) for h in (heads, kv, kv)
    )
    scale = hd ** -0.5
    args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(ids))
    # Each dispatch is ONE program, traced where its arm is decided (a
    # function object of its own each: ``tests/conftest.py`` ``program``): op
    # by op the loop over query blocks compiles every block's every primitive
    # alone.
    kernel_calls = []

    def interpreted(*a, **kw):
        kernel_calls.append(a[0].shape)
        return block_attention.block_range_attention(*a, interpret=True, **kw)

    monkeypatch.setattr(token_attention, "block_range_attention", interpreted)
    blockwise = np.asarray(program(token_attention.segment_causal_attention)(*args))
    assert not kernel_calls  # the CPU's arm: the loop over query blocks
    with platform_override("tpu"):
        got = np.asarray(program(token_attention.segment_causal_attention)(*args))
    assert len(kernel_calls) == 1  # the TPU's arm: the kernel, interpreted here
    assert got.shape == (rows, heads * hd)
    assert np.abs(got - blockwise).max() < 5e-6
    assert np.abs(got - _dense(q, k, v, ids, scale)).max() < 5e-6
    # The range is not the triangle but where one run fills the rows.
    visited, causal = token_attention.attention_key_blocks(ids)
    assert (visited == causal) == (len(documents) == 1 and rows % BLOCK == 0)


RUNS = {
    "one_run": ([4 * BLOCK], [0, 0, 0, 0]),
    "whole_blocks": ([BLOCK, 2 * BLOCK, BLOCK], [0, 1, 1, 3]),
    "a_run_inside_a_block": ([BLOCK + 10, 20, 3 * BLOCK - 30], [0, 0, 1, 1]),
    "a_block_of_short_runs": ([BLOCK // 2, 7, 9, BLOCK // 2 - 16, BLOCK], [0, 1]),
}


@pytest.mark.parametrize("case", RUNS)
def pytest_block_range_in_numpy_and_in_jax(case):
    lengths, want = RUNS[case]
    ids = np.repeat(np.arange(len(lengths), dtype=np.int32), lengths)
    lo = block_attention.block_range(ids, BLOCK)
    assert lo.tolist() == want
    in_jax = jax.jit(lambda i: block_attention.block_range(i, BLOCK, jnp))(ids)
    assert in_jax.dtype == jnp.int32 and np.array_equal(np.asarray(in_jax), lo)
    visited, causal = block_attention.block_pairs(lo)
    assert visited <= causal
    assert (visited == causal) == (len(lengths) == 1)
    # The flat schedule: each query block's range in order, then the last
    # pair again up to the triangle's length.
    qi, ki, total = (np.asarray(a) for a in block_attention._schedule(jnp.asarray(lo)))
    pairs = [(i, j) for i, first in enumerate(lo) for j in range(first, i + 1)]
    assert int(total[0]) == visited == len(pairs) and len(qi) == causal
    assert list(zip(qi[:visited], ki[:visited])) == pairs
    assert set(zip(qi[visited:], ki[visited:])) <= {pairs[-1]}


def pytest_rows_past_the_end_are_one_more_run():
    ids = _ids(BLOCK + 64, (BLOCK - 8,))
    padded = block_attention.whole_blocks(ids, BLOCK)
    assert padded.shape == (2 * BLOCK,) and (padded[BLOCK + 64:] == -1).all()
    assert np.array_equal(  # already whole: as it is
        block_attention.whole_blocks(padded, BLOCK), padded
    )
    # Block 1 opens inside the padding graph's run, which began in block 0.
    assert token_attention.attention_key_blocks(ids) == (3, 3)
    assert token_attention.attention_key_blocks(_ids(2 * BLOCK, (BLOCK,))) == (2, 3)
    assert token_attention.attention_key_blocks(_ids(2 * BLOCK, (BLOCK,)), ranged=False) == (3, 3)
