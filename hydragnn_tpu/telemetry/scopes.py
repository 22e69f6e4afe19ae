"""The one vocabulary of ``jax.named_scope`` names the compiled programs carry
(docs/OBSERVABILITY.md, "Device time by scope").

A named scope is operation metadata: it emits no instruction, and with the
profiler off it costs nothing. With it on, XLA writes each operation's
``op_name`` — ``jit(step)/jvp(hydragnn.train_step)/HydraGNN/conv_1/
hydragnn.agg.stats.csr/...`` — into the ``tf_op`` stat of that operation's
event metadata in the trace, where ``graftbench/xplane_scopes.py`` reads it.
flax writes the module path (``conv_1``, ``bn_0``, ``head_2``) itself, and
differentiation wraps the path in ``jvp(...)`` / ``transpose(...)``; so the
names here say only what neither does: which program this is, and which
operations are edge gathers, segment reductions (and by which arm), the
read-out, the loss, the optimizer and the gradient all-reduce.

All names are ``hydragnn.<layer>[.<what>[.<arm>]]``. Every site imports its
name from here; ``tests/test_scopes.py`` holds the compiled programs to this
table.
"""

from __future__ import annotations

import contextlib
from contextvars import ContextVar

# Bump when a name changes meaning. It is folded into the compile-cache keys
# (cache/jaxcache.py, cache/graftcache) because JAX leaves operation metadata
# out of them: an executable cached under the old names would otherwise be
# served for the new program, scopes and all.
VERSION = 1

# Roots: which program an operation belongs to.
TRAIN_STEP = "hydragnn.train_step"
TRAIN_EPOCH_SCAN = "hydragnn.train_epoch_scan"
EVAL_STEP = "hydragnn.eval_step"
ROOTS = (TRAIN_STEP, TRAIN_EPOCH_SCAN, EVAL_STEP)

# Leaves.
GATHER = "hydragnn.gather"  # node -> edge row gathers (backward: scatter-adds;
# the receiver side's sorted sums on the sorted arm, aggregate.gather_sorted)
POOL = "hydragnn.pool"  # graph read-out
# What depends on positions alone (PaiNN): edge vectors, lengths, the radial
# basis, the cutoff, and each block's filter Dense over them. Its two
# position gathers stay under GATHER (the innermost name wins).
GEOM = "hydragnn.geom"
# LFM2's two token mixers (models/lfm2.py): the gated short convolution's
# shifted reads and products, and attention's head norms, RoPE and blockwise
# softmax. Their projections stay with the module (Dense).
LFM2_CONV = "hydragnn.lfm2.conv"
LFM2_ATTN = "hydragnn.lfm2.attn"
# Laguna's two kinds of attention (models/laguna.py): the query scaling,
# rotary, the attention kernel's calls and the gate's product, under the
# complete causal graph and under the causal band of the sliding window.
ATTN_FULL = "hydragnn.attn.full"
ATTN_WINDOW = "hydragnn.attn.window"
# Mistral-Small-4's latent attention (models/mistral4.py): the two low-rank
# chains with their norms, the rotation of the rotary parts and the
# concatenation into whole heads; the causal kernel's calls stay under
# ATTN_FULL (PR 39; names added, none changed).
ATTN_LATENT = "hydragnn.attn.latent"
# The shared expert beside the routed ones, in that block alone (Laguna's
# stays with its module, where its readers book it).
MOE_SHARED = "hydragnn.moe.shared"
# The serving engine's reply of a class head on a token family
# (HydraGNN.score_tokens): the head's matmul in row blocks, the log-softmax
# and the pick of the next token's log-probability.
HEAD_LOGPROB = "hydragnn.head.logprob"
# The Mamba mixer of a state-space layer (models/jamba.py): the depthwise
# causal convolution and its silu; the split of ``u W_x``, the three inner
# norms, ``W_dt`` and the softplus; the selective scan's calls with the gate
# (ops/selective_scan.py). The other projections stay with the module (PR 45;
# names added, none changed).
SSM_CONV = "hydragnn.ssm.conv"
SSM_DT = "hydragnn.ssm.dt"
SSM_SCAN = "hydragnn.ssm.scan"
# The routed experts: router, top-k, the sort by expert, both row
# permutations and the weighting; and the grouped matmuls alone.
MOE_ROUTE = "hydragnn.moe.route"
MOE_EXPERTS = "hydragnn.moe.experts"
LOSS = "hydragnn.loss"
OPTIMIZER = "hydragnn.optimizer"  # update, apply, loss-scale and guard selects
GRAD_SYNC = "hydragnn.grad_sync"  # the mesh step's psums of gradients/counts
AGG_PNA = "hydragnn.agg.pna"  # PNA's bundle; its stats/extrema nest inside

AGG_WHATS = ("sum", "count", "sum_count", "mean", "stats", "extrema", "softmax")
# The route ops/aggregate.py took at trace time: masked XLA segment ops, the
# sorted prefix path with searched or with precomputed (CSR) boundaries, the
# Pallas kernel over the CSR boundaries (the extrema's scan over receiver
# runs, ops/extrema_scan.py), the sorted arm's wide rows as one XLA
# scatter-add told the ids are sorted (PR 32; a name added, none changed).
AGG_ARMS = ("xla", "sorted", "csr", "pallas_csr", "scatter_sorted")


def agg(what: str, arm: str) -> str:
    if what not in AGG_WHATS or arm not in AGG_ARMS:
        raise ValueError(f"not in the scope vocabulary: agg {what!r} {arm!r}")
    return f"hydragnn.agg.{what}.{arm}"


VOCABULARY = frozenset(
    ROOTS
    + (GATHER, POOL, GEOM, LFM2_CONV, LFM2_ATTN, ATTN_FULL, ATTN_WINDOW)
    + (MOE_ROUTE, MOE_EXPERTS, ATTN_LATENT, MOE_SHARED, HEAD_LOGPROB)
    + (LOSS, OPTIMIZER, GRAD_SYNC, AGG_PNA, SSM_CONV, SSM_DT, SSM_SCAN)
    + tuple(agg(w, a) for w in AGG_WHATS for a in AGG_ARMS)
)

_IN_AGG: ContextVar[bool] = ContextVar("hydragnn_agg_scope_open", default=False)


@contextlib.contextmanager
def agg_scope(what: str, arm: str):
    """``hydragnn.agg.<what>.<arm>`` round a segment reduction's entry point.
    The entry points call each other (``fused_segment_sum`` is
    ``fused_segment_sum_count``'s first output, ``segment_mean`` is a sum over
    a count): the OUTERMOST one, the one a conv called, names the operations,
    and a nested one adds nothing. A ``custom_vjp``'s backward is traced after
    this context has closed, and JAX gives it the call site's name stack."""
    if _IN_AGG.get():
        yield
        return
    import jax  # lazily, like graftel: the telemetry package imports no jax

    token = _IN_AGG.set(True)
    try:
        with jax.named_scope(agg(what, arm)):
            yield
    finally:
        _IN_AGG.reset(token)
