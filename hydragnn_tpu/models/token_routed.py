"""The feed-forward layers of the token stacks (``models/families.py``
``TOKEN_STACKS``): the dense SwiGLU and the ROUTED layer, with everything the
routed layer needs and nothing a family owns.

The routed feed-forward is a per-node update that is told which experts it
holds (``num_experts_held`` from ``experts_offset``): it routes over all
``num_experts``, computes its own experts' part of the result and leaves out
the rest -- one rank's share of an expert-parallel layer, without the
exchange (there is no code here that stands in for the absent ranks). What
this module hides: the router's two score functions, the sort of the ``K N``
assignments by expert, the grouped matmul (megablox on the TPU at tiles fitted
to the matrices, ``ragged_dot`` elsewhere), the compact ``[C, ·]`` row arrays
with their fall-back passes and the hand-written derivative of a loop of
unknown length, and what a routed layer sows: the experts each node chose, the
router's input and the step's counters (``INTERMEDIATES``, ``COUNTERS``,
``split_intermediates``: read by the train step, the serving engine and the
benchmark's check). ``pass_rows`` answers the engine how many rows one pass
holds. The fields read off a stack's sizes object are listed ONCE, in
``token_common.py``'s docstring. No family's name is in here, and this module
imports no family's file.

Precision, as the configurations state it: float32 parameters and sigmoid /
softmax; the experts' matrix multiplications at the backend's default for
float32 operands (on the TPU one bf16 pass with float32 accumulation), the
router's ``W_g x`` at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..ops.segment import execution_platform
from ..telemetry import scopes

# The collection the routed layers sow into: the experts each node chose and
# the router's input (read by the benchmark's check, which asks for the
# collection; a no-op in every program that does not), and the step's
# counters (asked for by the train step, train/trainer.py).
INTERMEDIATES = "intermediates"
COUNTERS = ("moe_rows_held", "moe_load_max", "moe_load_min", "moe_layers_compact")


def experts_share(arch: dict) -> Tuple[int, int]:
    """(``num_experts_held``, ``experts_offset``) of ``arch``: this rank's
    share of the routed experts, all of them unless told."""
    held = int(arch.get("num_experts_held", arch["num_experts"]))
    offset = int(arch.get("experts_offset", 0))
    if not 0 < held <= held + offset <= int(arch["num_experts"]):
        raise ValueError(
            f"experts {offset}..{offset + held} are not among "
            f"{arch['num_experts']}"
        )
    return held, offset


class DenseFFN(nn.Module):
    """SwiGLU: ``W2(silu(W1 x) * W3 x)``."""

    features: int
    width: int

    @nn.compact
    def __call__(self, x):
        a = nn.silu(nn.Dense(self.width, use_bias=False, name="w1")(x))
        b = nn.Dense(self.width, use_bias=False, name="w3")(x)
        return nn.Dense(self.features, use_bias=False, name="w2")(a * b)


_expert_init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
# (rows, contraction, columns) tiles of the TPU's grouped-matmul kernel; the
# row tile has to divide a row array's rows: ``_capacity`` is a multiple of
# it, and where a layer holds every expert the K N rows of a bucket
# (multiples of 64 nodes) are whole row tiles from K = 4 (the serving
# ladder's rungs of Mellum2's layer, K = 8: 98k-201k rows in ONE pass, 4.0 GB
# of temporaries at the largest, PERF.md section 6, PR 41); row arrays of
# another length go through ``ragged_dot`` (``grouped_matmul``).
GMM_TILING = (256, 1024, 1024)
# Rows of the routed layer's compact path over the rank's uniform share
# ``K N held / experts`` (``_capacity``).
CAPACITY_FACTOR = 1.5


def _capacity(assignments: int, held: int, experts: int) -> int:
    """Rows of the compact path for a layer that holds ``held`` of
    ``experts``: its share of the ``assignments`` under uniform routing times
    ``CAPACITY_FACTOR``, up to a whole row tile of the grouped matmul."""
    tile = GMM_TILING[0]
    share = assignments * held * CAPACITY_FACTOR / experts
    return -(-math.ceil(share) // tile) * tile


def pass_rows(cfg, nodes: int) -> int:
    """Rows of ONE pass of a routed layer of sizes ``cfg`` over ``nodes``
    nodes: ``_capacity`` of its ``K nodes`` assignments, never more than all
    of them. A step that sends the layer more takes a further pass (what the
    serving engine counts a flush's routing against)."""
    assignments = nodes * cfg.num_experts_per_tok
    rows = _capacity(assignments, cfg.num_experts_held, cfg.num_experts)
    return min(rows, assignments)


def _gmm_tile(tile: int, width: int) -> int:
    """A contraction or column tile for a matrix ``width`` wide: no wider
    than the matrix (a fine-grained expert, 512 wide, is narrower than a
    tile, and the kernel would multiply the tile); and where the last tile
    would be under half full, that remainder spread over the whole tiles
    before it (2304 is 2.25 tiles of 1024: 2 tiles of 1152) if that leaves
    whole lanes: the kernel multiplies a whole tile for a remainder. On the
    chip at 1,658 rows an expert 1152 beat 1024 by 12-15% and 768 by 1-5%
    (PERF.md section 6, PR 41). LFM2's 1792 keeps 1024 (its last tile is three
    quarters full)."""
    tile = min(tile, width)
    whole, rest = divmod(width, tile)
    if 0 < rest < tile // 2 and width % (128 * whole) == 0:
        tile = width // whole
    return tile


def _gmm_tiles(m: int, k: int, n: int):
    """``GMM_TILING`` fitted to the matrices (``_gmm_tile``)."""
    tm, tk, tn = GMM_TILING
    return tm, _gmm_tile(tk, k), _gmm_tile(tn, n)


@jax.custom_vjp
def _gmm_tpu(lhs, rhs, sizes):
    """``lhs[rows of group g] @ rhs[g]`` on the TPU: the grouped-matmul Pallas
    kernel of JAX's own library (megablox), operands rounded to bf16,
    float32 accumulation and results -- the stated precision, and what
    ``ragged_dot`` does there by default. Chosen over ``ragged_dot`` on the
    chip (PERF.md section 6, PR 31): XLA's own grouped kernel drops the
    operation's name, so its time could be booked to no scope."""
    return _gmm_tpu_fwd(lhs, rhs, sizes)[0]


def _gmm_tpu_fwd(lhs, rhs, sizes):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    lhs16, rhs16 = lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16)
    out = gmm(lhs16, rhs16, sizes, jnp.float32, _gmm_tiles)
    return out, (lhs16, rhs16, sizes)


def _gmm_tpu_bwd(residuals, ct):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs16, rhs16, sizes = residuals
    ct16 = ct.astype(jnp.bfloat16)
    d_lhs = gmm(ct16, rhs16, sizes, jnp.float32, _gmm_tiles, transpose_rhs=True)
    d_rhs = tgmm(lhs16.swapaxes(0, 1), ct16, sizes, jnp.float32, _gmm_tiles)
    return d_lhs, d_rhs, None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def grouped_matmul(lhs, rhs, sizes):
    """``[rows, k] x [groups, k, n] -> [rows, n]``: rows ``sizes[0]`` first
    by group 0, the next ``sizes[1]`` by group 1, ...; rows past the last
    group are NOT multiplied and hold whatever the kernel left there. The
    TPU's kernel takes whole row tiles: fewer rows than that (an initializer's
    example batch of 4 nodes, as ``InferenceEngine.from_config`` builds one)
    go through ``ragged_dot`` there too."""
    if execution_platform() == "tpu" and lhs.shape[0] % GMM_TILING[0] == 0:
        return _gmm_tpu(lhs, rhs, sizes)
    return jax.lax.ragged_dot(lhs, rhs, sizes)


def _held_experts(x, w1, w3, w2, weight, order, sizes, start=0, *, cap: int):
    """``sum over a node's K assignments of weight * SwiGLU_e(x)`` for the
    assignments to held experts that stand at ``start .. start + cap`` of the
    sorted order, over row arrays of ``cap`` rows. With ``cap = K N`` that is
    every assignment. A pure function of arrays."""
    n, d = x.shape
    k = weight.shape[1]
    if cap < n * k:
        order = jax.lax.dynamic_slice(
            jnp.pad(order, (0, -(n * k) % cap)), (start,), (cap,)
        )
        ends = jnp.cumsum(sizes) - start
        sizes = jnp.clip(ends, 0, cap) - jnp.clip(ends - sizes, 0, cap)
    # Zero outside the held groups, on the way in and (through the select's
    # transpose) on the way back: what a grouped matmul leaves in rows of no
    # group is its own business.
    live = (jnp.arange(cap) < sizes.sum())[:, None]
    node = order // k

    def grouped(lhs, rhs):
        return jnp.where(live, grouped_matmul(lhs, rhs, sizes), 0.0)

    with jax.named_scope(scopes.MOE_ROUTE):
        rows = jnp.where(live, x[node], 0.0)
    with jax.named_scope(scopes.MOE_EXPERTS):
        hidden = nn.silu(grouped(rows, w1)) * grouped(rows, w3)
        out = grouped(hidden, w2)
    with jax.named_scope(scopes.MOE_ROUTE):
        out = out * weight.reshape(-1)[order][:, None]
        return jnp.zeros_like(x).at[node].add(out)


_FLOATS = 5  # x, w1, w3, w2, weight lead the operands; order and sizes end them


def _further_passes(cap: int, operands, first, one_pass):
    """``first`` plus ``one_pass(start)`` for every further ``cap`` sorted
    rows the live rows reach into: none on a step whose live rows fit in
    ``cap``, and no loop at all where ``cap`` is every row."""
    weight, sizes = operands[_FLOATS - 1], operands[-1]
    if cap >= weight.size:
        return first

    def one_more(carry):
        start, total = carry
        return start + cap, jax.tree_util.tree_map(jnp.add, total, one_pass(start))

    if not isinstance(sizes, jax.core.Tracer):
        # Run eagerly (the initializer): the live rows are known, and a
        # ``while`` would be compiled a layer for passes that are never made
        # (a second each on the TPU, too short for the persistent cache).
        carry, live = (cap, first), sizes.sum()
        while carry[0] < live:
            carry = one_more(carry)
        return carry[1]
    return jax.lax.while_loop(
        lambda carry: carry[0] < sizes.sum(), one_more, (jnp.int32(cap), first)
    )[1]


def _one_pass(cap: int, operands):
    return lambda start: _held_experts(*operands, start, cap=cap)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _in_passes(cap: int, *operands):
    """``_held_experts`` over ``cap`` sorted rows at a time until the live
    rows are through: ONE pass on a step whose live rows fit in ``cap``, as
    many more as a step that overflows needs, each adding its part of the
    nodes' sums. Differentiated by hand, because a loop of unknown length has
    no reverse mode: the first pass keeps what its backward needs, as any
    straight-line code; a further pass keeps nothing, and its backward runs
    its forward again."""
    one_pass = _one_pass(cap, operands)
    return _further_passes(cap, operands, one_pass(0), one_pass)


def _in_passes_fwd(cap, *operands):
    y, pullback = jax.vjp(functools.partial(_held_experts, cap=cap), *operands)
    return _further_passes(cap, operands, y, _one_pass(cap, operands)), (operands, pullback)


def _in_passes_bwd(cap, residuals, ct):
    operands, pullback = residuals

    def again(start):
        _, pullback = jax.vjp(
            lambda *floats: _held_experts(*floats, *operands[_FLOATS:], start, cap=cap),
            *operands[:_FLOATS],
        )
        return pullback(ct)

    grads = _further_passes(cap, operands, pullback(ct)[:_FLOATS], again)
    return (*grads, None, None)


_in_passes.defvjp(_in_passes_fwd, _in_passes_bwd)


class RoutedFFN(nn.Module):
    """``s = sigmoid(W_g x)`` over all ``num_experts`` (``softmax(W_g x)``
    for a stack whose sizes say ``scoring_func = "softmax"``); the
    ``num_experts_per_tok`` largest of ``s + b`` are chosen (``b`` the expert
    bias: a buffer, no gradient); ``w_e = s_e / (sum over the chosen + 1e-6)``
    times ``routed_scaling_factor``, the sum over ALL chosen, held or not;
    ``y = sum over the chosen AND held of w_e SwiGLU_e(x)``. Dropless,
    compact with a fall-back.

    The ``K N`` assignments (node-major: node ``i``'s are rows ``K i ..``)
    are sorted by expert (stable), the held experts' rows first and, in ONE
    trailing group that is never multiplied, the assignments to absent
    experts and those of padding nodes. The held rows are gathered from
    their nodes, multiplied by one grouped matmul a projection, weighted, and
    added into their nodes' rows again (``_held_experts``).

    Static shapes, sized by what this rank can be sent and not by every
    assignment: the row arrays are ``[C, ·]``, ``C`` the rank's share of the
    ``K N`` assignments under uniform routing times ``CAPACITY_FACTOR``
    (``_capacity``; ``capacity`` overrides it: the tests' handle). A step
    whose routing sends the layer more than ``C`` rows falls back on further
    passes over the next ``C`` sorted rows until every live row has met its
    expert (``_in_passes``): no assignment is dropped, clipped or re-routed,
    and no ``[K N, ·]`` array exists on either path. A layer with
    ``C >= K N`` (one that holds every expert; tiny inputs) makes its one
    pass over all ``K N`` rows and compiles no loop."""

    features: int
    cfg: Any  # a stack's sizes: the routing fields token_common.py lists

    @nn.compact
    def __call__(self, x, node_mask, capacity=None):
        c = self.cfg
        n, d = x.shape
        experts, k, held, f = (
            c.num_experts, c.num_experts_per_tok, c.num_experts_held,
            c.moe_intermediate_size,
        )
        gate = self.param("gate", nn.initializers.lecun_normal(), (d, experts))
        bias = (
            self.param("expert_bias", nn.initializers.zeros, (experts,))
            if c.use_expert_bias else None
        )
        w1 = self.param("w1", _expert_init, (held, d, f))
        w3 = self.param("w3", _expert_init, (held, d, f))
        w2 = self.param("w2", _expert_init, (held, f, d))
        self.sow(INTERMEDIATES, "moe_router_in", x)
        with jax.named_scope(scopes.MOE_ROUTE):
            s = jnp.dot(x, gate, precision=jax.lax.Precision.HIGHEST)
            if c.scoring_func == "softmax":
                s = jax.nn.softmax(s, axis=-1)
            else:
                s = jax.nn.sigmoid(s)
            biased = s + jax.lax.stop_gradient(bias) if c.use_expert_bias else s
            _, chosen = jax.lax.top_k(biased, k)  # [N, K]
            # The chosen experts' own scores by a compare against an iota: a
            # gather of K N scalars costs a row each, forward and backward.
            picked = chosen[:, :, None] == jnp.arange(experts)[None, None, :]
            weight = jnp.sum(jnp.where(picked, s[:, None, :], 0.0), axis=-1)
            if c.norm_topk_prob:
                weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
            weight = weight * c.routed_scaling_factor
            local = chosen - c.experts_offset
            here = (local >= 0) & (local < held) & node_mask[:, None]
            group = jnp.where(here, local, held).reshape(-1)  # [K N]
            order = jnp.argsort(group, stable=True)  # expert order <- node-major
            sizes = (group[:, None] == jnp.arange(held)[None, :]).sum(
                axis=0, dtype=jnp.int32
            )
            cap = pass_rows(c, n) if capacity is None else min(capacity, n * k)
            y = _in_passes(cap, x, w1, w3, w2, weight, order, sizes)
            compact = (sizes.sum() <= cap) & (cap < n * k)
        self.sow(INTERMEDIATES, "moe_chosen", chosen)
        counted = (sizes.sum(), sizes.max(), sizes.min(), compact)
        for name, value in zip(COUNTERS, counted):
            self.sow(INTERMEDIATES, name, value.astype(jnp.float32))
        return y


def split_intermediates(tree) -> Tuple[dict, dict]:
    """What the routed layers sowed, as (per-layer dict of the check's
    arrays keyed ``conv_<i>``, the step's counters summed over the layers)."""
    per_layer, counters = {}, dict.fromkeys(COUNTERS, 0.0)
    for module, sub in (tree or {}).items():
        sown = sub.get("feed_forward", {})
        if "moe_chosen" in sown:
            per_layer[module] = {
                "chosen": sown["moe_chosen"][-1], "router_in": sown["moe_router_in"][-1],
            }
            for name in COUNTERS:
                counters[name] = counters[name] + sown[name][-1]
    return per_layer, counters
