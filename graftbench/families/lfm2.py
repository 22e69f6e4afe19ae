"""LFM2 (``model_type: "LFM2"``): the plain encoder of one sequence, the
routing check, and the counts.

LiquidAI LFM2-8B-A1B, ``model_type`` ``lfm2_moe``
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json; the
block as ``transformers`` ``modeling_lfm2_moe.py`` has it). A token is a
node, a sequence a graph, ``pos[:, 0]`` the token's place. With d the hidden
size and ``rms(x, w) = x * rsqrt(mean(x^2) + eps) * w``:

  h0 = E[token];  layer:  h += op(rms(h));  h += ffn(rms(h));  out = rms(h)
  conv   (B, C, u) = split3(W_in x);  z = B * u;
         c_i = k2 * z_i + k1 * z_{i-1} + k0 * z_{i-2}  (zero before the start);
         W_out (C * c)
  attn   q, k, v = W_q x, W_k x, W_v x  (H / KV / KV heads of hd);  rms over
         each head of q and of k;  RoPE(theta) at the token's place, halves
         convention;  causal softmax(q k / sqrt(hd)), a key-value head shared
         by H / KV query heads;  W_o
  dense  W2(silu(W1 x) * W3 x)                      (the leading layers)
  routed s = sigmoid(W_g x) over ALL experts;  the K largest of s + b chosen;
         w_e = s_e / (sum over the chosen + 1e-6) * routed_scaling_factor;
         y = sum over the chosen AND HELD of w_e W2e(silu(W1e x) * W3e x)

Here attention is a masked softmax over the whole sequence (in row blocks),
the experts a Python loop over the held ones, every matmul float32 at
``highest``; the program sorts rows by expert and multiplies ragged groups
with operands rounded to bf16. Given the same share (``num_experts_held``
from ``experts_offset``), what the absent experts would add is left out here
as there.

**Routing is discrete**, so the comparison is made continuous: ``forward``
takes the experts the PROGRAM chose in each routed layer, fails unless each
chosen set is a top-K of this file's own ``s + b`` within ``ROUTE_EPS``, and
then routes as the program did. ``ROUTER_EPS`` holds the router itself to
float32: the same test on scores computed HERE from the program's own router
input, where nothing upstream can differ.

Departures from the published model, each as the program has them: no
auxiliary balance loss (the config names none); the expert bias ``b`` is a
buffer that nothing updates; an untied head (the source ties it to the
embedding); this rank's share of the experts and of the vocabulary.
"""

import jax
import jax.numpy as jnp
import numpy as np

from graftbench import flops

# Program against reference on the logits of a sequence, three limits.
#
# The stated precision rounds every matmul's OPERANDS to bf16 (2^-9 relative
# an operand); that alone puts the program 1.7e-2 of the logits' norm from
# this float32 reference, and what a precision below adds (activations or
# the softmax kept in bf16 too) adds in quadrature, so the two readings lie
# close. What tells them apart is the RELATIVE L2 DISTANCE of a sequence's
# 16.8M logits: an average over so many roundings that it hardly moves with
# the seed. Readings at the configuration's widths, 6 layers, 1024 tokens
# (PERF.md section 6, PR 31): the program on the chip 1.703e-2 to 1.711e-2
# over its seeds, and 1.709e-2 / 1.718e-2 emulated on the CPU (this file
# with operands rounded, two seeds); the reference with bf16 operands AND the
# residual stream, every activation it keeps and the softmax's probabilities
# rounded to bf16 reads 2.180e-2 / 2.190e-2 and comes out NOT correct. The
# limit is 13% over the first reading and 11% under the second; both move
# by half a percent between seeds.
REL_L2 = 1.94e-2
# Elementwise, |a - b| <= ATOL + RTOL |b| on logits of rms 1.0: the largest
# |a - b| / (1 + |b|) the program reads is 0.085 (an extreme of 16.8M
# elements, 5 sigma of the distance above), 0.11 in the precision below; a
# mis-wired layer, a wrong norm or a missing residual is off by O(1).
ATOL = RTOL = 0.25
# A chosen expert's own score may lie this far under the reference's K-th
# largest, and a passed-over one this far above it. The program's activations
# reach the router through matmuls with bf16 operands and the reference's
# through float32 ones, so the two routers see inputs 1e-2 apart and scores
# (sigmoid's slope is at most 1/4) up to 1.3e-2 apart at the worst of the
# 8192 x 4 positions of a check: readings 1.24e-2 and 1.31e-2 on the chip,
# 1.02e-2 and 1.23e-2 emulated, 1.9e-2 in the precision below. The scores
# spread over (0, 1) and a wrong top-k, a dropped bias or another expert
# order reads 0.1 to 0.5 (tests/test_lfm2.py flips one choice). This margin
# cannot see the ROUTER's own precision (its error is under what reaches it
# from upstream); the next one does.
ROUTE_EPS = 3e-2
# The same margin on scores computed here from the PROGRAM's router input:
# only the router's own arithmetic differs. At Precision.HIGHEST it reads
# 0.0 on the chip (the same top-K, position for position); a router whose
# matmul rounds its operands to bf16 is off by 1e-3 at some of the positions
# of any check (graftbench/tests/test_lfm2_cell.py shows one) and fails.
ROUTER_EPS = 2e-5

_ROWS = 256  # query rows a block of the masked softmax


class Exact:
    """float32 throughout. The tests' controls subclass it to compute in the
    precision below (operands, or operands and what is kept between
    operations, rounded to bf16)."""

    @staticmethod
    def mm(a, w):
        return a @ w

    @staticmethod
    def keep(x):
        return x


def bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _conv(p, x, plain):
    d = x.shape[1]
    bcu = plain.keep(plain.mm(x, p["in_proj"]["kernel"]))
    b, c, u = bcu[:, :d], bcu[:, d : 2 * d], bcu[:, 2 * d :]
    z = b * u
    k = p["kernel"]
    taps = k.shape[0]
    conv = jnp.zeros_like(z)
    for back in range(taps):
        moved = jnp.concatenate([jnp.zeros((back, d), z.dtype), z[: z.shape[0] - back]])
        conv = conv + k[taps - 1 - back] * moved
    return plain.mm(plain.keep(c * conv), p["out_proj"]["kernel"])


def _rope(x, place, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = place[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, x, place, cfg, plain):
    n = x.shape[0]
    h, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = plain.mm(x, p["q_proj"]["kernel"]).reshape(n, h, hd)
    k = plain.mm(x, p["k_proj"]["kernel"]).reshape(n, kv, hd)
    v = plain.keep(plain.mm(x, p["v_proj"]["kernel"])).reshape(n, kv, hd)
    q = plain.keep(_rope(_rms(q, p["q_layernorm"]["weight"], cfg.norm_eps), place, cfg.rope_theta))
    k = plain.keep(_rope(_rms(k, p["k_layernorm"]["weight"], cfg.norm_eps), place, cfg.rope_theta))
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    out = []
    for start in range(0, n, _ROWS):
        rows = jnp.arange(start, min(start + _ROWS, n))
        s = jnp.stack([
            plain.mm(q[rows, head], k[:, head].T) for head in range(h)
        ]) * hd ** -0.5  # [h, rows, n]
        s = jnp.where(jnp.arange(n)[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        prob = plain.keep(jax.nn.softmax(s, axis=-1))
        out.append(jnp.stack(
            [plain.mm(prob[head], v[:, head]) for head in range(h)], axis=1
        ))
    y = plain.keep(jnp.concatenate(out).reshape(n, h * hd))
    return plain.mm(y, p["out_proj"]["kernel"])


def _swiglu(x, w1, w3, w2, plain):
    return plain.mm(plain.keep(jax.nn.silu(plain.mm(x, w1)) * plain.mm(x, w3)), w2)


def top_k_margin(scores, chosen, k):
    """How far ``chosen`` [n, k] is from being a top-k of ``scores`` [n, E]:
    the largest amount by which a chosen score lies under the k-th largest
    or a passed-over one above it (0 for a top-k; inf for a set that is not
    k distinct experts)."""
    n, experts = scores.shape
    chosen = np.asarray(chosen)
    hit = np.zeros((n, experts), bool)
    hit[np.arange(n)[:, None], chosen] = True
    if chosen.shape != (n, k) or (hit.sum(axis=1) != k).any():
        return float("inf")
    scores = np.asarray(scores, np.float64)
    kth = np.sort(scores, axis=1)[:, -k]
    under = kth - np.where(hit, scores, np.inf).min(axis=1)
    over = np.where(hit, -np.inf, scores).max(axis=1) - kth
    return float(max(under.max(), over.max(), 0.0))


def _routed(p, x, cfg, plain, routing, report, name=None):
    gate, bias = p["gate"], p["expert_bias"]
    k = cfg.num_experts_per_tok
    s = jax.nn.sigmoid(x @ gate)  # the router is float32 in every precision
    biased = s + bias if cfg.use_expert_bias else s
    if routing is None:
        chosen = jax.lax.top_k(biased, k)[1]
        if report is not None:  # what a program computed this way would return
            report.setdefault("routing", {})[name] = {"chosen": chosen, "router_in": x}
    else:
        chosen = jnp.asarray(routing["chosen"])
    if routing is not None and report is not None:
        report["route_margin"] = max(
            report["route_margin"], top_k_margin(biased, chosen, k)
        )
        own = jax.nn.sigmoid(jnp.asarray(routing["router_in"]) @ gate)
        report["router_margin"] = max(
            report["router_margin"],
            top_k_margin(own + bias if cfg.use_expert_bias else own, chosen, k),
        )
    weight = jnp.take_along_axis(s, chosen, axis=1)
    if cfg.norm_topk_prob:
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
    weight = weight * cfg.routed_scaling_factor
    y = jnp.zeros_like(x)
    for e in range(cfg.num_experts_held):  # the absent experts add nothing here
        w_e = jnp.sum(jnp.where(chosen == e + cfg.experts_offset, weight, 0.0), axis=1)
        if report is not None:
            report["rows_held"] = report["rows_held"] + jnp.sum(
                chosen == e + cfg.experts_offset
            )
        y = y + w_e[:, None] * _swiglu(x, p["w1"][e], p["w3"][e], p["w2"][e], plain)
    return y


def encode(model, params, stats, graph, routing=None, plain=Exact, report=None):
    """[n, d]: the stack's output for ONE sequence. ``routing``: per routed
    layer (``conv_<i>``) the program's ``chosen`` [n, K] and ``router_in``
    [n, d] for this sequence; None routes by this file's own top-k (what
    ``reference.forward`` and a test of the uncut layer want). ``report``, a
    dict, collects the routing margins (on concrete arrays: not under a
    transformation) and the rows routed to held experts."""
    cfg = model.lfm2
    if report is not None:
        report.update(route_margin=0.0, router_margin=0.0, rows_held=0)
    lo, hi = cfg.token_minmax
    ids = jnp.round(graph["x"][:, 0] * (hi - lo) + lo).astype(jnp.int32)
    h = params["conv_embed"]["embedding"][ids]
    place = graph["pos"][:, 0]
    for i in range(model.num_conv_layers):
        p, name = params[f"conv_{i}"], f"conv_{i}"
        x = plain.keep(_rms(h, p["operator_norm"]["weight"], cfg.norm_eps))
        if cfg.layer_types[i] == "conv":
            h = plain.keep(h + _conv(p["conv"], x, plain))
        else:
            h = plain.keep(h + _attention(p["self_attn"], x, place, cfg, plain))
        x = plain.keep(_rms(h, p["ffn_norm"]["weight"], cfg.norm_eps))
        f = p["feed_forward"]
        if cfg.routed(i):
            layer = None if routing is None else routing[name]
            h = plain.keep(h + _routed(f, x, cfg, plain, layer, report, name))
        else:
            h = plain.keep(h + _swiglu(
                x, f["w1"]["kernel"], f["w3"]["kernel"], f["w2"]["kernel"], plain
            ))
    return _rms(h, params["conv_norm"]["weight"], cfg.norm_eps)


def logits(model, params, graph, routing=None, plain=Exact):
    """([n, classes] logits of the one node head, the report of ``encode``)
    for one sequence, eagerly on the host in float32 at ``highest``."""
    report = {}
    with jax.default_matmul_precision("highest"):
        x = encode(model, params, None, graph, routing, plain, report)
        head = params["head_0"]["mlp"]["dense_0"]
        out = plain.mm(plain.keep(x), head["kernel"]) + head["bias"]
    report["rows_held"] = int(report["rows_held"])
    return np.asarray(out), report


def compare(got, want):
    """(max |diff|, relative L2, failure or None) of a sequence's logits
    under this file's three limits."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf"), float("inf"), "shape or non-finite logits"
    err = np.abs(got - want)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    fail = None
    if (err > ATOL + RTOL * np.abs(want)).any():
        fail = f"|program - reference| {err.max():.3e} beyond atol={ATOL} rtol={RTOL}"
    elif rel > REL_L2:
        fail = f"relative L2 distance {rel:.3e} beyond {REL_L2}"
    return float(err.max()), rel, fail


# ------------------------------------------------------------------- counts
def moe_counts(arch: dict, rows: float) -> dict:
    """Operations and bytes of ONE forward pass of the grouped matmuls over
    ``rows`` routed rows to held experts (all routed layers together; what
    the program's counter ``moe_rows_held`` counts a step): three
    projections a row, and each held expert's three matrices read once a
    layer. A train step is three of it."""
    d, f = arch["hidden_dim"], arch["moe_intermediate_size"]
    held = arch.get("num_experts_held", arch["num_experts"])
    routed = arch["num_conv_layers"] - arch["num_dense_layers"]
    return {
        "ops": 2 * rows * 3 * d * f + 2 * rows * f,
        "bytes": flops.B * (routed * held * 3 * d * f + rows * (2 * d + 3 * f + d)),
    }


def counts(arch, nodes, edges, routed_rows=None):
    """One forward pass over ``nodes`` real tokens (``edges`` is not read: no
    edge list is). ``routed_rows``: the rows routed to held experts, all
    routed layers together, as the program counted them; None takes what
    uniform routing would send (``K * held / experts`` a token and layer)."""
    d, layers = arch["hidden_dim"], arch["num_conv_layers"]
    h, kv, hd = arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]
    dense_layers = arch["num_dense_layers"]
    held = arch.get("num_experts_held", arch["num_experts"])
    if routed_rows is None:
        routed_rows = (
            (layers - dense_layers) * nodes * arch["num_experts_per_tok"]
            * held / arch["num_experts"]
        )
    parts = [flops.part(0, flops.B * (2 * nodes * d + nodes))]  # the embedding rows
    for kind in arch["layer_types"][:layers]:
        parts.append(flops.part(4 * nodes * d, flops.B * 2 * nodes * d))  # norm
        if kind == "conv":
            parts += [
                flops.dense(nodes, d, 3 * d),
                # B*u, three taps, C*c: elementwise over [n, d]
                flops.part(8 * nodes * d, flops.B * 4 * nodes * d),
                flops.dense(nodes, d, d),
            ]
        else:
            # Causal: a token attends to half its sequence on average. The
            # sequences are counted from the loaders' band graph, which this
            # family carries and does not read: a line of n nodes at radius
            # 2.5 has 4n - 6 directed edges, so 4 nodes - edges = 6 sequences.
            sequences = max((4 * nodes - edges) / 6, 1)
            scores = nodes * (nodes / sequences + 1) / 2
            parts += [
                flops.dense(nodes, d, (h + 2 * kv) * hd),
                flops.part(
                    4 * scores * h * hd + 5 * scores * h,
                    flops.B * nodes * (2 * h + 2 * kv) * hd,
                ),
                flops.dense(nodes, h * hd, d),
            ]
    ffn = arch["intermediate_size"]
    for _ in range(dense_layers):
        parts += [
            flops.part(4 * nodes * d, flops.B * 2 * nodes * d),
            flops.dense(nodes, d, ffn), flops.dense(nodes, d, ffn),
            flops.dense(nodes, ffn, d),
        ]
    for _ in range(layers - dense_layers):
        parts += [
            flops.part(4 * nodes * d, flops.B * 2 * nodes * d),
            flops.dense(nodes, d, arch["num_experts"]),  # the router
        ]
    moe = moe_counts(arch, routed_rows)
    parts.append(flops.part(int(moe["ops"]), int(moe["bytes"])))
    parts.append(flops.part(4 * nodes * d, flops.B * 2 * nodes * d))  # final norm
    return parts, d
