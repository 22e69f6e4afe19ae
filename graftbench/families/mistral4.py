"""MISTRAL4 (``model_type: "MISTRAL4"``): the plain encoder of one document,
its log-probabilities, the routing check, and the counts.

mistralai Mistral-Small-4-119B-2603, ``model_type`` ``mistral4``
(https://huggingface.co/mistralai/Mistral-Small-4-119B-2603/blob/main/config.json).
A token is a node, a document a graph, ``pos[:, 0]`` the token's place. With
``rms(x, w) = x * rsqrt(mean(x^2) + eps) * w``, every layer:

  h0 = E[token];  h += attn(rms(h));  h += shared(rms(h)) + routed(rms(h));  out = rms(h)
  attn   c_q = rms(W_qa x);  q = W_qb c_q -> [H, nope + rot] = [q_nope | q_rot]
         [c_kv | k_rot] = W_kva x;  c_kv = rms(c_kv);
         W_kvb c_kv -> [H, nope + vd] = [k_nope | v]
         q_rot and k_rot turned at the token's place over the INTERLEAVED
         pairs (2i, 2i+1), in place; k_rot is ONE head, shared by all H;
         YaRN over the rot dimensions: pair i turns at
         ``(1 - g_i) theta^(-2i/rot) / factor + g_i theta^(-2i/rot)``,
         ``g_i = 1 - clip((i - low) / (high - low), 0, 1)``,
         ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``,
         ``c(b) = rot ln(L / (2 pi b)) / (2 ln theta)``; cos and sin times
         ``m(mscale) / m(mscale_all_dim)``, ``m(s) = 0.1 s ln(factor) + 1``
         q <- q (1 + beta ln(1 + floor(place / L)))
         a_i = sum_{j <= i} softmax_j(s [q_nope|q_rot]_i . [k_nope|k_rot]_j) v_j,
         ``s = (nope + rot)^-0.5 m(mscale_all_dim)^2``;  W_o a   (no bias)
  shared W2(silu(W1 x) * W3 x), whole on every rank
  routed p = softmax(W_r x) over ALL experts;  the K largest chosen (no group
         limit, no bias);  w_e = p_e / (sum over the chosen + 1e-6) * scaling;
         y = sum over the chosen AND HELD of w_e W2e(silu(W1e x) * W3e x)
  reply  logp_i = log softmax(W_head out_i + b)[token_{i+1}], 0 for the last

Here attention is a masked softmax over each block of rows, an expert a plain
SwiGLU over the rows that chose it, the rotation a turn of each pair where it
stands, every matmul float32 at ``highest``; the program runs Pallas kernels
over blocks with operands rounded to bf16, multiplies ragged groups, and
rotates in the halves convention after permuting the rotary columns of q and
k alike. Nothing here is imported from ``hydragnn_tpu/models/``; the sizes
are read off the model's ``mistral4`` field by the source's names.

**Routing is discrete**, and handled as ``families/lfm2.py`` handles it, with
what the serving engine returns: ``logprobs`` takes the experts the PROGRAM
chose (a reply's ``routing`` [T, routed layers x K]), fails unless each
chosen set is a top-K of this file's own router LOGITS within ``ROUTE_EPS``,
then routes as the program did. The margin is on the logits, not on the
softmax's scores: those lie near 1/128, their differences near 1e-3, and a
margin on them would say nothing a reader can judge; the softmax is monotone,
so the top-K of the one is the top-K of the other.

Assumed, because the catalog's row has no key for it (PAPERS.md): the
router's softmax and the absence of a correction bias; both ``mscale``
conventions; Llama-4's factor; ``eps``; YaRN's ``truncate`` true.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from graftbench import flops
from graftbench.families.lfm2 import Exact, _rms, _swiglu, bf16, top_k_margin  # noqa: F401

# Engine against reference on the log-probabilities of a document's tokens
# ([T] numbers of about -ln(vocab) +- 1.3), three limits. Readings at the
# configuration's widths, 5 layers (PERF.md section 2 and section 6, PR 39,
# has every one; "emulated" is ``python3 -m graftbench.token_readings`` on a
# host's CPU):
#
# * RELATIVE L2 DISTANCE of a document's log-probabilities from the float32
#   reference, the number that tells the stated precision from the one below.
#   It FALLS WITH THE DOCUMENT'S LENGTH, in both precisions alike (a token
#   with few keys before it is read less exactly than one whose attention
#   averages over thousands), so the limit follows the length:
#   ``rel_l2_limit(T) = REL_L2 (T / 2048)^-0.125``. The engine on the chip
#   (operands rounded to bf16, everything kept in float32) reads 8.92 to
#   9.18e-4 at 2048 tokens (limit 1.030e-3), 8.42 to 8.71e-4 at 3072
#   (0.979e-3), 8.14 to 8.27e-4 at 4096 (0.945e-3) and 7.51 to 7.87e-4 at 6144
#   (0.898e-3) over 22 readings of 16 runs; emulated, 9.02 to 9.28e-4 and 7.64 to 7.81e-4 at
#   2048 and 6144 (four seeds each). This file with operands AND the residual
#   stream, every kept activation and the softmax's probabilities rounded to
#   bf16 (``Below``, the precision below) reads 1.144 to 1.197e-3 at 2048 and
#   1.032 to 1.046e-3 at 6144 (four seeds each) and comes out NOT correct. The
#   limit lies 12-14% over the first reading's largest and 10-13% under the
#   second's smallest at either length; under ONE number for all lengths the
#   room would be 6% a side. (A reference at the STATED precision, ``Operands``,
#   was tried as a second limit and separates no better: the engine lies 6.2
#   to 7.3e-4 from it, the precision below 1.1e-3: rounding decorrelates
#   after the first matmul, PERF.md section 6.)
REL_L2, REL_L2_TOKENS, REL_L2_SLOPE = 1.03e-3, 2048, 0.125
# * Elementwise, |a - b| <= ATOL + RTOL |b| on log-probabilities of about
#   -9.7: the largest |a - b| the engine reads is 0.035 to 0.059 on the chip
#   (an extreme of 2048-6144 numbers), 0.045 to 0.062 in the precision below;
#   a mis-wired layer, a wrong frequency, a missing shared expert or scale is
#   off by O(1).
ATOL, RTOL = 0.2, 0.0
# * A chosen expert's router LOGIT may lie this far under the reference's
#   K-th largest, and a passed-over one this far above it (logits of rms
#   ~1.0: the router's input is a normed row, its matrix N(0, 1/d)). The
#   program's activations reach the router through bf16-operand matmuls:
#   readings 0.029 to 0.045 on the chip (16 runs) at the worst of a check's
#   (8-12 k) x 5 x 4 positions, 0.023 to 0.047 emulated; a wrong top-k or
#   another expert order reads 0.5 to 3 (tests/test_mistral4.py flips one).
ROUTE_EPS = 0.15

_ROWS = 512  # query rows a block of the masked softmax
_EXPERT_ROWS = 256  # an expert's rows are multiplied in whole blocks of it


class Operands(Exact):
    """The STATED precision, emulated: every matmul's operands rounded to
    bf16, float32 accumulation, everything kept in float32 (what the engine
    does on the chip)."""

    @staticmethod
    def mm(a, w):
        return bf16(a) @ bf16(w)


class Below(Operands):
    """The precision BELOW the stated one, the control of the limits: the
    operands rounded, and the residual stream, every kept activation and the
    softmax's probabilities rounded to bf16 too."""

    @staticmethod
    def keep(x):
        return bf16(x)


def sizes(model):
    """The stack's sizes, by the source's names."""
    return model.mistral4


def frequencies(rope, rot: int):
    """(inv [rot / 2] float32, the factor on cos and sin) of the source's
    ``rope_parameters`` over ``rot`` rotated dimensions, written out here from
    the formulas above (float64, by hand: the program has its own)."""
    i = np.arange(rot // 2, dtype=np.float64)
    plain = float(rope["rope_theta"]) ** (-2 * i / rot)
    if rope.get("rope_type", rope.get("type", "default")) != "yarn":
        return jnp.asarray(plain, jnp.float32), 1.0
    length, theta = rope["original_max_position_embeddings"], rope["rope_theta"]

    def c(b):
        return rot * math.log(length / (2 * math.pi * b)) / (2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), rot - 1)
    g = 1 - np.clip((i - low) / (high - low), 0, 1)
    inv = (1 - g) * plain / rope["factor"] + g * plain
    return jnp.asarray(inv, jnp.float32), mscale(rope, "mscale") / mscale(rope, "mscale_all_dim")


def mscale(rope, key: str) -> float:
    factor = float(rope.get("factor", 1.0))
    return 0.1 * float(rope.get(key, 0.0)) * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(rope, qk: int) -> float:
    return qk ** -0.5 * mscale(rope, "mscale_all_dim") ** 2


def _rope_dict(cfg):
    """The published ``rope_parameters`` again, from the program's sizes."""
    r = cfg.rope_parameters
    return {
        "rope_theta": r.rope_theta, "rope_type": r.rope_type, "factor": r.factor,
        "original_max_position_embeddings": r.original_max_position_embeddings,
        "beta_fast": r.beta_fast, "beta_slow": r.beta_slow,
        "mscale": cfg.mscale, "mscale_all_dim": cfg.mscale_all_dim,
        "llama_4_scaling_beta": cfg.llama_4_scaling_beta,
    }


def turn_pairs(x, place, inv, factor):
    """Each pair ``(2i, 2i+1)`` of the last axis turned by ``place * inv_i``,
    where it stands."""
    angle = place[:, None] * inv  # [n, rot / 2]
    cos, sin = factor * jnp.cos(angle)[:, None, :], factor * jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _attention(p, x, place, cfg, rope, plain):
    n, h = x.shape[0], cfg.num_attention_heads
    nope, rot, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps, rank = cfg.rms_norm_eps, cfg.kv_lora_rank
    c_q = plain.keep(_rms(plain.mm(x, p["q_a_proj"]["kernel"]), p["q_a_layernorm"]["weight"], eps))
    q = plain.mm(c_q, p["q_b_proj"]["kernel"]).reshape(n, h, nope + rot)
    kv_a = plain.mm(x, p["kv_a_proj_with_mqa"]["kernel"])
    c_kv = plain.keep(_rms(kv_a[:, :rank], p["kv_a_layernorm"]["weight"], eps))
    kv = plain.mm(c_kv, p["kv_b_proj"]["kernel"]).reshape(n, h, nope + vd)
    inv, factor = frequencies(rope, rot)
    q_rot = turn_pairs(q[..., nope:], place, inv, factor)
    k_rot = turn_pairs(kv_a[:, None, rank:], place, inv, factor)  # ONE head
    grow = 1.0 + rope.get("llama_4_scaling_beta", 0.0) * jnp.log1p(
        jnp.floor(place / rope["original_max_position_embeddings"])
    )
    q = plain.keep(jnp.concatenate([q[..., :nope], q_rot], axis=-1) * grow[:, None, None])
    k = plain.keep(jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rot, (n, h, rot))], axis=-1
    ))
    v = plain.keep(kv[..., nope:])
    # Head-major: ``mm`` is a matmul a head (``@`` over the leading axis).
    q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))
    scale = softmax_scale(rope, nope + rot)
    out = []
    for start in range(0, n, _ROWS):
        end = min(start + _ROWS, n)
        s = plain.mm(q[:, start:end], k[:, :end].transpose(0, 2, 1)) * scale
        keep = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]
        prob = plain.keep(jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1))
        out.append(plain.mm(prob, v[:, :end]).transpose(1, 0, 2))  # [rows, h, vd]
    y = plain.keep(jnp.concatenate(out)).reshape(n, h * vd)
    return plain.mm(y, p["o_proj"]["kernel"])


def _routed(p, x, cfg, plain, chosen, report):
    """``sum over the chosen and held of w_e SwiGLU_e(x)``, each held expert
    over the rows that chose it. ``chosen`` None routes by this file's own
    top-K; the rows each held expert received go into ``report["loads"]``."""
    k = cfg.num_experts_per_tok
    logit = x @ p["gate"]  # the router is float32 in every precision
    if chosen is None:
        chosen = np.asarray(jax.lax.top_k(logit, k)[1])
    else:
        chosen = np.asarray(chosen)
        report["route_margin"] = max(report["route_margin"], top_k_margin(logit, chosen, k))
    score = jax.nn.softmax(logit, axis=-1)
    weight = jnp.take_along_axis(score, jnp.asarray(chosen), axis=1)
    if cfg.norm_topk_prob:
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
    weight = weight * cfg.routed_scaling_factor
    y, loads = jnp.zeros_like(x), []
    for e in range(cfg.num_experts_held):
        mine = chosen == e + cfg.experts_offset
        rows = np.flatnonzero(mine.any(axis=1))
        loads.append(len(rows))
        if not len(rows):
            continue
        # Up to a whole block of rows, the further ones row 0 again under a
        # weight of 0: a few shapes an expert loop, where each would compile
        # its own (the reference runs eagerly).
        live = np.arange(len(rows) + -len(rows) % _EXPERT_ROWS) < len(rows)
        rows = np.concatenate([rows, np.zeros(len(live) - len(rows), rows.dtype)])
        w_e = jnp.sum(jnp.where(mine[rows], weight[rows], 0.0), axis=1) * live
        y = y.at[rows].add(
            w_e[:, None] * _swiglu(x[rows], p["w1"][e], p["w3"][e], p["w2"][e], plain)
        )
    report["loads"].append(loads)
    report["chosen"].append(chosen)
    return y


def _dense(p, x, plain):
    return _swiglu(x, p["w1"]["kernel"], p["w3"]["kernel"], p["w2"]["kernel"], plain)


def encode(model, params, stats, graph, routing=None, plain=Exact, report=None):
    """[n, d]: the stack's output for ONE document. ``routing``: the experts
    the program chose, [n, routed layers x K] as the engine's reply has them
    (a concrete array); None routes by this file's own top-K. ``report``, a
    dict, collects the routing margin (on the router's logits), the rows each
    held expert received a layer (``loads``) and the choices made (``chosen``)."""
    cfg = sizes(model)
    rope = _rope_dict(cfg)
    if report is None:
        report = {}
    report.update(route_margin=0.0, loads=[], chosen=[])
    lo, hi = cfg.token_minmax
    ids = jnp.round(jnp.asarray(graph["x"])[:, 0] * (hi - lo) + lo).astype(jnp.int32)
    h = params["conv_embed"]["embedding"][ids]
    place = jnp.asarray(graph["pos"], jnp.float32)[:, 0]
    k, layer = cfg.num_experts_per_tok, 0
    for i in range(model.num_conv_layers):
        p = params[f"conv_{i}"]
        x = plain.keep(_rms(h, p["input_layernorm"]["weight"], cfg.rms_norm_eps))
        h = plain.keep(h + _attention(p["self_attn"], x, place, cfg, rope, plain))
        x = plain.keep(_rms(h, p["post_attention_layernorm"]["weight"], cfg.rms_norm_eps))
        if i < cfg.first_k_dense_replace:
            h = plain.keep(h + _dense(p["feed_forward"], x, plain))
            continue
        chosen = None if routing is None else np.asarray(routing)[:, k * layer : k * (layer + 1)]
        layer += 1
        h = plain.keep(
            h + _dense(p["shared_experts"], x, plain)
            + _routed(p["feed_forward"], x, cfg, plain, chosen, report)
        )
    return _rms(h, params["conv_norm"]["weight"], cfg.rms_norm_eps)


def logits(model, params, graph, routing=None, plain=Exact):
    """([n, classes] logits of the one node head, the report of ``encode``)
    for one document, eagerly in float32 at ``highest``."""
    report = {}
    with jax.default_matmul_precision("highest"):
        x = encode(model, params, None, graph, routing, plain, report)
        head = params["head_0"]["mlp"]["dense_0"]
        out = plain.mm(plain.keep(x), head["kernel"]) + head["bias"]
    report["rows_held"] = int(np.sum(report["loads"]))
    return np.asarray(out), report


def logprobs(model, params, graph, routing=None, plain=Exact):
    """([n, 1] the log-probability of each next token of the document, 0 for
    its last; the report of ``encode``): what the serving engine replies."""
    out, report = logits(model, params, graph, routing, plain)
    cfg = sizes(model)
    lo, hi = cfg.token_minmax
    ids = np.round(np.asarray(graph["x"], np.float64)[:, 0] * (hi - lo) + lo).astype(np.int64)
    out = out.astype(np.float64)
    top = out.max(axis=1, keepdims=True)
    lse = top[:, 0] + np.log(np.exp(out - top).sum(axis=1))
    logp = np.zeros(len(ids))
    logp[:-1] = out[np.arange(len(ids) - 1), ids[1:]] - lse[:-1]
    return logp[:, None].astype(np.float32), report


def rel_l2_limit(tokens: int) -> float:
    """The relative-L2 limit for a document of ``tokens`` tokens (the reason
    and the readings stand beside ``REL_L2``)."""
    return REL_L2 * (max(tokens, 1) / REL_L2_TOKENS) ** -REL_L2_SLOPE


def compare(got, want):
    """(max |diff|, relative L2, failure or None) of a document's
    log-probabilities under this file's limits."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf"), float("inf"), "shape or non-finite log-probabilities"
    err = np.abs(got - want)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    limit = rel_l2_limit(len(want))
    fail = None
    if (err > ATOL + RTOL * np.abs(want)).any():
        fail = f"|reply - reference| {err.max():.3e} beyond atol={ATOL} rtol={RTOL}"
    elif rel > limit:
        fail = f"relative L2 distance {rel:.3e} beyond {limit:.3e} ({len(want)} tokens)"
    return float(err.max()), rel, fail


# ------------------------------------------------------------------- counts
def _routed_layers(arch) -> int:
    return arch["num_conv_layers"] - int(arch.get("first_k_dense_replace", 0))


def pairs(length: float) -> float:
    """The (query, key) pairs of one document of ``length`` tokens: the
    causal triangle, the token itself counted -- REAL pairs, not the blocks a
    kernel pads them to."""
    return length * (length + 1) / 2


def attn_counts(arch: dict, lengths) -> dict:
    """Operations and bytes of ONE forward pass of the attention cores over
    documents of ``lengths`` tokens, all layers together: ``q k`` over
    ``nope + rot`` dimensions and ``p v`` over ``vd`` (2 operations a pair, a
    head and a dimension each: 4 x 128 a pair and head as published), the
    softmax (5 a pair and a head), and q, k, v and the output read or written
    once."""
    h, layers = arch["num_attention_heads"], arch["num_conv_layers"]
    qk, vd = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"], arch["v_head_dim"]
    n_pairs = float(sum(pairs(n) for n in lengths))
    tokens = float(sum(lengths))
    return {"full": {
        "ops": layers * (2 * n_pairs * h * (qk + vd) + 5 * n_pairs * h),
        "bytes": layers * flops.B * tokens * h * (2 * qk + 2 * vd),
        "pairs": layers * n_pairs, "layers": layers,
    }}


def moe_counts(arch: dict, rows: float) -> dict:
    """Operations and bytes of ONE forward pass of the grouped matmuls over
    ``rows`` routed rows to held experts, all routed layers together
    (``families/lfm2.py``'s count: three projections a row, each held
    expert's three matrices read once a layer)."""
    d, f = arch["hidden_dim"], arch["moe_intermediate_size"]
    held = arch.get("num_experts_held", arch["n_routed_experts"])
    return {
        "ops": 2 * rows * 3 * d * f + 2 * rows * f,
        "bytes": flops.B * (_routed_layers(arch) * held * 3 * d * f + rows * (2 * d + 3 * f + d)),
    }


def counts(arch, nodes, edges=0, routed_rows=None, lengths=None):
    """One forward pass of the ENCODER over ``nodes`` real tokens in documents
    of ``lengths`` (one document of all the tokens where none are given;
    ``edges`` is not read: a document has none). ``routed_rows``: the rows
    routed to held experts, all routed layers together, as the engine counted
    them; None takes what uniform routing would send (``K * held / experts``
    a token and layer). The head is ``head_counts``."""
    d, h = arch["hidden_dim"], arch["num_attention_heads"]
    nope, rot, vd = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"], arch["v_head_dim"]
    q_rank, kv_rank = arch["q_lora_rank"], arch["kv_lora_rank"]
    experts = arch["n_routed_experts"]
    held = arch.get("num_experts_held", experts)
    routed, dense = _routed_layers(arch), int(arch.get("first_k_dense_replace", 0))
    if routed_rows is None:
        routed_rows = routed * nodes * arch["num_experts_per_tok"] * held / experts
    norm = flops.part(4 * nodes * d, flops.B * 2 * nodes * d)
    parts = [flops.part(0, flops.B * (2 * nodes * d + nodes))]  # the embedding rows
    shared = arch["moe_intermediate_size"] * int(arch.get("n_shared_experts", 1))
    for layer in range(arch["num_conv_layers"]):
        parts += [
            norm,
            flops.dense(nodes, d, q_rank), flops.part(4 * nodes * q_rank, flops.B * 2 * nodes * q_rank),
            flops.dense(nodes, q_rank, h * (nope + rot)),
            flops.dense(nodes, d, kv_rank + rot),
            flops.part(4 * nodes * kv_rank, flops.B * 2 * nodes * kv_rank),
            flops.dense(nodes, kv_rank, h * (nope + vd)),
            # the rotation of q's and the shared key's rotary parts, the
            # factor on q, the concatenations
            flops.part(
                nodes * (6 * (h + 1) * rot + h * (nope + rot)),
                flops.B * nodes * (3 * h * (nope + rot) + h * vd),
            ),
            flops.dense(nodes, h * vd, d), norm,
        ]
        width = arch.get("intermediate_size", 0) if layer < dense else shared
        parts += [
            flops.dense(nodes, d, width), flops.dense(nodes, d, width),
            flops.dense(nodes, width, d),
        ]
        if layer >= dense:
            parts.append(flops.dense(nodes, d, experts))  # the router
    core = attn_counts(arch, lengths if lengths is not None else [nodes])["full"]
    parts.append(flops.part(int(core["ops"]), int(core["bytes"])))
    moe = moe_counts(arch, routed_rows)
    parts.append(flops.part(int(moe["ops"]), int(moe["bytes"])))
    parts.append(norm)  # the final norm
    return parts, d


def head_counts(arch: dict, nodes: float, classes: int):
    """The class head's reply over ``nodes`` tokens: the matmul, the
    log-softmax (5 operations a logit) and the pick; the logits are written
    and read once a row block, the reply is one number a token."""
    d = arch["hidden_dim"]
    return [
        flops.dense(nodes, d, classes),
        flops.part(6 * nodes * classes, flops.B * (2 * nodes * classes + nodes)),
    ]
