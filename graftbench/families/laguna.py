"""LAGUNA (``model_type: "LAGUNA"``): the plain encoder of one sequence, the
routing check, and the counts.

poolside Laguna-XS.2, ``model_type`` ``laguna``
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json). A token
is a node, a sequence a graph, ``pos[:, 0]`` the token's place. With
``rms(x, w) = x * rsqrt(mean(x^2) + eps) * w`` and layer ``l``:

  h0 = E[token];  h += attn_l(rms(h));  h += ffn_l(rms(h));  out = rms(h)
  attn   q, k, v = W_q x, W_k x, W_v x  (H_l / KV / KV heads of hd; H_l by
         ``num_attention_heads_per_layer``), no bias, no norm on q / k;
         rotary at the token's place, halves convention, by the layer's kind:
           full     the first ``partial_rotary_factor * hd`` dimensions of a
                    head, YaRN: pair i turns at
                    ``(1 - g_i) theta^(-2i/r) / factor + g_i theta^(-2i/r)``,
                    ``g_i = 1 - clip((i - low) / (high - low), 0, 1)``,
                    ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``,
                    ``c(b) = r ln(L / (2 pi b)) / (2 ln theta)``; cos and sin
                    times ``attention_factor``; the other dimensions as they are
           sliding  the whole head at ``theta^(-2i/hd)``
         a_i = sum_j softmax_j(q_i k_j / sqrt(hd)) v_j  over j <= i, on
         sliding layers also i - j < ``sliding_window``; a key-value head
         shared by H_l / KV query heads;  g = sigmoid(W_g x) [n, H_l];
         W_o (g * a)
  dense  W2(silu(W1 x) * W3 x)                       (``mlp_layer_types`` dense)
  sparse shared(x) + routed(x): shared a SwiGLU of its own width;
         s = sigmoid(W_r x) over ALL experts;  the K largest chosen (no bias);
         w_e = scaling * s_e / (sum over the chosen + 1e-6);
         routed = sum over the chosen AND HELD of w_e W2e(silu(W1e x) * W3e x)

Here attention is a masked softmax over each block of rows against the keys
it can see, an expert a plain SwiGLU over the rows that chose it, every
matmul float32 at ``highest``; the program runs Pallas kernels over blocks
with operands rounded to bf16 and multiplies ragged groups. Given the same
share (``num_experts_held`` from ``experts_offset``), what the absent experts
would add is left out here as there; the shared expert is whole on every
rank.

**Routing is discrete**, and handled as ``families/lfm2.py`` handles it:
``logits`` takes the experts the PROGRAM chose, fails unless each chosen set is a top-K
of this file's own scores within ``ROUTE_EPS`` and of the scores of the
program's own router input within ``ROUTER_EPS``, then routes as the program
did.

Assumed, because the catalog's row has no key for it (PAPERS.md): the
router's sigmoid, normalisation and scaling; no expert bias, no balance loss;
``gating`` as one sigmoid gate a head from the layer's normed input; no norm
on q / k; the window counting the token itself; YaRN's ``truncate`` true.
"""

import jax
import jax.numpy as jnp
import numpy as np

from graftbench import flops
from graftbench.families import lfm2
from graftbench.families.lfm2 import Exact, _rms, _swiglu, bf16, top_k_margin  # noqa: F401

# Program against reference on the logits of a sequence: the three limits of
# ``families/lfm2.py``, read again for this stack.
#
# The stated precision rounds every matmul's OPERANDS to bf16 (the attention
# kernels' included: they are handed float32 rows and the MXU takes one bf16
# pass); what the precision below adds (the residual stream, every activation
# kept and the softmax's probabilities rounded to bf16 too) adds in
# quadrature, and with five attention layers and 45 matmuls in series the
# operands' part is the larger here, so the two readings lie 8% apart where
# LFM2's lie 28%. What tells them apart is still the RELATIVE L2 DISTANCE of
# a sequence's 51M logits, an average over so many roundings that it moves by
# a few parts in a thousand with the seed. Readings at the configuration's
# widths, 5 layers, one sequence of 4096 tokens (PERF.md section 6, PR 33):
# the program on the chip 1.956e-2 to 1.974e-2 over nine seeds, this file with
# operands rounded 1.962e-2 / 1.973e-2 emulated on the CPU (two seeds; 1.964e-2
# at 256 tokens); the reference in the precision below reads 2.126e-2 /
# 2.134e-2 (2.129e-2 at 256 tokens) and comes out NOT correct. The limit lies
# 3.6% over the first reading and 3.8% under the second.
REL_L2 = 2.045e-2
# Elementwise, |a - b| <= ATOL + RTOL |b| on logits of rms ~1: the largest
# |a - b| the program reads is 0.13 to 0.16 (an extreme of 51M elements), 0.14
# in the precision below; a mis-wired layer, a wrong frequency, a missing gate or
# shared expert is off by O(1).
ATOL = RTOL = 0.25
# A chosen expert's own score may lie this far under the reference's K-th
# largest, and a passed-over one this far above it: the program's activations
# reach the router through bf16-operand matmuls. Readings 0.84e-2 to 1.28e-2
# on the chip, 0.89e-2 and 0.94e-2 emulated, at the worst of a check's
# 4096 x 8 x 4 positions; a wrong top-k or another expert order reads 0.1 to
# 0.5 (tests/test_laguna.py).
ROUTE_EPS = 3e-2
# The same on scores computed here from the PROGRAM's router input: only the
# router's own arithmetic differs. At Precision.HIGHEST it reads 0.0 on the
# chip; a router whose matmul rounds its operands to bf16 reads ~1e-3
# (graftbench/tests/test_laguna_cell.py shows one).
ROUTER_EPS = 2e-5

_ROWS = 512  # query rows a block of the masked softmax


def _attention(p, x, place, cfg, layer, plain):
    n = x.shape[0]
    h, kv, hd = cfg.num_attention_heads_per_layer[layer], cfg.num_key_value_heads, cfg.head_dim
    kind = cfg.layer_types[layer]
    sliding = kind == "sliding_attention"
    inv, factor, r = frequencies(cfg.rope_parameters[int(sliding)], hd)  # (full, sliding)
    window = cfg.sliding_window if sliding else None

    def turn(a):
        half = r // 2
        angle = place[:, None] * inv
        cos, sin = factor * jnp.cos(angle)[:, None, :], factor * jnp.sin(angle)[:, None, :]
        a1, a2 = a[..., :half], a[..., half:r]
        return jnp.concatenate(
            [a1 * cos - a2 * sin, a2 * cos + a1 * sin, a[..., r:]], axis=-1
        )

    q = plain.keep(turn(plain.mm(x, p["q_proj"]["kernel"]).reshape(n, h, hd)))
    k = plain.keep(turn(plain.mm(x, p["k_proj"]["kernel"]).reshape(n, kv, hd)))
    v = plain.keep(plain.mm(x, p["v_proj"]["kernel"])).reshape(n, kv, hd)
    gate = jax.nn.sigmoid(plain.mm(x, p["g_proj"]["kernel"]))  # [n, h]
    # Head-major, each key-value head repeated for its H_l / KV query heads;
    # ``mm`` is a matmul a head (``@`` over the leading axis).
    q = q.transpose(1, 0, 2)
    k, v = (jnp.repeat(a, h // kv, axis=1).transpose(1, 0, 2) for a in (k, v))
    out = []
    for start in range(0, n, _ROWS):
        end = min(start + _ROWS, n)
        lo = 0 if window is None else max(0, start - window + 1)
        rows, keys = jnp.arange(start, end), jnp.arange(lo, end)
        s = plain.mm(q[:, start:end], k[:, lo:end].transpose(0, 2, 1)) * hd ** -0.5
        keep = keys[None, :] <= rows[:, None]  # [rows, keys]
        if window is not None:
            keep &= rows[:, None] - keys[None, :] < window
        prob = plain.keep(jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1))
        out.append(plain.mm(prob, v[:, lo:end]).transpose(1, 0, 2))  # [rows, h, hd]
    y = plain.keep(jnp.concatenate(out) * gate[:, :, None]).reshape(n, h * hd)
    return plain.mm(y, p["o_proj"]["kernel"])


def frequencies(rope, head_dim):
    """(inv [r / 2], the factor on cos and sin, r) of one ``rope_parameters``
    entry, written out here from the formulas above (float64, by hand: the
    program has its own)."""
    r = int(head_dim * rope.partial_rotary_factor)
    i = np.arange(r // 2, dtype=np.float64)
    plain_inv = rope.rope_theta ** (-2 * i / r)
    if rope.rope_type == "default":
        return jnp.asarray(plain_inv, jnp.float32), 1.0, r
    ln = np.log

    def c(b):
        return r * ln(rope.original_max_position_embeddings / (2 * np.pi * b)) / (
            2 * ln(rope.rope_theta)
        )

    low, high = max(np.floor(c(rope.beta_fast)), 0), min(np.ceil(c(rope.beta_slow)), r - 1)
    g = 1 - np.clip((i - low) / (high - low), 0, 1)
    inv = (1 - g) * plain_inv / rope.factor + g * plain_inv
    factor = rope.attention_factor or 0.1 * ln(rope.factor) + 1
    return jnp.asarray(inv, jnp.float32), float(factor), r


def _routed(p, x, cfg, plain, routing, report, name):
    """``sum over the chosen and held of w_e SwiGLU_e(x)``, each held expert
    over the rows that chose it (all the others add nothing)."""
    gate, k = p["gate"], cfg.num_experts_per_tok
    s = jax.nn.sigmoid(x @ gate)  # the router is float32 in every precision
    if routing is None:
        chosen = np.asarray(jax.lax.top_k(s, k)[1])
        if report is not None:
            report.setdefault("routing", {})[name] = {"chosen": chosen, "router_in": x}
    else:
        chosen = np.asarray(routing["chosen"])
        if report is not None:
            report["route_margin"] = max(report["route_margin"], top_k_margin(s, chosen, k))
            own = jax.nn.sigmoid(jnp.asarray(routing["router_in"]) @ gate)
            report["router_margin"] = max(
                report["router_margin"], top_k_margin(own, chosen, k)
            )
    weight = jnp.take_along_axis(s, jnp.asarray(chosen), axis=1)
    weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
    weight = weight * cfg.moe_routed_scaling_factor
    y = jnp.zeros_like(x)
    for e in range(cfg.num_experts_held):
        mine = chosen == e + cfg.experts_offset
        rows = np.flatnonzero(mine.any(axis=1))
        if report is not None:
            report["rows_held"] += len(rows)
        if not len(rows):
            continue
        w_e = jnp.sum(jnp.where(mine[rows], weight[rows], 0.0), axis=1)
        y = y.at[rows].add(
            w_e[:, None] * _swiglu(x[rows], p["w1"][e], p["w3"][e], p["w2"][e], plain)
        )
    return y


def _dense(p, x, plain):
    return _swiglu(x, p["w1"]["kernel"], p["w3"]["kernel"], p["w2"]["kernel"], plain)


def encode(model, params, stats, graph, routing=None, plain=Exact, report=None):
    """[n, d]: the stack's output for ONE sequence. ``routing``: per routed
    layer (``conv_<i>``) the program's ``chosen`` [n, K] and ``router_in``
    [n, d] for this sequence (concrete arrays); None routes by this file's
    own top-k. ``report``, a dict, collects the routing margins and the rows
    routed to held experts."""
    cfg = model.laguna
    if report is not None:
        report.update(route_margin=0.0, router_margin=0.0, rows_held=0)
    lo, hi = cfg.token_minmax
    ids = jnp.round(graph["x"][:, 0] * (hi - lo) + lo).astype(jnp.int32)
    h = params["conv_embed"]["embedding"][ids]
    place = jnp.asarray(graph["pos"][:, 0])
    for i in range(model.num_conv_layers):
        p, name = params[f"conv_{i}"], f"conv_{i}"
        x = plain.keep(_rms(h, p["input_layernorm"]["weight"], cfg.rms_norm_eps))
        h = plain.keep(h + _attention(p["self_attn"], x, place, cfg, i, plain))
        x = plain.keep(_rms(h, p["post_attention_layernorm"]["weight"], cfg.rms_norm_eps))
        if cfg.mlp_layer_types[i] == "sparse":
            layer = None if routing is None else routing[name]
            h = plain.keep(
                h + _dense(p["shared_expert"], x, plain)
                + _routed(p["feed_forward"], x, cfg, plain, layer, report, name)
            )
        else:
            h = plain.keep(h + _dense(p["feed_forward"], x, plain))
    return _rms(h, params["conv_norm"]["weight"], cfg.rms_norm_eps)


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
def _kinds(arch):
    layers = arch["num_conv_layers"]
    return list(zip(
        arch["layer_types"][:layers], arch["mlp_layer_types"][:layers],
        arch["num_attention_heads_per_layer"][:layers],
    ))


def pairs(length: float, window=None) -> float:
    """The (query, key) pairs of one sequence of ``length`` tokens: the
    causal triangle, or the causal band of ``window`` (the token itself
    counts) -- REAL pairs, not the blocks a kernel pads them to."""
    if window is None or length <= window:
        return length * (length + 1) / 2
    return window * (window + 1) / 2 + (length - window) * window


def attn_counts(arch: dict, lengths) -> dict:
    """Operations and bytes of ONE forward pass of the attention cores over
    sequences of ``lengths`` tokens, by kind (``window``, ``full``), all
    layers of the kind together: ``q k`` and ``p v`` (4 operations a pair, a
    head and a head dimension), the softmax (5 a pair and a head), and q, the
    output, k and v read or written once. A train step is three of it, and a
    fourth where the block is rematerialized (``Architecture.remat``)."""
    hd, kv = arch["head_dim"], arch["num_key_value_heads"]
    tokens = float(sum(lengths))
    out = {k: {"ops": 0.0, "bytes": 0.0, "pairs": 0.0, "layers": 0} for k in ("window", "full")}
    for kind, _, heads in _kinds(arch):
        sliding = kind == "sliding_attention"
        n_pairs = sum(pairs(n, arch["sliding_window"] if sliding else None) for n in lengths)
        o = out["window" if sliding else "full"]
        o["ops"] += 4 * n_pairs * heads * hd + 5 * n_pairs * heads
        o["bytes"] += flops.B * tokens * (2 * heads + 2 * kv) * hd
        o["pairs"] += n_pairs
        o["layers"] += 1
    return out


def moe_counts(arch: dict, rows: float) -> dict:
    """Operations and bytes of ONE forward pass of the grouped matmuls over
    ``rows`` routed rows to held experts, all routed layers together:
    ``families/lfm2.py``'s count (three projections a row, each held expert's
    three matrices read once a layer), the routed layers being the
    ``sparse`` ones here. A train step is three of it."""
    dense = sum(mlp == "dense" for _, mlp, _ in _kinds(arch))
    return lfm2.moe_counts(dict(arch, num_dense_layers=dense), rows)


def counts(arch, nodes, edges, routed_rows=None):
    """One forward pass over ``nodes`` real tokens (``edges`` counts the
    sequences: the loaders' band graph of a line of n nodes at radius 2.5 has
    4n - 6 directed edges, so 4 nodes - edges = 6 sequences; they are taken
    as equally long). ``routed_rows``: the rows routed to held experts, all
    routed layers together, as the program counted them; None takes what
    uniform routing would send (``K * held / experts`` a token and layer)."""
    d = arch["hidden_dim"]
    hd, kv = arch["head_dim"], arch["num_key_value_heads"]
    held = arch.get("num_experts_held", arch["num_experts"])
    kinds = _kinds(arch)
    routed = sum(mlp == "sparse" for _, mlp, _ in kinds)
    if routed_rows is None:
        routed_rows = routed * nodes * arch["num_experts_per_tok"] * held / arch["num_experts"]
    sequences = max((4 * nodes - edges) / 6, 1)
    cores = attn_counts(arch, [nodes / sequences] * max(int(round(sequences)), 1))
    norm = flops.part(4 * nodes * d, flops.B * 2 * nodes * d)
    parts = [flops.part(0, flops.B * (2 * nodes * d + nodes))]  # the embedding rows
    for _, mlp, heads in kinds:
        parts += [
            norm, flops.dense(nodes, d, (heads + 2 * kv) * hd),
            flops.dense(nodes, d, heads),  # the gate
            flops.part(7 * nodes * heads * hd, flops.B * 3 * nodes * heads * hd),  # rotary, gate
            flops.dense(nodes, heads * hd, d), norm,
        ]
        width = (
            arch["shared_expert_intermediate_size"] if mlp == "sparse"
            else arch["intermediate_size"]
        )
        parts += [
            flops.dense(nodes, d, width), flops.dense(nodes, d, width),
            flops.dense(nodes, width, d),
        ]
        if mlp == "sparse":
            parts.append(flops.dense(nodes, d, arch["num_experts"]))  # the router
    for core in cores.values():
        parts.append(flops.part(int(core["ops"]), int(core["bytes"])))
    moe = moe_counts(arch, routed_rows)
    parts.append(flops.part(int(moe["ops"]), int(moe["bytes"])))
    parts.append(norm)  # the final norm
    return parts, d
