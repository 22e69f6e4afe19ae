"""JAMBA (``model_type: "JAMBA"``): the plain encoder of one document, its
log-probabilities, and the counts.

AI21-Jamba2-3B, ``model_type`` ``jamba``
(https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json). A
token is a node, a document a graph. With ``rms(x, w) = x * rsqrt(mean(x^2) +
eps) * w`` and layer ``l``:

  h0 = E[token];  h += mixer_l(rms(h));  h += W2(silu(W1 x') * W3 x'), x' = rms(h)
  out = rms(h);   logits = out E^T   (the embedding's own table, no bias)
  mixer  attention where ``l mod attn_layer_period == attn_layer_offset``,
         Mamba elsewhere
  Mamba  [u, z] = W_in x;  u_t <- silu(b_c + sum_j k_j * u_{t-3+j}) a channel,
         rows before the document's first read as 0;
         [delta, B, C] = W_x u;  delta, B, C <- rms of each (its own weight);
         dt = softplus(W_dt delta + b_dt + softplus^-1(dt0));
         A = -exp(log(1..S) + A_log);  D = 1 + D_;
         h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * u_t) (x) B_t,  h = 0 before
         the first token;  y_t = h_t C_t + D * u_t;  W_out (y * silu(z))
  attn   q, k, v = W_q x, W_k x, W_v x (H / KV / KV heads of hd = d / H), no
         bias, no rotary, no norm; a_i = sum_j softmax_j(q_i k_j / sqrt(hd)) v_j
         over j <= i;  W_o a
  reply  logp_i = log softmax(logits_i)[token_{i+1}], 0 for the last

``dt0`` (the channels' first step sizes, log-spaced over [1e-3, 1e-1]) and
``log(1..S)`` are Mamba's published starting point, which the program holds
the three scan parameters as distances from (``hydragnn_tpu/models/jamba.py``
says why); this file reads the same tree and spells the constants itself.

Here the recurrence is a ``lax.scan`` ONE TOKEN A STEP over the state
``[d_inner, S]``, the convolution four shifted copies of the document,
attention a masked softmax over each block of 512 rows against all the
document's keys, every matmul float32 at ``highest``, the reply taken a block
of rows of logits at a time; the program runs a Pallas kernel over chunks of
256 rows with the state in VMEM (``ops/selective_scan.py``), a packed flush of
several documents, ``block_range_attention`` for the triangle, and matmuls
with operands rounded to bf16. Nothing here is imported from
``hydragnn_tpu/models/``; the sizes are read off ``model.token_cfg`` by the
source's names.

Nothing is routed: ``logprobs`` takes ``routing`` (None from this engine) and
ignores it, ``route_margin`` is 0.

The precisions ``encode`` can run in (``plain``): ``Exact`` (float32
throughout: the reference), ``Operands`` (the STATED precision emulated: each
matmul's operands rounded to bf16, everything kept and the whole recurrence in
float32) and ``Below`` (the precision below: as ``Operands``, and dt, the
decay ``exp(dt A)`` and the state rounded to bf16 at every step of the
recurrence), the control of the limits.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from graftbench import flops
from graftbench.families.lfm2 import Exact, _rms, _swiglu  # noqa: F401
from graftbench.families.mistral4 import head_counts  # noqa: F401

DT_MIN, DT_MAX = 1e-3, 1e-1  # Mamba's published dt_min, dt_max


def bf16(x):
    """``x`` rounded to bf16's 8 bits of mantissa, as float32.
    ``reduce_precision``, not a cast there and back: inside a compiled
    function XLA is allowed to keep the excess precision of such a pair
    (``xla_allow_excess_precision``) and then rounds nothing (my chip run, PR
    45: with the casts, the state's rounding inside ``_mamba``'s compiled
    ``lax.scan`` was dropped and ``Below`` read the same number as
    ``Operands`` to sixteen digits; a matmul's operands kept theirs)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


class Operands(Exact):
    """The STATED precision, emulated: every matmul's operands rounded to
    bf16, float32 accumulation, everything kept and the whole recurrence in
    float32 (what the engine does on the chip)."""

    @staticmethod
    def mm(a, w):
        return bf16(a) @ bf16(w)


class Below(Operands):
    """The precision BELOW the stated one: matmul operands rounded to bf16 as
    stated, and the recurrence's own numbers (dt, the decay, the state after
    every step) rounded to bf16 where the configuration states float32."""

    @staticmethod
    def state(x):
        return bf16(x)


def _state(plain):
    return getattr(plain, "state", lambda x: x)


# Engine against reference on the log-probabilities of a document's tokens.
# Under the benchmark's seeded weights the TIED head's logits are hot: the
# embedding's rows are N(0, 1) over 2560 columns, a row's own embedding is
# still a share of the normed stream after 28 layers, and the head multiplies
# by the same table, so a row's own token reads a logit of hundreds and a next
# token's log-probability is its logit less that: numbers of about -656 +- 75
# (my chip runs, PR 45), not -ln(vocab). The RELATIVE L2 distance does not see
# that scale; ``ATOL`` does, and is set on these numbers. Readings (my chip
# runs, PR 45; PERF.md section 2 has every one; "emulated" is ``python3 -m
# graftbench.token_readings`` run on the chip, one seed a process):
#
# * RELATIVE L2 DISTANCE of a document's log-probabilities from the float32
#   reference, the number that tells the stated precision from the one below,
#   under ONE limit for every length (``REL_L2_SLOPE`` 0). The engine as
#   stated (operands rounded to bf16, the whole recurrence float32) reads 1.93
#   to 1.98e-3 at 1024, 3072 and 4096 tokens over five runs on the chip, and
#   hardly moves with the length or the seed (28 layers and thousands of
#   tokens average it); emulated (``Operands``) 1.94 to 1.99e-3 at 1024 (three
#   seeds), 1.94e-3 at 2048, 1.97e-3 at 3072, 1.95e-3 at 4096. ``Below`` (dt,
#   the decay and the state rounded to bf16 at every step too) reads 2.46,
#   2.93 and 3.64e-3 at 1024 (three seeds), 3.22e-3 at 2048, 4.31e-3 at 3072
#   and 3.63e-3 at 4096: it grows with the length (a slow channel's state
#   carries a thousand roundings) and moves with the seed, and comes out NOT
#   correct. The limit lies 11% over the first reading's largest (1.986e-3)
#   and 10% under the second's smallest (2.458e-3), both at 1024 tokens, where
#   the two lie nearest; at 4096 the second reading is 1.6 times the limit.
REL_L2, REL_L2_TOKENS, REL_L2_SLOPE = 2.21e-3, 1024, 0.0
# * Elementwise, |a - b| <= ATOL + RTOL |b| on a token's log-probability of
#   about -656: the largest |a - b| the engine reads is 4.5 to 5.8 on the chip
#   (an extreme of 1024-4096 numbers; five runs), 4.2 to 5.2 emulated, 5.5 to
#   15.1 in the precision below (this limit does not tell the two apart and
#   is not meant to: the relative L2 does). A convolution tap read from an
#   overwritten row was off by 90 to 203 (PERF.md section 6, PR 45); a
#   mis-wired layer, a dropped reset or a missing norm moves every token by
#   tens.
ATOL, RTOL = 20.0, 0.0
# Nothing is routed: the driver compares a margin of 0 with this.
ROUTE_EPS = 0.15

_ROWS = 512  # query rows a block of the masked softmax, and of the reply


def sizes(model):
    """The stack's sizes, by the source's names."""
    return model.token_cfg


def scans(cfg, layer: int) -> bool:
    """The library's rule for this ``model_type``: attention where ``layer
    mod attn_layer_period`` is ``attn_layer_offset``, Mamba elsewhere."""
    return layer % cfg.attn_layer_period != cfg.attn_layer_offset


def _first_steps(channels: int):
    dt0 = np.exp(np.linspace(math.log(DT_MIN), math.log(DT_MAX), channels))
    return jnp.asarray(np.log(np.expm1(dt0)), jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg", "plain"))
def _mamba(p, x, cfg, plain):
    """One Mamba mixer over ONE document, the recurrence a token a step."""
    n, d = x.shape[0], cfg.mamba_expand * x.shape[1]
    s, r, taps = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    rnd = _state(plain)
    uz = plain.mm(x, p["in_proj"]["kernel"])
    u, z = uz[:, :d], uz[:, d:]
    conv = p["conv_bias"][None, :] if cfg.mamba_conv_bias else 0.0
    for back in range(taps):
        moved = jnp.concatenate([jnp.zeros((back, d), u.dtype), u[: n - back]])
        conv = conv + p["conv_kernel"][taps - 1 - back] * moved
    u = jax.nn.silu(conv)
    ssm = plain.mm(u, p["x_proj"]["kernel"])
    eps = cfg.rms_norm_eps
    delta = _rms(ssm[:, :r], p["dt_layernorm"]["weight"], eps)
    b = _rms(ssm[:, r : r + s], p["b_layernorm"]["weight"], eps)
    c = _rms(ssm[:, r + s :], p["c_layernorm"]["weight"], eps)
    dt = jax.nn.softplus(
        plain.mm(delta, p["dt_proj"]["kernel"]) + p["dt_proj"]["bias"] + _first_steps(d)
    )
    dt = rnd(dt)
    a = -jnp.exp(p["A_log"] + jnp.log(jnp.arange(1, s + 1, dtype=jnp.float32)))

    def step(h, row):
        dt_t, u_t, b_t, c_t = row
        h = rnd(rnd(jnp.exp(dt_t[:, None] * a)) * h + (dt_t * u_t)[:, None] * b_t[None, :])
        return h, jnp.sum(h * c_t[None, :], axis=1)

    _, y = jax.lax.scan(step, jnp.zeros((d, s), jnp.float32), (dt, u, b, c))
    y = y + (1.0 + p["D"]) * u
    return plain.mm(y * jax.nn.silu(z), p["out_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("cfg", "plain"))
def _attention(p, x, cfg, plain):
    """One attention layer over ONE document: each block of ``_ROWS`` query
    rows against all the document's keys under a dense causal mask (compiled
    once a length, every block the same shape)."""
    n, h, kv = x.shape[0], cfg.num_attention_heads, cfg.num_key_value_heads
    hd = x.shape[1] // h
    q = plain.mm(x, p["q_proj"]["kernel"]).reshape(n, h, hd)
    k = plain.mm(x, p["k_proj"]["kernel"]).reshape(n, kv, hd)
    v = plain.mm(x, p["v_proj"]["kernel"]).reshape(n, kv, hd)
    whole = -(-n // _ROWS) * _ROWS
    q = jnp.pad(q.transpose(1, 0, 2), ((0, 0), (0, whole - n), (0, 0)))
    k, v = (
        jnp.pad(jnp.repeat(a, h // kv, axis=1).transpose(1, 0, 2),
                ((0, 0), (0, whole - n), (0, 0)))
        for a in (k, v)
    )
    keys = jnp.arange(whole)[None, :]

    def block(start):
        rows = start + jnp.arange(_ROWS)[:, None]
        q_b = jax.lax.dynamic_slice_in_dim(q, start, _ROWS, axis=1)
        score = plain.mm(q_b, k.transpose(0, 2, 1)) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where((keys <= rows)[None], score, -jnp.inf), axis=-1)
        return plain.mm(prob, v).transpose(1, 0, 2)  # [rows, h, hd]

    out = jax.lax.map(block, jnp.arange(0, whole, _ROWS)).reshape(whole, h * hd)
    return plain.mm(out[:n], p["o_proj"]["kernel"])


def encode(model, params, stats, graph, routing=None, plain=Exact, report=None):
    """[n, d]: the stack's output for ONE document. ``routing`` is not read
    (nothing is routed); ``report``, a dict, gets ``route_margin`` 0."""
    cfg = sizes(model)
    if report is None:
        report = {}
    n = len(graph["x"])
    report.update(route_margin=0.0, loads=[], chosen=[np.zeros((n, 0), np.int32)])
    lo, hi = cfg.token_minmax
    ids = jnp.round(jnp.asarray(graph["x"])[:, 0] * (hi - lo) + lo).astype(jnp.int32)
    h = params["conv_embed"]["embedding"][ids]
    for i in range(model.num_conv_layers):
        p = params[f"conv_{i}"]
        x = _rms(h, p["input_layernorm"]["weight"], cfg.rms_norm_eps)
        if scans(cfg, i):
            h = h + _mamba(p["mamba"], x, cfg, plain)
        else:
            h = h + _attention(p["self_attn"], x, cfg, plain)
        x = _rms(h, p["pre_ff_layernorm"]["weight"], cfg.rms_norm_eps)
        ff = p["feed_forward"]
        h = h + _swiglu(x, ff["w1"]["kernel"], ff["w3"]["kernel"], ff["w2"]["kernel"], plain)
    return _rms(h, params["conv_norm"]["weight"], cfg.rms_norm_eps)


def _head_rows(params, x, plain):
    """The tied head's logits a block of ``_ROWS`` rows at a time: ``x E^T``
    with ``E`` the embedding's table (at 65,536 classes a 4096-token
    document's whole array is 1.07 GB)."""
    table = params["conv_embed"]["embedding"].T
    for start in range(0, x.shape[0], _ROWS):
        yield start, np.asarray(plain.mm(x[start : start + _ROWS], table))


def logits(model, params, graph, routing=None, plain=Exact):
    """([n, classes] logits of the tied head, the report of ``encode``) for
    one document, in float32 at ``highest``."""
    report = {}
    with jax.default_matmul_precision("highest"):
        x = encode(model, params, None, graph, routing, plain, report)
        out = np.concatenate([rows for _, rows in _head_rows(params, x, plain)])
    report["rows_held"] = 0
    return out, report


def logprobs(model, params, graph, routing=None, plain=Exact):
    """([n, 1] the log-probability of each next token of the document, 0 for
    its last; the report of ``encode``): what the serving engine replies."""
    cfg, report = sizes(model), {}
    lo, hi = cfg.token_minmax
    ids = np.round(np.asarray(graph["x"], np.float64)[:, 0] * (hi - lo) + lo).astype(np.int64)
    nxt = np.append(ids[1:], 0)
    logp = np.zeros(len(ids))
    with jax.default_matmul_precision("highest"):
        x = encode(model, params, None, graph, routing, plain, report)
        for start, rows in _head_rows(params, x, plain):
            rows = rows.astype(np.float64)
            top = rows.max(axis=1, keepdims=True)
            lse = top[:, 0] + np.log(np.exp(rows - top).sum(axis=1))
            picked = rows[np.arange(len(rows)), nxt[start : start + len(rows)]]
            logp[start : start + len(rows)] = picked - lse
    logp[-1] = 0.0
    report["rows_held"] = 0
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
def _layers(arch):
    """(Mamba layers, attention layers) of the first ``num_conv_layers``."""
    period, offset = arch["attn_layer_period"], arch["attn_layer_offset"]
    attn = sum(1 for i in range(arch["num_conv_layers"]) if i % period == offset)
    return arch["num_conv_layers"] - attn, attn


def parameters(arch: dict) -> dict:
    """Parameters by part, as the program's tree holds them (the tied head
    has none of its own): a Mamba layer, an attention layer, the embedding,
    the whole."""
    d, f, v = arch["hidden_dim"], arch["intermediate_size"], arch["vocab_size"]
    di, s = arch["mamba_expand"] * d, arch["mamba_d_state"]
    r, taps = arch["mamba_dt_rank"], arch["mamba_d_conv"]
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = d // h
    ffn = 3 * d * f + 2 * d  # SwiGLU and the block's two norms
    mamba = (
        d * 2 * di + di * taps + di + di * (r + 2 * s) + r * di + di
        + di * s + di + (r + 2 * s) + di * d
    )
    attn = 2 * d * h * hd + 2 * d * kv * hd
    n_mamba, n_attn = _layers(arch)
    return {
        "mamba_layer": mamba + ffn, "attention_layer": attn + ffn, "embedding": v * d,
        "whole": n_mamba * (mamba + ffn) + n_attn * (attn + ffn) + v * d + d,
    }


def pairs(length: float) -> float:
    """The (query, key) pairs of one document: the causal triangle, the token
    itself counted."""
    return length * (length + 1) / 2


def attn_counts(arch: dict, lengths) -> dict:
    """Operations and bytes of ONE forward pass of the attention cores over
    documents of ``lengths`` tokens, the attention layers together, over REAL
    pairs: ``q k`` and ``p v`` (4 operations a pair, a head and a head
    dimension), the softmax (5 a pair and a head), and q, the output, k and v
    read or written once."""
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = arch["hidden_dim"] // h
    layers = _layers(arch)[1]
    n_pairs, tokens = float(sum(pairs(n) for n in lengths)), float(sum(lengths))
    return {"full": {
        "ops": layers * (4 * n_pairs * h * hd + 5 * n_pairs * h),
        "bytes": layers * flops.B * tokens * (2 * h + 2 * kv) * hd,
        "pairs": layers * n_pairs, "layers": layers,
    }}


# What ONE step of the recurrence costs a state element: the product dt A,
# its exp (counted as one), the decay's product with the state, the drive's
# product with B and its sum into the state, the product with C and its sum
# into y.
SCAN_OPS_A_STATE = 7


def scan_counts(arch: dict, tokens: float) -> dict:
    """Operations and bytes of ONE forward pass of the selective scans over
    ``tokens`` real tokens, the Mamba layers together. The kernel's rows in
    and out: ``u``, ``dt`` and ``y``, ``d_inner`` float32 numbers each a token
    and layer (20,480 B at 5120), plus ``B`` and ``C`` (``d_state`` each);
    ``A`` and ``D`` once a layer (the gate is the caller's, outside the
    kernel). Its operations: ``d_inner x d_state`` state elements a token and
    layer (81,920) times ``SCAN_OPS_A_STATE``, and a channel's ``dt u``,
    ``D u`` and sum."""
    di, s = arch["mamba_expand"] * arch["hidden_dim"], arch["mamba_d_state"]
    layers = _layers(arch)[0]
    return {
        "ops": layers * tokens * (di * s * SCAN_OPS_A_STATE + 3 * di),
        "bytes": layers * flops.B * (tokens * (3 * di + 2 * s) + di * s + di),
        "state_elements": layers * tokens * di * s, "layers": layers,
    }


def counts(arch, nodes, edges=0, routed_rows=None, lengths=None):
    """One forward pass of the ENCODER over ``nodes`` real tokens in documents
    of ``lengths`` (one document of all the tokens where none are given;
    ``edges`` and ``routed_rows`` are not read: a document has no edge and
    nothing is routed). The head is ``head_counts`` (the sibling's: one
    matmul, the log-softmax, the pick)."""
    d, f = arch["hidden_dim"], arch["intermediate_size"]
    di, s = arch["mamba_expand"] * d, arch["mamba_d_state"]
    r, taps = arch["mamba_dt_rank"], arch["mamba_d_conv"]
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = d // h
    period, offset = arch["attn_layer_period"], arch["attn_layer_offset"]
    norm = flops.part(4 * nodes * d, flops.B * 2 * nodes * d)
    parts = [flops.part(0, flops.B * (2 * nodes * d + nodes))]  # the embedding rows
    for layer in range(arch["num_conv_layers"]):
        parts.append(norm)
        if layer % period == offset:
            parts += [flops.dense(nodes, d, (h + 2 * kv) * hd), flops.dense(nodes, h * hd, d)]
        else:
            parts += [
                flops.dense(nodes, d, 2 * di),
                # the convolution's taps, bias and silu
                flops.part(nodes * di * (2 * taps + 5), flops.B * 2 * nodes * di),
                flops.dense(nodes, di, r + 2 * s),
                flops.part(4 * nodes * (r + 2 * s), flops.B * 2 * nodes * (r + 2 * s)),
                flops.dense(nodes, r, di),
                flops.part(4 * nodes * di, flops.B * 2 * nodes * di),  # bias, softplus
                flops.dense(nodes, di, d),
            ]
        parts += [
            norm, flops.dense(nodes, d, f), flops.dense(nodes, d, f),
            flops.part(5 * nodes * f, flops.B * 3 * nodes * f), flops.dense(nodes, f, d),
        ]
    core = attn_counts(arch, lengths if lengths is not None else [nodes])["full"]
    parts.append(flops.part(int(core["ops"]), int(core["bytes"])))
    scan = scan_counts(arch, nodes)
    parts.append(flops.part(int(scan["ops"]), int(scan["bytes"])))
    parts.append(norm)  # the final norm
    return parts, d
