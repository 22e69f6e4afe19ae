"""Laguna-XS.2's block (``model_type: "LAGUNA"``, hydragnn_tpu/models/laguna.py)
on the CPU at small widths (d 32, 5 layers: full, sliding x 3, full with 4 / 6
/ 6 / 6 / 4 query heads over 2 key-value heads of 8; a window of 4; the first
layer dense, then 16 experts, 2 a token, this rank holding 4 of them from
expert 2, and a shared expert): the program against the plain reference of
``graftbench/families/laguna.py`` for forward, loss and gradients with the
routing taken from the program and held to the margins; the eight shares of a
routed layer, the shared expert counted once, adding up to the uncut layer;
the band against the triangle at the published window; heads by layer; YaRN's
frequencies by hand; graph boundaries, padding, the entry points, the scopes,
``run_training``. Values and counts, never a time.

Two parts of it are files of their own, because ``--dist loadfile`` gives a
file to ONE worker: the compact row arrays inside the model
(tests/test_laguna_compact.py) and ``run_training``
(tests/test_laguna_train.py); the sizes above are theirs too."""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from graftbench.drivers.train_epochs import shaken  # noqa: E402
from graftbench.families import laguna as plain  # noqa: E402
from hydragnn_tpu.graphs import collate_graphs  # noqa: E402
from hydragnn_tpu.models import create_model, init_model_variables  # noqa: E402
from hydragnn_tpu.models import (  # noqa: E402
    laguna, token_attention, token_common, token_routed,
)
from hydragnn_tpu.telemetry import scopes  # noqa: E402
from tests import test_lfm2 as sibling  # noqa: E402
from tests.test_lfm2 import (  # noqa: E402, F401
    _collate, _forward, _rows, _sequences, loss_and_grads, loss_of, programs,
)

V, D, LAYERS = sibling.V, 32, 5  # the sibling's sequences: ids under its V
CONFIG = os.path.join(REPO, "graftbench", "configs", "laguna_xs2_ep8.json")
with open(CONFIG) as _f:
    PUBLISHED = json.load(_f)["NeuralNetwork"]
ARCH = dict(
    layer_types=PUBLISHED["Architecture"]["layer_types"],
    mlp_layer_types=PUBLISHED["Architecture"]["mlp_layer_types"],
    num_attention_heads_per_layer=[4, 6, 6, 6] * 10, num_key_value_heads=2, head_dim=8,
    intermediate_size=48, moe_intermediate_size=24, shared_expert_intermediate_size=16,
    num_experts=16, num_experts_per_tok=2, num_experts_held=4, experts_offset=2,
    sliding_window=4, rope_parameters=PUBLISHED["Architecture"]["rope_parameters"],
    moe_routed_scaling_factor=2.5, vocab_size=V, token_minmax=[0.0, V - 1.0],
)
HEADS = {"node": {"num_headlayers": 0, "dim_headlayers": [], "type": "mlp"}}
ROUTED = tuple(f"conv_{i}" for i in range(1, LAYERS))


def _model(remat=False, **arch):
    return create_model(
        "LAGUNA", 1, D, (V,), ("node",), HEADS, [1.0], LAYERS,
        token_arch=dict(ARCH, **arch), head_loss=("cross_entropy",),
        class_minmax=([0.0, V - 1.0],), remat=remat,
    )


@pytest.fixture(scope="module")
def setup():
    model = _model()
    graphs = _sequences((5, 9, 12))
    batch = _collate(graphs)
    variables = shaken(init_model_variables(model, batch), 33)
    return model, graphs, batch, variables


def pytest_forward_against_the_plain_reference_routing_from_the_program(setup):
    model, graphs, batch, variables = setup
    got, routing, counters = _forward(model, variables, batch)
    assert set(routing) == set(ROUTED) and got.shape == (32, V)
    start = held = 0
    for g in graphs:
        rows = slice(start, start + g.num_nodes)
        start += g.num_nodes
        want, report = plain.logits(
            model, variables["params"], {"x": g.x, "pos": g.pos}, _rows(routing, rows)
        )
        worst, rel, fail = plain.compare(got[rows], want)
        assert fail is None and worst < 2e-5 and rel < 5e-6, (worst, rel, fail)
        assert report["route_margin"] < 1e-6 and report["router_margin"] < 1e-6
        held += report["rows_held"]
    # The counter: rows routed to held experts, padding nodes routed nowhere.
    assert float(counters["moe_rows_held"]) == held > 0
    assert float(counters["moe_load_min"]) <= held / 4 <= float(counters["moe_load_max"])


def pytest_rematerialized_blocks_compute_the_same(setup):
    """``Architecture.remat`` (the cell sets it): the same parameters, the
    same outputs and routing, the same gradients."""
    model, graphs, batch, variables = setup
    again = _model(remat=True)
    got, routing, _ = _forward(model, variables, batch)
    got2, routing2, _ = _forward(again, variables, batch)
    assert np.array_equal(got, got2)
    assert np.array_equal(routing["conv_3"]["chosen"], routing2["conv_3"]["chosen"])

    def loss(m):
        return loss_and_grads(m, variables["params"], batch, True)[1]

    for a, b in zip(*(jax.tree_util.tree_leaves(loss(m)) for m in (model, again))):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() <= 1e-6 * max(
            np.abs(np.asarray(a)).max(), 1e-6
        )


def pytest_a_wrong_choice_fails_the_margin(setup):
    model, graphs, batch, variables = setup
    _, routing, _ = _forward(model, variables, batch)
    g, rows = graphs[0], slice(0, graphs[0].num_nodes)
    flipped = _rows(routing, rows)
    scores = jax.nn.sigmoid(
        flipped["conv_2"]["router_in"] @ variables["params"]["conv_2"]["feed_forward"]["gate"]
    )
    worst = np.argsort(np.asarray(scores), axis=1)[:, 0]  # the LEAST likely expert
    flipped["conv_2"]["chosen"] = np.stack(
        [flipped["conv_2"]["chosen"][:, 0], worst], axis=1
    )
    _, report = plain.logits(model, variables["params"], {"x": g.x, "pos": g.pos}, flipped)
    assert report["route_margin"] > plain.ROUTE_EPS
    assert report["router_margin"] > plain.ROUTER_EPS
    twice = _rows(routing, rows)
    twice["conv_2"]["chosen"] = np.repeat(twice["conv_2"]["chosen"][:, :1], 2, axis=1)
    _, report = plain.logits(model, variables["params"], {"x": g.x, "pos": g.pos}, twice)
    assert report["route_margin"] == float("inf")  # not K distinct experts


def pytest_loss_and_every_gradient_against_the_plain_reference(setup):
    model, graphs, batch, variables = setup
    _, routing, _ = _forward(model, variables, batch)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])

    def reference(p):
        total, start = 0.0, 0
        for g in graphs:
            rows = slice(start, start + g.num_nodes)
            start += g.num_nodes
            x = plain.encode(model, p, None, {"x": g.x, "pos": g.pos}, _rows(routing, rows))
            head = p["head_0"]["mlp"]["dense_0"]
            logp = jax.nn.log_softmax(x @ head["kernel"] + head["bias"])
            label = np.round(g.y * (V - 1.0)).astype(np.int32)
            total = total - logp[np.arange(g.num_nodes), label].sum()
        return total / start

    # Both sides one program each, traced at the precision they are held to
    # (the reference's own code, with the routing it is given as numbers).
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(loss_of(model, False)))(params, batch)
        want, g_want = jax.jit(jax.value_and_grad(reference))(params)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    flat_got, flat_want = (
        jax.tree_util.tree_leaves_with_path(t) for t in (g_got, g_want)
    )
    assert len(flat_got) == len(flat_want)
    for (path, a), (_, b) in zip(flat_got, flat_want):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all(), path
        scale = max(np.abs(b).max(), 1e-6)
        assert np.abs(a - b).max() <= 2e-4 * scale, (path, np.abs(a - b).max(), scale)
    # Every projection of both kinds of layer, the gate, the shared expert
    # and the router learn; no expert bias exists.
    for layer, names in (("conv_0", ("q_proj", "g_proj")), ("conv_2", ("k_proj", "g_proj", "o_proj"))):
        for name in names:
            assert np.asarray(g_got[layer]["self_attn"][name]["kernel"]).any(), (layer, name)
    assert np.asarray(g_got["conv_2"]["shared_expert"]["w2"]["kernel"]).any()
    assert np.asarray(g_got["conv_2"]["feed_forward"]["gate"]).any()
    assert "expert_bias" not in g_got["conv_2"]["feed_forward"]


def pytest_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """The share test: a sparse layer cut over 8 ranks (2 of 16 experts each,
    the router 16 wide and the shared expert whole on every rank) -- what the
    eight PROGRAM shares of the routed sum compute, plus the shared expert
    counted ONCE, is what the plain reference gives for the uncut layer."""
    rng = np.random.default_rng(4)
    n, k, experts = 40, 2, 16
    whole = laguna.LagunaConfig.from_arch(
        dict(ARCH, num_experts_held=experts, experts_offset=0), LAYERS
    )
    x = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
    mask = jnp.ones((n,), bool)
    full = token_routed.RoutedFFN(D, whole).init(jax.random.PRNGKey(0), x, mask)["params"]
    shared = token_routed.DenseFFN(D, whole.shared_expert_intermediate_size)
    shared_p = shared.init(jax.random.PRNGKey(1), x)["params"]
    report = dict(route_margin=0.0, router_margin=0.0, rows_held=0)
    want = plain._dense(shared_p, x, plain.Exact) + plain._routed(
        full, x, whole, plain.Exact, None, report, "layer"
    )
    assert int(report["rows_held"]) == n * k  # the uncut layer holds every expert
    total, seen = shared.apply({"params": shared_p}, x), 0
    for rank in range(8):
        share = laguna.LagunaConfig.from_arch(
            dict(ARCH, num_experts_held=2, experts_offset=2 * rank), LAYERS
        )
        held = slice(2 * rank, 2 * rank + 2)
        part = dict(full, w1=full["w1"][held], w3=full["w3"][held], w2=full["w2"][held])
        out, sown = token_routed.RoutedFFN(D, share).apply(
            {"params": part}, x, mask, mutable=[token_routed.INTERMEDIATES]
        )
        seen += int(sown[token_routed.INTERMEDIATES]["moe_rows_held"][-1])
        total = total + out
    assert seen == n * k  # every assignment is computed on exactly one rank
    assert np.abs(np.asarray(total - want)).max() < 1e-5 * np.abs(np.asarray(want)).max()
    # The scaling is in the weights: each token's weights sum to 2.5.
    s = jax.nn.sigmoid(x @ full["gate"])
    top = jax.lax.top_k(s, k)[0]
    assert np.allclose(np.asarray(2.5 * top / (top.sum(-1, keepdims=True) + 1e-6)).sum(-1), 2.5, atol=1e-4)


def _band_by_hand(q, k, v, seg, window):
    n, h, hd = q.shape
    rep = h // k.shape[1]
    k, v = (np.repeat(np.asarray(a, np.float64), rep, axis=1) for a in (k, v))
    s = np.einsum("qhd,khd->hqk", np.asarray(q, np.float64), k) * hd ** -0.5
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    keep = (seg[:, None] == seg[None, :]) & (j <= i)
    if window is not None:
        keep &= i - j < window
    s = np.where(keep[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, v).reshape(n, h * hd)


def pytest_the_band_at_the_published_window():
    """``sliding_window`` 512: on a sequence no longer than the window a
    sliding layer's aggregation IS the full one's; on a longer one it differs
    from it exactly as the masked softmax worked by hand does; and no key 512
    or more places back moves a row (the rows from 512 places after a changed
    key on are bit-equal, the nearer ones move). Two sequences in one packed
    array, the second starting mid-block."""
    rng = np.random.default_rng(2)
    h, kv, hd, w = 4, 2, 8, 512
    attention = jax.jit(token_attention.segment_causal_attention, static_argnames="window")
    for lengths in ((512,), (300, 900)):
        n = sum(lengths)
        seg = np.repeat(np.arange(len(lengths)), lengths).astype(np.int32)
        q, k, v = (
            jnp.asarray(rng.normal(size=(n, heads, hd)).astype(np.float32))
            for heads in (h, kv, kv)
        )
        band = np.asarray(attention(q, k, v, jnp.asarray(seg), window=w))
        full = np.asarray(attention(q, k, v, jnp.asarray(seg)))
        assert np.abs(band - _band_by_hand(q, k, v, seg, w)).max() < 2e-5
        assert np.abs(full - _band_by_hand(q, k, v, seg, None)).max() < 2e-5
        if max(lengths) <= w:
            assert np.abs(band - full).max() < 1e-6
            continue
        first = lengths[0]
        assert np.abs(band[:first] - full[:first]).max() < 1e-6  # 300 <= 512
        assert np.abs(band[first + w :] - full[first + w :]).max() > 1e-3
        at = first + 100  # a key of the second sequence
        k2 = k.at[at].add(1.0)
        v2 = v.at[at].add(1.0)
        moved = np.asarray(attention(q, k2, v2, jnp.asarray(seg), window=w))
        assert np.array_equal(moved[:at], band[:at])  # earlier rows, the other sequence
        assert np.abs(moved[at : at + w] - band[at : at + w]).min(axis=0).max() > 0
        assert np.abs(moved[at + w - 1] - band[at + w - 1]).max() > 1e-6  # 511 back: seen
        assert np.array_equal(moved[at + w :], band[at + w :])  # 512 back and more: not


def pytest_sliding_layers_equal_full_ones_on_short_sequences_only(setup):
    """Through the model: with the window no shorter than every sequence the
    stack's outputs are those of the window 4 on sequences of at most 4, and
    differ on longer ones, as the reference's do."""
    model, graphs, batch, variables = setup
    wide = _model(sliding_window=64)
    got, routing, _ = _forward(model, variables, batch)
    got_wide, routing_wide, _ = _forward(wide, variables, batch)
    short = _collate(_sequences((4, 3), seed=5))
    a, _, _ = _forward(model, variables, short)
    b, _, _ = _forward(wide, variables, short)
    assert np.abs(a[:7] - b[:7]).max() < 1e-6
    assert np.abs(got[14:26] - got_wide[14:26]).max() > 1e-3  # the 12-token sequence
    g = graphs[2]
    want, _ = plain.logits(
        wide, variables["params"], {"x": g.x, "pos": g.pos}, _rows(routing_wide, slice(14, 26))
    )
    assert plain.compare(got_wide[14:26], want)[1] < 5e-6


def pytest_heads_by_layer_and_the_published_parameter_count():
    """48 heads on the full layers, 64 on the sliding ones, at the published
    widths (shapes only: nothing is allocated): the projections are 6144 or
    8192 wide, the gate 48 or 64, and the cut's parameters are the
    configuration file's 691.6M."""
    arch = dict(PUBLISHED["Architecture"], token_minmax=[0.0, 12543.0])
    model = create_model(
        "LAGUNA", 1, 2048, (12544,), ("node",), HEADS, [1.0], 5, token_arch=arch,
        head_loss=("cross_entropy",), class_minmax=([0.0, 12543.0],), remat=True,
    )
    batch = _collate(_sequences((6,)))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch, train=False)
    )["params"]
    for layer, heads in enumerate((48, 64, 64, 64, 48)):
        attn = shapes[f"conv_{layer}"]["self_attn"]
        assert attn["q_proj"]["kernel"].shape == (2048, heads * 128)
        assert attn["o_proj"]["kernel"].shape == (heads * 128, 2048)
        assert attn["g_proj"]["kernel"].shape == (2048, heads)
        assert attn["k_proj"]["kernel"].shape == (2048, 8 * 128)
    assert shapes["conv_0"]["feed_forward"]["w1"]["kernel"].shape == (2048, 8192)
    assert shapes["conv_1"]["feed_forward"]["w1"].shape == (32, 2048, 512)
    assert shapes["conv_1"]["feed_forward"]["gate"].shape == (2048, 256)
    assert shapes["conv_1"]["shared_expert"]["w1"]["kernel"].shape == (2048, 512)
    count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert count == 691_636_480 and abs(count * 16 / 1e9 - 11.07) < 0.01


def pytest_yarn_frequencies_and_the_unrotated_half_by_hand():
    cfg = laguna.LagunaConfig.from_arch(
        dict(PUBLISHED["Architecture"], token_minmax=[0.0, 1.0]), 5
    )
    full, sliding = cfg.rope_parameters
    inv, factor, r = full.frequencies(128)
    assert r == 64 and inv.shape == (32,) and abs(factor - 1.4158883) < 1e-6
    assert abs(factor - (0.1 * np.log(64) + 1)) < 1e-6
    # c(64) = 64 ln(4096 / (2 pi 64)) / (2 ln 5e5) = 5.66, c(1) = 15.80: pairs
    # 0-5 keep theta^(-2i/64), pairs 16-31 are divided by 64, between them
    # the ramp (i - 5) / 11.
    theta = 500000.0
    for i in (0, 3, 5):
        assert abs(inv[i] - theta ** (-2 * i / 64)) < 1e-6 * inv[i]
    for i in (16, 20, 31):
        assert abs(inv[i] - theta ** (-2 * i / 64) / 64) < 1e-6 * inv[i]
    ramp = 5 / 11  # pair 10
    by_hand = theta ** (-20 / 64) * (ramp / 64 + (1 - ramp))
    assert abs(inv[10] - by_hand) < 1e-6 * by_hand
    assert abs(inv[10] - 0.0091506) < 1e-6  # 0.016560 x (0.45455 / 64 + 0.54545)
    ref_inv, ref_factor, ref_r = plain.frequencies(full, 128)
    assert np.allclose(np.asarray(ref_inv), inv, rtol=1e-6) and ref_r == r
    assert abs(ref_factor - factor) < 1e-7
    inv_s, factor_s, r_s = sliding.frequencies(128)
    assert r_s == 128 and factor_s == 1.0 and abs(inv_s[1] - 10000 ** (-2 / 128)) < 1e-7
    # The second half of a full layer's head is as it was; the first at place
    # 0 is the input times the attention factor; at place 3 pair 0 turns by
    # 3 rad.
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 3, 128)).astype(np.float32))
    out = np.asarray(token_common.rotary(x, jnp.asarray([0.0, 3.0]), full))
    assert np.array_equal(out[..., 64:], np.asarray(x)[..., 64:])
    assert np.allclose(out[0, :, :64], np.asarray(x)[0, :, :64] * factor, rtol=1e-6)
    a, b = np.asarray(x)[1, 0, 0], np.asarray(x)[1, 0, 32]
    assert abs(out[1, 0, 0] - factor * (a * np.cos(3.0) - b * np.sin(3.0))) < 1e-5
    assert abs(out[1, 0, 32] - factor * (b * np.cos(3.0) + a * np.sin(3.0))) < 1e-5
    whole = np.asarray(token_common.rotary(x, jnp.asarray([0.0, 3.0]), sliding))
    assert np.abs(whole[1, :, 64:] - np.asarray(x)[1, :, 64:]).max() > 1e-3


def pytest_no_mixing_across_a_graph_boundary_and_none_from_later_tokens(setup):
    model, graphs, batch, variables = setup
    base, _, _ = _forward(model, variables, batch)
    other = copy.deepcopy(graphs)
    other[1].x = ((np.round(other[1].x * (V - 1.0)) + 7) % V / (V - 1.0)).astype(np.float32)
    moved, _, _ = _forward(model, variables, _collate(other))
    assert np.array_equal(moved[:5], base[:5]) and np.array_equal(moved[14:26], base[14:26])
    assert np.abs(moved[5:14] - base[5:14]).max() > 1e-3
    later = copy.deepcopy(graphs)
    later[2].x[8:] = ((np.round(later[2].x[8:] * (V - 1.0)) + 3) % V / (V - 1.0))
    moved, _, _ = _forward(model, variables, _collate(later))
    assert np.array_equal(moved[: 14 + 8], base[: 14 + 8])
    assert np.abs(moved[14 + 8 : 26] - base[14 + 8 : 26]).max() > 1e-3


def pytest_padding_changes_nothing_and_every_gradient_is_finite(setup):
    model, graphs, batch, variables = setup
    base, routing, counters = _forward(model, variables, batch)
    wide = _collate(graphs, num_nodes_pad=64, num_edges_pad=128, num_graphs_pad=6)
    got, routing_wide, counters_wide = _forward(model, variables, wide)
    assert np.abs(got[:26] - base[:26]).max() < 5e-5  # another shape, other fusions
    assert np.array_equal(routing_wide["conv_2"]["chosen"][:26], routing["conv_2"]["chosen"][:26])
    assert float(counters_wide["moe_rows_held"]) == float(counters["moe_rows_held"])

    value, grads = loss_and_grads(model, variables["params"], wide, True)
    assert np.isfinite(float(value))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.isfinite(np.asarray(g)).all(), path


def pytest_train_step_scopes_counters_and_other_families_untouched():
    """The compiled train step (rematerialized, as the cell's) carries both
    attention scopes and the routed layers' under the root, forward and
    backward, and nothing outside the vocabulary; its metrics hold the four
    counters. LFM2's step opens neither new scope; a classic family's has no
    counters."""
    import re

    from hydragnn_tpu.train.trainer import create_train_state, make_train_step
    from hydragnn_tpu.utils.optimizer import select_optimizer

    def used_scopes(text):
        names = re.findall(r'op_name="([^"]*)"', text)
        return set(re.findall(r"hydragnn\.[\w.]+", " ".join(names))), names

    model = _model(remat=True)
    batch = _collate(_sequences((5, 9, 12)))
    opt = select_optimizer("AdamW", 1e-3)
    state = create_train_state(model, init_model_variables(model, batch), opt)
    assert state.batch_stats == {} and model.counts_routing
    step = make_train_step(model, opt, donate=False)
    program = step.lower(state, batch, jax.random.PRNGKey(0)).compile()
    used, names = used_scopes(program.as_text())
    assert {scopes.ATTN_FULL, scopes.ATTN_WINDOW, scopes.MOE_ROUTE, scopes.MOE_EXPERTS,
            scopes.LOSS, scopes.OPTIMIZER, scopes.TRAIN_STEP} <= used
    assert used <= scopes.VOCABULARY and not {scopes.LFM2_ATTN, scopes.LFM2_CONV} & used
    backward = [n for n in names if "transpose(" in n]
    for scope in (scopes.ATTN_FULL, scopes.ATTN_WINDOW, scopes.MOE_EXPERTS):
        assert any(scope in n for n in backward), scope
    _, metrics = program(state, batch, jax.random.PRNGKey(0))  # the one read above
    assert set(metrics) == {"loss", "rmses", "count", *token_routed.COUNTERS}
    assert 0 <= float(metrics["moe_load_min"]) <= float(metrics["moe_load_max"])
    assert 0 < float(metrics["moe_rows_held"]) <= 4 * 26 * 2

    lfm2_model = sibling._model()  # the sibling's small model
    sbatch = _collate(_sequences((5, 9)))
    sstate = jax.eval_shape(lambda: create_train_state(  # lowered, never run: shapes
        lfm2_model, init_model_variables(lfm2_model, sbatch), opt
    ))
    used, _ = used_scopes(
        make_train_step(lfm2_model, opt, donate=False)
        .lower(sstate, sbatch, jax.random.PRNGKey(0)).compile().as_text()
    )
    assert {scopes.LFM2_ATTN, scopes.MOE_EXPERTS} <= used
    assert not {scopes.ATTN_FULL, scopes.ATTN_WINDOW} & used
    assert "expert_bias" in sstate.params["conv_2"]["feed_forward"]
    classic = create_model(
        "SAGE", 1, 8, (1,), ("node",),
        {"node": {"num_headlayers": 1, "dim_headlayers": [4], "type": "mlp"}}, [1.0], 2,
    )
    assert not classic.counts_routing and classic.token_cfg is None
    cbatch = collate_graphs(_sequences((5, 9)), ("node",), (1,))
    cstate = create_train_state(classic, init_model_variables(classic, cbatch), opt)
    _, cmetrics = make_train_step(classic, opt, donate=False)(
        cstate, cbatch, jax.random.PRNGKey(0)
    )
    assert set(cmetrics) == {"loss", "rmses", "count"}


def pytest_entry_points_refuse_what_the_family_cannot_run():
    make = lambda **kw: create_model(  # noqa: E731
        "LAGUNA", 1, D, (V,), ("node",), HEADS, [1.0], LAYERS, **kw
    )
    with pytest.raises(ValueError, match="compute_dtype"):
        make(token_arch=ARCH, compute_dtype="bfloat16")
    with pytest.raises(ValueError, match="token_minmax"):
        make(token_arch={k: v for k, v in ARCH.items() if k != "token_minmax"})
    with pytest.raises(ValueError, match="stack's sizes"):
        make()
    with pytest.raises(ValueError, match="not among"):
        _model(num_experts_held=4, experts_offset=14)
    with pytest.raises(ValueError, match="layer_types"):
        _model(layer_types=["full_attention", "conv"] * 3)
    with pytest.raises(ValueError, match="key-value heads"):
        _model(num_attention_heads_per_layer=[4, 5, 6, 6, 4])
    with pytest.raises(ValueError, match="rope_type"):
        _model(rope_parameters={
            "full_attention": {"rope_theta": 1e4, "rope_type": "llama3"},
            "sliding_attention": {"rope_theta": 1e4},
        })
    with pytest.raises(ValueError, match="class_minmax"):
        make(token_arch=ARCH, head_loss=("cross_entropy",))
    model = _model()
    with pytest.raises(ValueError, match="positions"):
        batch = collate_graphs(_sequences((5,)), ("node",), (1,))
        model.init(jax.random.PRNGKey(0), batch, train=False)
    from hydragnn_tpu.analysis.contracts import check_config

    config = {
        "Verbosity": {"level": 0},
        "Dataset": {"name": "x", "format": "unit_test", "path": {"total": "x"},
                    "node_features": {"name": ["t", "n"], "dim": [1, 1], "column_index": [0, 1]},
                    "graph_features": {"name": ["u"], "dim": [1], "column_index": [0]}},
        "NeuralNetwork": copy.deepcopy(PUBLISHED),
    }
    del config["NeuralNetwork"]["Architecture"]["sliding_window"]
    report = check_config(config, strict=False, deep=False)
    assert any(
        e["code"] == "bad-arch" and "LAGUNA" in e["message"] and "sliding_window" in e["message"]
        for e in report["errors"]
    ), report["errors"]
