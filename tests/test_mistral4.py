"""Mistral-Small-4's block (``model_type: "MISTRAL4"``,
hydragnn_tpu/models/mistral4.py) on the CPU at small widths with the published
RATIOS (d 32, 3 layers, 4 heads with a per-head part of 6, a rotary part of 4
and a value of 8: three different widths; q rank 24, kv rank 12; 8 experts, 2
a token, this rank holding 4 of them from expert 2, a shared expert; a rotary
trained at 8 places, so that Llama-4's factor is at work inside the test
documents): the program against the plain reference of
``graftbench/families/mistral4.py`` with the routing taken from the program
and held to the margin; the four shares of a routed layer, the shared expert
counted once, adding up to the uncut layer; the rotation in place against the
permuted halves; YaRN's frequencies, the scale and Llama-4's factor by hand;
the router; padding and graph boundaries; ``run_training``; and the serving
engine's token path: the reply against the direct forward, alone and
co-batched, ``routing`` and the three counters against a hand count, what is
refused, and no compile after ``warmup()``. Values and counts, never a time."""

import copy
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from graftbench.drivers.train_epochs import shaken  # noqa: E402
from graftbench.families import mistral4 as plain  # noqa: E402
from hydragnn_tpu.graphs import GraphSample, collate_graphs  # noqa: E402
from hydragnn_tpu.models import create_model, init_model_variables  # noqa: E402
from hydragnn_tpu.models import (  # noqa: E402
    laguna, lfm2, mistral4, token_attention, token_common, token_routed,
)
from hydragnn_tpu.models.base import HydraGNN  # noqa: E402
from hydragnn_tpu.telemetry import scopes  # noqa: E402
from tests import test_lfm2 as sibling  # noqa: E402
from tests.test_lfm2 import (  # noqa: E402, F401
    _collate, _sequences, apply_routed, compiled, loss_and_grads, programs,
)

V, D, LAYERS = sibling.V, 32, 3  # the sibling's sequences: ids under its V
CONFIG = os.path.join(REPO, "graftbench", "configs", "mistral_small4_ep8.json")
with open(CONFIG) as _f:
    PUBLISHED = json.load(_f)["NeuralNetwork"]
ROPE = dict(PUBLISHED["Architecture"]["rope_parameters"], original_max_position_embeddings=8)
ARCH = dict(
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=12, qk_nope_head_dim=6,
    qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48, moe_intermediate_size=24,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2, num_experts_held=4,
    experts_offset=2, rope_parameters=ROPE, vocab_size=V, token_minmax=[0.0, V - 1.0],
)
HEADS = {"node": {"num_headlayers": 0, "dim_headlayers": [], "type": "mlp"}}
K = ARCH["num_experts_per_tok"]


def _model(layers=LAYERS, **arch):
    return create_model(
        "MISTRAL4", 1, D, (V,), ("node",), HEADS, [1.0], layers,
        token_arch=dict(ARCH, **arch), head_loss=("cross_entropy",),
        class_minmax=([0.0, V - 1.0],),
    )


def _forward(model, variables, batch):
    """(logits, the routing as the engine returns it [N, layers x K], counters)."""
    out, sown = apply_routed(model, variables["params"], batch)
    routing, counters = token_routed.split_intermediates(sown[token_routed.INTERMEDIATES])
    chosen = np.concatenate(
        [np.asarray(routing[f"conv_{i}"]["chosen"]) for i in range(model.num_conv_layers)], axis=1
    )
    return np.asarray(out[0]), chosen, counters


@pytest.fixture(scope="module")
def setup():
    model = _model()
    graphs = _sequences((5, 9, 30))  # the third runs past the 8 trained places
    batch = _collate(graphs)
    variables = shaken(init_model_variables(model, batch), 39)
    return model, graphs, batch, variables


def pytest_forward_against_the_plain_reference_routing_from_the_program(setup):
    model, graphs, batch, variables = setup
    got, routing, counters = _forward(model, variables, batch)
    assert got.shape == (64, V) and routing.shape == (64, LAYERS * K)
    start = held = 0
    for g in graphs:
        rows = slice(start, start + g.num_nodes)
        start += g.num_nodes
        want, report = plain.logits(
            model, variables["params"], {"x": g.x, "pos": g.pos}, routing[rows]
        )
        assert np.abs(got[rows] - want).max() < 5e-5 * max(np.abs(want).max(), 1.0)
        assert report["route_margin"] < 1e-5
        held += report["rows_held"]
        # Routed by the reference's own top-K: the same experts.
        _, own = plain.logits(model, variables["params"], {"x": g.x, "pos": g.pos})
        assert np.array_equal(np.sort(np.concatenate(own["chosen"], 1).reshape(-1, K)),
                              np.sort(routing[rows].reshape(-1, K)))
    assert float(counters["moe_rows_held"]) == held > 0
    assert float(counters["moe_load_min"]) <= held / 4 <= float(counters["moe_load_max"])


def pytest_a_wrong_choice_fails_the_margin_on_the_routers_logits(setup):
    model, graphs, batch, variables = setup
    _, routing, _ = _forward(model, variables, batch)
    g, rows = graphs[2], slice(14, 44)
    graph = {"x": g.x, "pos": g.pos}
    _, report = plain.logits(model, variables["params"], graph, routing[rows])
    assert report["route_margin"] < 1e-5
    flipped = routing[rows].copy()
    # Layer 1's second choice becomes an expert the token did not choose.
    flipped[:, 2 * K - 1] = [
        next(e for e in range(8) if e not in flipped[i, K : 2 * K])
        for i in range(g.num_nodes)
    ]
    _, report = plain.logits(model, variables["params"], graph, flipped)
    assert report["route_margin"] > plain.ROUTE_EPS, report["route_margin"]
    twice = routing[rows].copy()
    twice[:, 1] = twice[:, 0]
    _, report = plain.logits(model, variables["params"], graph, twice)
    assert report["route_margin"] == float("inf")  # not K distinct experts


def pytest_the_router_is_a_softmax_over_all_experts_top_k_normalised():
    rng = np.random.default_rng(5)
    cfg = mistral4.Mistral4Config.from_arch(dict(ARCH, num_experts_held=8, experts_offset=0), LAYERS)
    assert cfg.scoring_func == "softmax" and not cfg.use_expert_bias and cfg.norm_topk_prob
    x = jnp.asarray(rng.normal(size=(12, D)).astype(np.float32))
    layer = token_routed.RoutedFFN(D, cfg)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x, jnp.ones((12,), bool))["params"]
    assert "expert_bias" not in params
    out, sown = layer.apply(
        {"params": params}, x, jnp.ones((12,), bool), mutable=[token_routed.INTERMEDIATES]
    )
    chosen = np.asarray(sown[token_routed.INTERMEDIATES]["moe_chosen"][-1])
    p = np.asarray(jax.nn.softmax(np.asarray(x, np.float64) @ np.asarray(params["gate"], np.float64)))
    assert np.allclose(p.sum(-1), 1.0)
    assert np.array_equal(np.sort(chosen), np.sort(np.argsort(-p, axis=1)[:, :K]))
    want = np.zeros((12, D))
    for i in range(12):
        w = p[i, chosen[i]] / (p[i, chosen[i]].sum() + 1e-6)
        for e, w_e in zip(chosen[i], w):
            a = np.asarray(x[i], np.float64) @ np.asarray(params["w1"][e], np.float64)
            b = np.asarray(x[i], np.float64) @ np.asarray(params["w3"][e], np.float64)
            want[i] += w_e * ((a / (1 + np.exp(-a))) * b) @ np.asarray(params["w2"][e], np.float64)
    assert np.abs(np.asarray(out) - want).max() < 1e-5 * np.abs(want).max()
    # The siblings' sizes keep the sigmoid, and say so themselves: the routed
    # layer defaults nothing.
    assert lfm2.LFM2Config.scoring_func == laguna.LagunaConfig.scoring_func == "sigmoid"


def pytest_four_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """The share test: a layer cut over 4 ranks (2 of 8 experts each, the
    router 8 wide and the shared expert whole on every rank) -- what the four
    PROGRAM shares of the routed sum compute, plus the shared expert counted
    ONCE, is what the plain reference gives for the uncut layer."""
    rng = np.random.default_rng(4)
    n, experts = 40, 8
    whole = mistral4.Mistral4Config.from_arch(
        dict(ARCH, num_experts_held=experts, experts_offset=0), LAYERS
    )
    x = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
    mask = jnp.ones((n,), bool)
    full = token_routed.RoutedFFN(D, whole).init(jax.random.PRNGKey(0), x, mask)["params"]
    shared = token_routed.DenseFFN(D, whole.moe_intermediate_size)
    shared_p = shared.init(jax.random.PRNGKey(1), x)["params"]
    report = dict(route_margin=0.0, loads=[], chosen=[])
    want = plain._dense(shared_p, x, plain.Exact) + plain._routed(
        full, x, whole, plain.Exact, None, report
    )
    assert sum(report["loads"][0]) == n * K  # the uncut layer holds every expert
    total, seen = shared.apply({"params": shared_p}, x), 0
    for rank in range(4):
        share = mistral4.Mistral4Config.from_arch(
            dict(ARCH, num_experts_held=2, experts_offset=2 * rank), LAYERS
        )
        held = slice(2 * rank, 2 * rank + 2)
        part = dict(full, w1=full["w1"][held], w3=full["w3"][held], w2=full["w2"][held])
        out, sown = token_routed.RoutedFFN(D, share).apply(
            {"params": part}, x, mask, mutable=[token_routed.INTERMEDIATES]
        )
        seen += int(sown[token_routed.INTERMEDIATES]["moe_rows_held"][-1])
        total = total + out
    assert seen == n * K  # every assignment is computed on exactly one rank
    assert np.abs(np.asarray(total - want)).max() < 1e-5 * np.abs(np.asarray(want)).max()


def pytest_interleaved_rotation_in_place_against_the_permuted_halves():
    """The program permutes the rotary columns of q and k alike and rotates
    in the halves convention; the reference turns each pair where it stands.
    Every dot product agrees; a permutation of one side alone does not."""
    rng = np.random.default_rng(6)
    n, rot = 11, 8
    place = jnp.asarray(rng.permutation(40)[:n].astype(np.float32))
    q = jnp.asarray(rng.normal(size=(n, 3, rot)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(n, 1, rot)).astype(np.float32))
    inv, factor = plain.frequencies(ROPE, rot)
    q_ref, k_ref = (plain.turn_pairs(a, place, inv, factor) for a in (q, k))
    q_got, k_got = (
        token_common.rotate(mistral4.pairs_to_halves(a), place, inv, factor) for a in (q, k)
    )
    want = np.einsum("qhd,kd->hqk", np.asarray(q_ref), np.asarray(k_ref)[:, 0])
    got = np.einsum("qhd,kd->hqk", np.asarray(q_got), np.asarray(k_got)[:, 0])
    assert np.abs(got - want).max() < 1e-5
    one_side = np.einsum("qhd,kd->hqk", np.asarray(q_got), np.asarray(k_ref)[:, 0])
    assert np.abs(one_side - want).max() > 0.1
    # By hand: pair (2, 3) of a row at place p turns by p * inv[1].
    p, angle = float(place[4]), float(place[4]) * float(inv[1])
    a, b = float(q[4, 2, 2]), float(q[4, 2, 3])
    assert float(q_ref[4, 2, 2]) == pytest.approx(a * math.cos(angle) - b * math.sin(angle), abs=1e-5)
    assert float(q_ref[4, 2, 3]) == pytest.approx(b * math.cos(angle) + a * math.sin(angle), abs=1e-5)
    assert p == float(place[4])


def pytest_yarn_frequencies_the_scale_and_llama4s_factor_by_hand():
    rope = PUBLISHED["Architecture"]["rope_parameters"]
    cfg = mistral4.Mistral4Config.from_arch(
        dict(PUBLISHED["Architecture"], token_minmax=[0.0, 16383.0]), 5
    )
    inv, factor, r = cfg.rope_parameters.frequencies(cfg.qk_rope_head_dim)
    assert r == 64 and factor == 1.0  # mscale(128, 1) / mscale(128, 1)
    # c(32) = 12.88, c(1) = 24.92: pairs 0-12 keep theta^(-2i/64), pairs from
    # 25 are divided by 128, a linear blend between.
    c = lambda b: 64 * math.log(8192 / (2 * math.pi * b)) / (2 * math.log(1e4))  # noqa: E731
    assert (math.floor(c(32)), math.ceil(c(1))) == (12, 25)
    for i in (0, 7, 12):
        assert inv[i] == pytest.approx(1e4 ** (-2 * i / 64), rel=1e-6)
    for i in (25, 31):
        assert inv[i] == pytest.approx(1e4 ** (-2 * i / 64) / 128, rel=1e-6)
    g = 1 - (18 - 12) / (25 - 12)
    assert inv[18] == pytest.approx(1e4 ** (-36 / 64) * ((1 - g) / 128 + g), rel=1e-6)
    ref_inv, ref_factor = plain.frequencies(rope, 64)
    assert np.allclose(np.asarray(ref_inv), inv, rtol=1e-6) and ref_factor == 1.0
    # s = 128^-0.5 (0.1 ln 128 + 1)^2
    assert cfg.softmax_scale == pytest.approx(0.19497, abs=1e-5)
    assert plain.softmax_scale(rope, 128) == pytest.approx(cfg.softmax_scale, rel=1e-9)
    assert mistral4.yarn_mscale(128, 1) ** 2 == pytest.approx(2.2058, abs=1e-4)
    # A config whose two mscales differ puts their ratio on cos and sin.
    other = mistral4.Mistral4Config.from_arch(
        dict(ARCH, rope_parameters=dict(ROPE, mscale=0.5)), LAYERS
    )
    assert other.rope_parameters.attention_factor == pytest.approx(
        (0.05 * math.log(128) + 1) / (0.1 * math.log(128) + 1)
    )
    # Llama-4's factor: 1 below the trained context, growing by blocks of it.
    place = jnp.asarray([0.0, 8191.0, 8192.0, 16383.0, 16384.0, 81920.0])
    got = np.asarray(mistral4.llama4_factor(place, 0.1, 8192))
    want = [1, 1, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(3), 1 + 0.1 * math.log(11)]
    assert np.allclose(got, want, rtol=1e-6)


def pytest_llama4s_factor_is_at_work_past_the_trained_places(setup):
    """The fixture's rotary was trained at 8 places and its longest document
    holds 30: without the factor on q the logits past place 8 differ."""
    model, graphs, batch, variables = setup
    got, _, _ = _forward(model, variables, batch)
    flat = _model(rope_parameters=dict(ROPE, llama_4_scaling_beta=0.0))
    other, _, _ = _forward(flat, variables, batch)
    assert np.array_equal(got[:5], other[:5])  # 5 tokens: every place under 8
    assert np.abs(got[14 + 8 : 44] - other[14 + 8 : 44]).max() > 1e-4


def pytest_no_mixing_across_a_boundary_and_padding_changes_nothing(setup):
    model, graphs, batch, variables = setup
    got, routing, _ = _forward(model, variables, batch)
    alone = _collate(graphs[1:2])
    one, r_one, _ = _forward(model, variables, alone)
    assert np.abs(one[:9] - got[5:14]).max() < 2e-5 and np.array_equal(r_one[:9], routing[5:14])
    wide = _collate(graphs, num_nodes_pad=96, num_edges_pad=256)
    padded, r_pad, _ = _forward(model, variables, wide)
    assert np.abs(padded[:44] - got[:44]).max() < 2e-5 and np.array_equal(r_pad[:44], routing[:44])
    # A later token changes no earlier row of its own document.
    cut = copy.deepcopy(graphs[2])
    cut.x, cut.pos, cut.y = cut.x[:20], cut.pos[:20], cut.y[:20]
    cut.y_loc, cut.edge_index = np.array([[0, 20]], np.int64), None
    short, _, _ = _forward(model, variables, _collate([cut]))
    assert np.abs(short[:20] - got[14:34]).max() < 2e-5


def pytest_scopes_of_the_block_and_of_the_reply(setup):
    model, graphs, batch, variables = setup
    names = {scopes.ATTN_LATENT, scopes.ATTN_FULL, scopes.MOE_SHARED, scopes.MOE_ROUTE,
             scopes.MOE_EXPERTS, scopes.HEAD_LOGPROB}
    assert names <= scopes.VOCABULARY and scopes.VERSION == 1
    text = jax.jit(
        lambda p: model.apply({"params": p}, batch, method=HydraGNN.score_tokens)
    ).lower(variables["params"]).as_text(debug_info=True)
    for name in names:
        assert name in text, name
    # The low-rank chains are under the latent scope, the output projection
    # and the flash kernel's rows are not.
    assert f"self_attn/{scopes.ATTN_LATENT}/q_a_proj" in text
    assert f"self_attn/{scopes.ATTN_LATENT}/kv_b_proj" in text
    assert f"{scopes.ATTN_LATENT}/o_proj" not in text and "self_attn/o_proj" in text
    assert f"{scopes.MOE_SHARED}/shared_experts" in text
    plain_forward = jax.jit(lambda p: model.apply({"params": p}, batch)).lower(
        variables["params"]
    ).as_text(debug_info=True)
    assert scopes.HEAD_LOGPROB not in plain_forward  # run_prediction returns logits


def pytest_entry_points_refuse_what_the_family_cannot_run():
    make = lambda **kw: create_model(  # noqa: E731
        "MISTRAL4", 1, D, (V,), ("node",), HEADS, [1.0], LAYERS, **kw
    )
    with pytest.raises(ValueError, match="compute_dtype"):
        make(token_arch=ARCH, compute_dtype="bfloat16")
    with pytest.raises(ValueError, match="token_minmax"):
        make(token_arch={k: v for k, v in ARCH.items() if k != "token_minmax"})
    with pytest.raises(ValueError, match="stack's sizes"):
        make()
    with pytest.raises(ValueError, match="not among"):
        _model(num_experts_held=4, experts_offset=6)
    with pytest.raises(ValueError, match="group limit"):
        _model(n_group=4, topk_group=2)
    with pytest.raises(ValueError, match="rope_interleave"):
        _model(rope_interleave=False)
    with pytest.raises(ValueError, match="intermediate_size"):
        _model(first_k_dense_replace=1, intermediate_size=0)
    dense_first = _model(first_k_dense_replace=1)
    batch = _collate(_sequences((5,)))
    params = init_model_variables(dense_first, batch)["params"]
    assert "shared_experts" not in params["conv_0"] and "shared_experts" in params["conv_1"]
    assert params["conv_0"]["feed_forward"]["w1"]["kernel"].shape == (D, 48)
    with pytest.raises(ValueError, match="positions"):
        _model().init(jax.random.PRNGKey(0), collate_graphs(_sequences((5,)), ("node",), (1,)), train=False)
    from hydragnn_tpu.analysis.contracts import check_config

    config = {
        "Verbosity": {"level": 0},
        "Dataset": {"name": "x", "format": "unit_test", "path": {"total": "x"},
                    "node_features": {"name": ["t", "n"], "dim": [1, 1], "column_index": [0, 1]},
                    "graph_features": {"name": ["u"], "dim": [1], "column_index": [0]}},
        "NeuralNetwork": copy.deepcopy(PUBLISHED),
    }
    del config["NeuralNetwork"]["Architecture"]["kv_lora_rank"]
    report = check_config(config, strict=False, deep=False)
    assert any(
        e["code"] == "bad-arch" and "MISTRAL4" in e["message"] and "kv_lora_rank" in e["message"]
        for e in report["errors"]
    ), report["errors"]


def pytest_published_parameter_count():
    """The configuration's ``parameters`` arithmetic against the tree the
    initializer would make (shapes alone)."""
    from graftbench.drivers import serve_tokens

    with open(CONFIG) as f:
        config = json.load(f)
    model, template, _ = serve_tokens.init_model(serve_tokens.completed_arch(config))
    sizes = {
        k: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(v))
        for k, v in template["params"].items()
    }
    attn = 4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144 + 4096 * 4096 + 1024 + 256
    layer = attn + 3 * 4096 * 2048 + 4096 * 128 + 16 * 3 * 4096 * 2048 + 2 * 4096
    assert sizes["conv_0"] == layer and round(layer / 1e6, 1) == 456.4
    assert sizes["conv_embed"] == 16384 * 4096 and sizes["head_0"] == 16384 * 4097
    total = sum(sizes.values())
    assert round(total * 4 / 1e9, 2) == 9.66


def pytest_run_training_trains_the_family_through_the_loaders(tmp_path, monkeypatch):
    """``run_training`` on a ``model_type: "MISTRAL4"`` config: the
    benchmark's generator and configuration file at small sizes, the loaders'
    split, config completion, ``TrainingDriver``'s scanned epoch: the loss
    falls from ln(vocab), every value finite, the counters published; then
    ``run_prediction`` still returns logits."""
    import hydragnn_tpu
    from graftbench import datasets
    from hydragnn_tpu import telemetry

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    block, _ = datasets.materialize(
        {"generator": "token_chain", "graphs": 40, "tokens": 24, "vocab": V,
         "successors": 2}, 7, str(tmp_path / "cache"),
    )
    nn_block = copy.deepcopy(PUBLISHED)
    nn_block["Architecture"].update(
        {k: v for k, v in ARCH.items() if k != "token_minmax"}, hidden_dim=D,
        num_conv_layers=LAYERS,
    )
    nn_block["Variables_of_interest"]["num_classes"] = [V]
    nn_block["Training"].update(batch_size=4, num_epoch=1, learning_rate=0.01)
    config = {
        "Verbosity": {"level": 0}, "Dataset": block, "NeuralNetwork": nn_block,
        "Visualization": {"create_plots": 0},
    }
    history = hydragnn_tpu.run_training(config)
    losses = history["total_loss_train"]
    assert abs(losses[0] - np.log(V)) < 1.0 and np.isfinite(losses).all()
    assert all(np.isfinite(history["total_loss_val"]))
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["output_dim"] == [V] and arch["target_dim"] == [1]
    assert arch["head_loss"] == ["cross_entropy"]
    gauges = telemetry.gauges_snapshot()
    assert gauges["train/moe_rows_held_per_epoch"] > 0


def pytest_gradients_are_finite_through_the_block(setup):
    model, graphs, batch, variables = setup
    _, grads = loss_and_grads(model, variables["params"], batch, True)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert all(np.isfinite(np.asarray(g)).all() for _, g in flat)
    moved = {jax.tree_util.keystr(p) for p, g in flat if np.abs(np.asarray(g)).max() > 0}
    for name in ("q_a_proj", "kv_a_proj_with_mqa", "kv_b_proj", "q_a_layernorm",
                 "kv_a_layernorm", "shared_experts", "gate", "w2"):
        assert any(name in key for key in moved), name


# ----------------------------------------------------------------- the engine
def _requests(graphs):
    return [GraphSample(x=g.x, pos=g.pos) for g in graphs]


def _direct_logprobs(model, variables, g):
    """The direct forward's log-probabilities of one document's next tokens."""
    out = compiled(model, "logits", lambda params, batch: model.apply(
        {"params": params}, batch
    ))(variables["params"], _collate([g]))[0]
    logp = np.asarray(jax.nn.log_softmax(out[: g.num_nodes], axis=-1))
    ids = np.round(g.x[:, 0] * (V - 1.0)).astype(int)
    want = np.zeros((g.num_nodes, 1), np.float32)
    want[:-1, 0] = logp[np.arange(g.num_nodes - 1), ids[1:]]
    return want


@pytest.fixture(scope="module")
def engine(setup):
    from hydragnn_tpu.serve import InferenceEngine

    model, graphs, batch, variables = setup
    eng = InferenceEngine(
        model, variables, max_batch_graphs=3, max_delay_ms=300.0, queue_limit=8,
        bucket_ladder=[32, (64, 4096)], warmup=True, autostart=True,
    )
    yield eng
    eng.close()


def pytest_engine_reply_is_the_direct_forwards_log_probabilities(setup, engine):
    from hydragnn_tpu.analysis.sentinel import compile_count

    model, graphs, batch, variables = setup
    # Rungs in TOKENS: a bare count or a pair, each with the 8 padding edges.
    assert engine._current_ladder() == [(32, 8), (64, 8)] and engine.compiled_buckets == 2
    before = compile_count()
    futures = [engine.submit(r) for r in _requests(graphs)]
    replies = [f.result(60) for f in futures]
    together = engine.metrics.snapshot()
    # Alone, a document is routed and scored as it was co-batched; both rungs
    # were warmed, so neither flush compiled.
    alone = engine.submit(_requests(graphs)[2])
    alone_reply = alone.result(60)
    assert compile_count() == before
    for g, reply, future in zip(graphs, replies, futures):
        assert len(reply) == 1 and reply[0].shape == (g.num_nodes, 1)
        assert reply[0][-1, 0] == 0.0  # a document's last token has no next
        assert np.abs(reply[0] - _direct_logprobs(model, variables, g)).max() < 2e-5
        want, report = plain.logprobs(
            model, variables["params"], {"x": g.x, "pos": g.pos}, future.routing
        )
        worst, rel, fail = plain.compare(reply[0], want)
        assert fail is None and worst < 5e-5 and report["route_margin"] < 1e-5
        assert future.routing.shape == (g.num_nodes, LAYERS * K)
        assert future.routing.dtype == np.int32
    assert np.abs(alone_reply[0] - replies[2][0]).max() < 2e-5
    assert np.array_equal(alone.routing, futures[2].routing)
    assert together["per_bucket"] == {
        "64x8": {"batches": 1, "graphs": 3, "node_fill_mean": 0.6875, "edge_fill_mean": 0.0}
    }
    # The three counters of the first flush against a hand count of its rows.
    chosen = np.concatenate([f.routing for f in futures]).reshape(44, LAYERS, K) - 2
    loads = np.stack([
        np.bincount(chosen[:, layer][(chosen[:, layer] >= 0) & (chosen[:, layer] < 4)], minlength=4)
        for layer in range(LAYERS)
    ])
    cap = token_routed._capacity(64 * K, 4, 8)
    assert cap == 256  # every row array of a 64-token rung is one pass
    assert together["moe_rows_held_total"] == loads.sum() > 0
    assert together["moe_load_max_total"] == loads.max(axis=1).sum()
    assert together["moe_fallback_layers_total"] == 0
    text = engine.metrics.render_prometheus()
    for name in ("moe_rows_held_total", "moe_load_max_total", "moe_fallback_layers_total"):
        assert f"hydragnn_serve_{name} " in text


def pytest_engine_spans_the_copy_to_the_host_and_gauges_the_counters(setup):
    """graftel: ``serve/d2h`` round the copy of a flush's outputs to the host,
    inside ``serve/device``; the three routing counters as gauges a flush."""
    from hydragnn_tpu import telemetry
    from hydragnn_tpu.serve import InferenceEngine

    model, graphs, batch, variables = setup
    telemetry.configure(collect=True)
    try:
        with InferenceEngine(model, variables, max_batch_graphs=1, max_delay_ms=1.0,
                             bucket_ladder=[32], warmup=True) as eng:
            future = eng.submit(_requests(graphs)[1])
            future.result(60)
        spans = {r["name"]: r for r in telemetry.collected_records() if r["kind"] == "span"}
        gauges = telemetry.gauges_snapshot()
    finally:
        telemetry.configure(collect=False)
    assert {"serve/collate", "serve/h2d", "serve/device", "serve/d2h"} <= set(spans)
    assert spans["serve/d2h"]["parent_id"] == spans["serve/device"]["span_id"]
    held = int(((future.routing >= 2) & (future.routing < 6)).sum())
    assert gauges["serve/moe_rows_held"] == held > 0
    assert gauges["serve/moe_load_max"] >= held / (4 * LAYERS)
    assert gauges["serve/moe_fallback_layers"] == 0


def pytest_engine_counts_the_key_blocks_a_flush_visits(setup, engine, monkeypatch):
    """``attn_key_blocks_visited_total`` / ``_causal_total`` a flush, by the
    function that hands the TPU's kernel its block range, on the flush's own
    ``node_graph``: 5 + 9 + 30 tokens and 20 padding rows in the 64-token
    rung, in blocks of 8 rows here. An engine on a CPU walks the triangle."""
    from hydragnn_tpu import telemetry

    model, graphs, batch, variables = setup
    monkeypatch.setattr(token_attention, "ATTN_BLOCK", 8)
    names = ("attn_key_blocks_visited_total", "attn_key_blocks_causal_total")

    def flush():
        before = engine.metrics.read_counters(*names)
        for future in [engine.submit(r) for r in _requests(graphs)]:
            future.result(60)
        after = engine.metrics.read_counters(*names)
        return tuple(after[n] - before[n] for n in names)

    assert flush() == (36, 36)
    # As an engine on a TPU counts it: block 0 and 1 open in the first two
    # documents (from block 0), blocks 2-5 in the third (rows 14-43: from
    # block 1), blocks 6 and 7 in the padding (rows 44-63: from block 5).
    monkeypatch.setitem(engine.device, "platform", "tpu")
    telemetry.configure(collect=True)
    try:
        assert flush() == (1 + 2 + 2 + 3 + 4 + 5 + 2 + 3, 36)
        gauges = telemetry.gauges_snapshot()
    finally:
        telemetry.configure(collect=False)
    assert gauges["serve/attn_key_blocks_visited"] == 22
    assert gauges["serve/attn_key_blocks_causal"] == 36
    text = engine.metrics.render_prometheus()
    assert all(f"hydragnn_serve_{name} " in text for name in names)
    assert set(names) <= set(engine.metrics.snapshot())


def pytest_engine_counts_a_layer_past_its_capacity():
    """A rung whose row arrays are compact ([C, .], C under K N) and a
    document that sends one layer more rows than C: counted as a fall-back
    layer, and the reply is still the direct forward's."""
    from hydragnn_tpu.serve import InferenceEngine

    # 8 experts, 2 a token, 2 held: C = 1.5 x 2 x 300 x 2 / 8 -> 256 rows.
    model = _model(layers=1, num_experts_held=2, experts_offset=0)
    g = _sequences((280,), seed=3)[0]
    variables = shaken(init_model_variables(model, _collate([g])), 5)
    gate = np.array(variables["params"]["conv_0"]["feed_forward"]["gate"])
    gate[:, :2] *= 30.0  # nearly every token chooses the two held experts
    variables["params"]["conv_0"]["feed_forward"]["gate"] = jnp.asarray(gate)
    with InferenceEngine(model, variables, max_batch_graphs=1, max_delay_ms=1.0,
                         bucket_ladder=[300], warmup=True) as eng:
        future = eng.submit(_requests([g])[0])
        reply = future.result(120)
        snap = eng.metrics.snapshot()
    cap = token_routed._capacity(300 * K, 2, 8)
    assert cap == 256 < 300 * K
    held = int(((future.routing >= 0) & (future.routing < 2)).sum())
    assert snap["moe_rows_held_total"] == held > cap
    assert snap["moe_fallback_layers_total"] == 1
    assert np.abs(reply[0] - _direct_logprobs(model, variables, g)).max() < 5e-5


def pytest_engine_refuses_what_is_no_document(setup, engine):
    model, graphs, batch, variables = setup
    g = graphs[0]
    with pytest.raises(ValueError, match="place"):
        engine.submit(GraphSample(x=g.x))  # no pos
    with pytest.raises(ValueError, match="outside 0..63"):
        engine.submit(GraphSample(x=g.x + 1.0, pos=g.pos))  # ids past the slice
    with pytest.raises(ValueError, match="in order"):
        engine.submit(GraphSample(x=g.x, pos=g.pos[::-1].copy()))  # not increasing
    stay = g.pos.copy()
    stay[2, 0] = stay[1, 0]
    with pytest.raises(ValueError, match="in order"):
        engine.submit(GraphSample(x=g.x, pos=stay))
    with pytest.raises(ValueError, match="no edges"):
        engine.submit(GraphSample(x=g.x, pos=g.pos, edge_index=g.edge_index))
    with pytest.raises(ValueError, match="feature width"):
        engine.submit(GraphSample(x=np.repeat(g.x, 2, axis=1), pos=g.pos))
    from hydragnn_tpu.serve import InferenceEngine

    with pytest.raises(ValueError, match="round"):
        InferenceEngine(model, variables, precision="bf16", tolerance=1.0, autostart=False)


def pytest_served_from_a_snapshot_through_from_config_and_check_config(tmp_path, monkeypatch):
    """``run_training`` writes the completed config and the checkpoint;
    ``check_config`` in serving mode passes it with a token ladder;
    ``InferenceEngine.from_config`` (what ``python -m hydragnn_tpu.serve``
    calls) serves it: a document's reply is the trained model's direct
    log-probabilities; a regression family's reply is unchanged (its whole
    head arrays: tests/test_serve_engine.py)."""
    import hydragnn_tpu
    from graftbench import datasets
    from hydragnn_tpu.analysis.contracts import check_config
    from hydragnn_tpu.serve import InferenceEngine
    from hydragnn_tpu.utils.config_utils import get_log_name_config

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    block, _ = datasets.materialize(
        {"generator": "token_chain", "graphs": 24, "tokens": 12, "vocab": V,
         "successors": 2}, 11, str(tmp_path / "cache"),
    )
    nn_block = copy.deepcopy(PUBLISHED)
    nn_block["Architecture"].update(
        {k: v for k, v in ARCH.items() if k != "token_minmax"}, hidden_dim=D,
        num_conv_layers=2,
    )
    nn_block["Variables_of_interest"]["num_classes"] = [V]
    nn_block["Training"].update(batch_size=4, num_epoch=1, learning_rate=0.01)
    config = {
        "Verbosity": {"level": 0}, "Dataset": block, "NeuralNetwork": nn_block,
        "Visualization": {"create_plots": 0},
    }
    hydragnn_tpu.run_training(config)
    snapshot = os.path.join("logs", get_log_name_config(config), "config.json")
    with open(snapshot) as f:
        completed = json.load(f)
    report = check_config(completed, mode="serving", bucket_ladder=[(32, 8)], strict=False)
    assert not report["errors"], report["errors"]
    lo, hi = completed["NeuralNetwork"]["Architecture"]["token_minmax"]
    with InferenceEngine.from_config(
        snapshot, max_batch_graphs=2, max_delay_ms=1.0, bucket_ladder=[32], warmup=True,
    ) as eng:
        assert eng.compiled_buckets == 1
        ids = np.arange(9) % int(hi - lo + 1) + int(lo)
        pos = np.zeros((9, 3), np.float32)
        pos[:, 0] = np.arange(9)
        x = ((ids[:, None] - lo) / (hi - lo)).astype(np.float32)
        future = eng.submit(GraphSample(x=x, pos=pos))
        reply = future.result(120)
        params, bstats, _ = eng._current_weights()
        batch = collate_graphs([GraphSample(x=x, pos=pos)], with_positions=True)
        out = jax.jit(eng.model.apply)({"params": params, "batch_stats": bstats}, batch)[0]
    logp = np.asarray(jax.nn.log_softmax(out[:9], axis=-1))
    assert reply[0].shape == (9, 1) and future.routing.shape == (9, 2 * K)
    assert np.abs(reply[0][:-1, 0] - logp[np.arange(8), ids[1:]]).max() < 2e-5
