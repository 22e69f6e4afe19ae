"""Mellum2-12B-A2.5B's block (``model_type: "MELLUM"``,
hydragnn_tpu/models/mellum.py) on the CPU at small widths with the published
PATTERN (d 32, 4 layers: three over the band of a window of 8 to one over the
whole triangle; 4 query heads on 2 key-value heads of 8; 8 experts, 2 a token,
ALL held, no shared expert; YaRN trained at 8 places; documents of 5, 13 and
30 tokens in ONE batch, so that documents lie on both sides of the window's
length and a band crosses a document boundary): the block by kind and the
whole stack against the plain reference of ``graftbench/families/mellum.py``
with the routing taken from the program and held to the margin; the all-held
routed layer, in one pass and in passes under a forced capacity, against the
dense sum over experts; YaRN at factor 16 by hand; the ids of a 98,304-row
vocabulary carried exactly; the grouped matmul's tiles for 2304 and 896; the
band's key blocks against the splash kernel's own mask; and the serving
engine: the reply against the reference's log-probabilities, the three
key-block counters against a hand count, the executable's scopes. Values and
counts, never a time."""

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
from graftbench.families import mellum as plain  # noqa: E402
from hydragnn_tpu.graphs import GraphSample, collate_graphs  # noqa: E402
from hydragnn_tpu.models import create_model, init_model_variables  # noqa: E402
from hydragnn_tpu.models import (  # noqa: E402
    mellum, token_attention, token_common, token_routed,
)
from hydragnn_tpu.models.base import HydraGNN  # noqa: E402
from hydragnn_tpu.models.layers import scaled_ids  # noqa: E402
from hydragnn_tpu.telemetry import scopes  # noqa: E402
from tests import test_lfm2 as sibling  # noqa: E402
from tests.test_lfm2 import (  # noqa: E402, F401
    _collate, _sequences, apply_routed, programs,
)

V, D, LAYERS, WINDOW = sibling.V, 32, 4, 8  # the sibling's sequences: ids under its V
CONFIG = os.path.join(REPO, "graftbench", "configs", "mellum2_12b_l4.json")
with open(CONFIG) as _f:
    PUBLISHED = json.load(_f)["NeuralNetwork"]
ROPE = copy.deepcopy(PUBLISHED["Architecture"]["rope_parameters"])
ROPE["full_attention"]["original_max_position_embeddings"] = 8
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
ARCH = dict(
    layer_types=KINDS, mlp_layer_types=["sparse"] * 4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, sliding_window=WINDOW, rope_parameters=ROPE,
    moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2, vocab_size=V,
    token_minmax=[0.0, V - 1.0],
)
HEADS = {"node": {"num_headlayers": 0, "dim_headlayers": [], "type": "mlp"}}
K = ARCH["num_experts_per_tok"]
LENGTHS = (5, 13, 30)  # under the window, over it, and over it after a boundary


def _model(layers=LAYERS, **arch):
    return create_model(
        "MELLUM", 1, D, (V,), ("node",), HEADS, [1.0], layers,
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


def _documents(graphs):
    start = 0
    for g in graphs:
        yield g, slice(start, start + g.num_nodes)
        start += g.num_nodes


@pytest.fixture(scope="module")
def setup():
    model = _model()
    graphs = _sequences(LENGTHS)
    batch = _collate(graphs)
    variables = shaken(init_model_variables(model, batch), 41)
    return model, graphs, batch, variables


def pytest_forward_against_the_plain_reference_routing_from_the_program(setup):
    model, graphs, batch, variables = setup
    got, routing, counters = _forward(model, variables, batch)
    assert got.shape == (64, V) and routing.shape == (64, LAYERS * K)
    held = 0
    for g, rows in _documents(graphs):
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
    # Every expert is held: every assignment of every real token is computed.
    assert float(counters["moe_rows_held"]) == held == sum(LENGTHS) * K * LAYERS
    assert float(counters["moe_layers_compact"]) == 0  # one pass over all K N rows, no loop


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def pytest_one_block_of_a_kind_against_the_reference(kind):
    """A stack of ONE layer of the kind: the block's rotary (plain on the
    band, YaRN past its trained places on the triangle), its graph and its
    routed feed-forward, on documents both sides of the window."""
    model = _model(layers=1, layer_types=[kind], mlp_layer_types=["sparse"])
    graphs = _sequences(LENGTHS, seed=3)
    batch = _collate(graphs)
    variables = shaken(init_model_variables(model, batch), 7)
    got, routing, _ = _forward(model, variables, batch)
    other = _model(
        layers=1, mlp_layer_types=["sparse"],
        layer_types=["full_attention" if kind == "sliding_attention" else "sliding_attention"],
    )
    swapped, _, _ = _forward(other, variables, batch)
    for g, rows in _documents(graphs):
        want, report = plain.logits(
            model, variables["params"], {"x": g.x, "pos": g.pos}, routing[rows]
        )
        assert np.abs(got[rows] - want).max() < 5e-5 * max(np.abs(want).max(), 1.0)
        assert report["route_margin"] < 1e-5
        # The other kind is another function (its rotary differs from the
        # first place on; the band only past the window).
        assert np.abs(got[rows] - swapped[rows]).max() > 1e-3


def pytest_a_wrong_choice_fails_the_margin_on_the_routers_logits(setup):
    model, graphs, batch, variables = setup
    _, routing, _ = _forward(model, variables, batch)
    g, rows = graphs[2], slice(18, 48)
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


@pytest.mark.parametrize("capacity", [None, 64, 16])
def pytest_the_all_held_layer_is_the_dense_sum_over_experts(capacity):
    """``RoutedFFN`` holding every expert, as the stack calls it (``C >= K N``:
    ONE pass over all K N rows, no loop compiled) and in passes under a forced
    ``capacity`` (96 rows through 64: two passes; through 16: six), against
    ``sum_e w_e SwiGLU_e(x)`` written out expert by expert in float64. The
    router: softmax over all experts, the K largest, renormalised."""
    rng = np.random.default_rng(5)
    n = 48
    cfg = mellum.MellumConfig.from_arch(ARCH, LAYERS)
    assert cfg.scoring_func == "softmax" and not cfg.use_expert_bias and cfg.norm_topk_prob
    assert (cfg.num_experts_held, cfg.experts_offset, cfg.routed_scaling_factor) == (8, 0, 1.0)
    x = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
    mask = jnp.ones((n,), bool)
    layer = token_routed.RoutedFFN(D, cfg)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x, mask)["params"]
    assert "expert_bias" not in params and params["w1"].shape == (8, D, 24)
    apply = jax.jit(
        lambda p: layer.apply({"params": p}, x, mask, capacity, mutable=[token_routed.INTERMEDIATES])
    )
    out, sown = apply(params)
    loop = "while" in apply.lower(params).as_text()
    assert loop == (capacity is not None)
    sown = sown[token_routed.INTERMEDIATES]
    chosen = np.asarray(sown["moe_chosen"][-1])
    assert float(sown["moe_rows_held"][-1]) == n * K
    p = np.asarray(jax.nn.softmax(np.asarray(x, np.float64) @ np.asarray(params["gate"], np.float64)))
    assert np.array_equal(np.sort(chosen), np.sort(np.argsort(-p, axis=1)[:, :K]))
    want = np.zeros((n, D))
    for i in range(n):
        w = p[i, chosen[i]] / (p[i, chosen[i]].sum() + 1e-6)
        for e, w_e in zip(chosen[i], w):
            a = np.asarray(x[i], np.float64) @ np.asarray(params["w1"][e], np.float64)
            b = np.asarray(x[i], np.float64) @ np.asarray(params["w3"][e], np.float64)
            want[i] += w_e * ((a / (1 + np.exp(-a))) * b) @ np.asarray(params["w2"][e], np.float64)
    assert np.abs(np.asarray(out) - want).max() < 1e-5 * np.abs(want).max()
    # The reference's routed layer, the sibling family's, says the same.
    report = dict(route_margin=0.0, loads=[], chosen=[])
    again = plain._routed(params, x, cfg, plain.Exact, chosen, report)
    assert np.abs(np.asarray(again) - want).max() < 1e-5 * np.abs(want).max()
    assert sum(report["loads"][0]) == n * K and report["route_margin"] < 1e-6


def pytest_yarn_at_factor_16_and_the_plain_band_by_hand():
    cfg = mellum.MellumConfig.from_arch(
        dict(PUBLISHED["Architecture"], token_minmax=[0.0, 98303.0]), 4
    )
    assert [cfg.sliding(i) for i in range(4)] == [True, True, True, False]
    assert cfg.rope(0).rope_type == "default" and cfg.rope(3).rope_type == "yarn"
    inv, factor, r = cfg.rope(3).frequencies(cfg.head_dim)
    assert r == 128 and factor == pytest.approx(0.1 * math.log(16) + 1) == pytest.approx(1.27726, abs=1e-5)
    # c(32) = 18.08, c(1) = 34.98: pairs 0-18 keep theta^(-2i/128), pairs from
    # 35 are divided by 16, a linear blend between.
    c = lambda b: 128 * math.log(8192 / (2 * math.pi * b)) / (2 * math.log(5e5))  # noqa: E731
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    for i in (0, 9, 18):
        assert inv[i] == pytest.approx(5e5 ** (-2 * i / 128), rel=1e-6)
    for i in (35, 63):
        assert inv[i] == pytest.approx(5e5 ** (-2 * i / 128) / 16, rel=1e-6)
    g = 1 - (26 - 18) / (35 - 18)
    assert inv[26] == pytest.approx(5e5 ** (-52 / 128) * ((1 - g) / 16 + g), rel=1e-6)
    plain_inv, plain_factor, r = cfg.rope(0).frequencies(cfg.head_dim)
    assert r == 128 and plain_factor == 1.0
    assert np.allclose(plain_inv, 5e5 ** (-2 * np.arange(64) / 128), rtol=1e-6)
    # The reference's own frequencies (written out in families/laguna.py).
    for layer in (0, 3):
        ref_inv, ref_factor, _ = plain.frequencies(cfg.rope(layer), 128)
        mine = cfg.rope(layer).frequencies(128)
        assert np.allclose(np.asarray(ref_inv), mine[0], rtol=1e-6) and ref_factor == mine[1]
    # The rotation itself at a place past the trained context, one pair.
    x = np.zeros((1, 1, 128), np.float32)
    x[0, 0, 40], x[0, 0, 104] = 1.0, 2.0  # pair 40 = (40, 40 + 64)
    turned = np.asarray(token_common.rotary(jnp.asarray(x), jnp.asarray([9000.0]), cfg.rope(3)))[0, 0]
    angle = 9000.0 * float(inv[40])
    assert turned[40] == pytest.approx(factor * (math.cos(angle) - 2 * math.sin(angle)), abs=2e-4)
    assert turned[104] == pytest.approx(factor * (2 * math.cos(angle) + math.sin(angle)), abs=2e-4)


def pytest_scaled_ids_carry_a_98304_row_vocabulary_exactly():
    """The float32 node column holds ``id / 98303``; un-scaled and rounded it
    is the id again, at both ends and all the way between."""
    count = 98304
    ids = np.arange(count)
    column = (ids / (count - 1.0)).astype(np.float32)
    got = np.asarray(scaled_ids(jnp.asarray(column), (0.0, count - 1.0), count))
    assert got.dtype == np.int32 and np.array_equal(got, ids)
    assert list(got[[0, 1, 98302, 98303]]) == [0, 1, 98302, 98303]
    # What un-scaling alone is off by, at the worst id: far from the 0.5
    # where rounding would pick a neighbour.
    assert np.abs(column.astype(np.float32) * np.float32(count - 1.0) - ids).max() < 0.01


def pytest_grouped_matmul_tiles_fit_2304_and_896():
    """Mellum2's expert is ``3 x 2304 x 896``: neither width is a whole
    number of ``GMM_TILING``'s 1024. A last tile under half full is spread
    over the whole tiles before it (2304 = 2 x 1152); a matrix narrower than
    the tile takes its width (896); the siblings' tiles stay what they were
    (LFM2's 1792 keeps 1024: its last tile is three quarters full)."""
    assert token_routed._gmm_tiles(126976, 2304, 896) == (256, 1152, 896)  # w1, w3
    assert token_routed._gmm_tiles(126976, 896, 2304) == (256, 896, 1152)  # w2
    assert token_routed._gmm_tiles(6400, 2048, 1792) == token_routed.GMM_TILING == (256, 1024, 1024)
    assert token_routed._gmm_tiles(6400, 1792, 2048) == token_routed.GMM_TILING
    assert token_routed._gmm_tiles(6400, 2048, 512) == (256, 1024, 512)
    assert token_routed._gmm_tiles(6400, 512, 2048) == (256, 512, 1024)
    assert token_routed._gmm_tiles(18944, 4096, 2048) == token_routed._gmm_tiles(18944, 2048, 4096) == token_routed.GMM_TILING
    for width in (2304, 896, 1792, 512, 2048, 4096, 24, 100, 1100, 2560, 3328):
        tile = token_routed._gmm_tile(1024, width)
        assert tile <= min(width, 1280) and (tile % 128 == 0 or tile == width)
        assert tile >= min(width, 1024)
    # Every rung of the cell's ladder is whole row tiles of K N = 8 N rows.
    assert all(8 * n % token_routed.GMM_TILING[0] == 0 for n in (12288, 15872, 19968, 25088))


@pytest.mark.parametrize("rows,window,block", [
    (40, 8, 8), (37, 8, 8), (64, 17, 8), (64, 16, 8), (48, 3, 16), (25088, 1024, 512),
])
def pytest_band_key_blocks_are_the_splash_masks_blocks(rows, window, block, monkeypatch):
    """``band_key_blocks`` against the occupancy of the mask the band's kernel
    is built from (``LocalMask`` of ``_band_reach``), block by block; at the
    published window a query block of 512 reaches into 3 key blocks."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as masks

    monkeypatch.setattr(token_attention, "ATTN_BLOCK", block)
    padded = -(-rows // block) * block
    if padded <= 512:
        band = masks.LocalMask((padded, padded), token_attention._band_reach(window), 0)
        blocks = padded // block
        occupied = sum(
            bool(band[i * block : (i + 1) * block, j * block : (j + 1) * block].any())
            for i in range(blocks) for j in range(blocks)
        )
        assert token_attention.band_key_blocks(rows, window) == occupied
    else:
        assert token_attention.band_key_blocks(rows, window) == 1 + 2 + 3 * (padded // block - 2) == 144
    assert token_attention._band_reach(window) == (window - 1, 0)


def pytest_no_mixing_across_a_boundary_and_none_from_beyond_the_window(setup):
    """Two documents in one batch: a token of the second document moves no
    row of the first nor of the third; inside the second (13 tokens, window
    8) the three band layers carry a change 7 places a layer, so through the
    whole stack every later row of the document moves (the full layer sees
    all); in a stack of band layers ALONE a change at place 0 reaches place
    3 x 7 = 21 of the third document and no further."""
    model, graphs, batch, variables = setup
    got, _, _ = _forward(model, variables, batch)
    x = np.array(batch.node_features)
    x[7, 0] = (np.round(x[7, 0] * (V - 1.0)) + 1) % V / (V - 1.0)  # document 2, place 2
    moved, _, _ = _forward(model, variables, batch.replace(node_features=jnp.asarray(x)))
    assert np.array_equal(moved[:5], got[:5]) and np.array_equal(moved[18:48], got[18:48])
    assert np.array_equal(moved[5:7], got[5:7])  # earlier places of its own document
    assert all(np.abs(moved[i] - got[i]).max() > 0 for i in range(7, 18))
    bands = _model(layers=3)
    a, _, _ = _forward(bands, variables, batch)
    x = np.array(batch.node_features)
    x[18, 0] = (np.round(x[18, 0] * (V - 1.0)) + 1) % V / (V - 1.0)  # document 3, place 0
    b, _, _ = _forward(bands, variables, batch.replace(node_features=jnp.asarray(x)))
    assert np.abs(b[18 + 21] - a[18 + 21]).max() > 0
    assert np.array_equal(b[18 + 22 : 48], a[18 + 22 : 48]) and np.array_equal(b[:18], a[:18])
    # Padding: the same documents under a larger bucket give the same rows.
    wide = _collate(graphs, num_nodes_pad=128)
    again, _, _ = _forward(model, variables, wide)
    assert np.abs(again[:48] - got[:48]).max() < 1e-5


def pytest_entry_points_refuse_what_the_family_cannot_run():
    make = lambda **kw: create_model(  # noqa: E731
        "MELLUM", 1, D, (V,), ("node",), HEADS, [1.0], LAYERS, **kw
    )
    with pytest.raises(ValueError, match="compute_dtype"):
        make(token_arch=ARCH, compute_dtype="bfloat16")
    with pytest.raises(ValueError, match="token_minmax"):
        make(token_arch={k: v for k, v in ARCH.items() if k != "token_minmax"})
    with pytest.raises(ValueError, match="sliding_window"):
        make(token_arch={k: v for k, v in ARCH.items() if k != "sliding_window"})
    with pytest.raises(ValueError, match="stack's sizes"):
        make()
    with pytest.raises(ValueError, match="not among"):
        _model(num_experts_held=4, experts_offset=6)
    with pytest.raises(ValueError, match="dense layer is not built"):
        _model(mlp_layer_types=["dense", "sparse", "sparse", "sparse"])
    with pytest.raises(ValueError, match="layer_types"):
        _model(layer_types=["conv"] * 4)
    with pytest.raises(ValueError, match="evenly"):
        _model(num_attention_heads=5)
    with pytest.raises(ValueError, match="positions"):
        _model().init(jax.random.PRNGKey(0), collate_graphs(_sequences((5,)), ("node",), (1,)), train=False)
    # A rank's share is what the siblings' is: told which experts it holds.
    share = _model(num_experts_held=2, experts_offset=4)
    params = jax.eval_shape(lambda: init_model_variables(share, _collate(_sequences((5,)))))["params"]
    assert params["conv_0"]["feed_forward"]["w1"].shape == (2, D, 24)
    assert set(params["conv_0"]) == {"input_layernorm", "self_attn", "post_attention_layernorm", "feed_forward"}
    assert set(params["conv_0"]["self_attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
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
        e["code"] == "bad-arch" and "MELLUM" in e["message"] and "sliding_window" in e["message"]
        for e in report["errors"]
    ), report["errors"]


def pytest_published_parameter_count():
    """The configuration's ``parameters`` arithmetic against the tree the
    initializer would make (shapes alone): every expert, the whole vocabulary."""
    from graftbench.drivers import serve_tokens

    with open(CONFIG) as f:
        config = json.load(f)
    model, template, _ = serve_tokens.init_model(serve_tokens.completed_arch(config))
    assert model.conv_type == "MELLUM" and model.num_conv_layers == 4
    sizes = {
        k: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(v))
        for k, v in template["params"].items()
    }
    attn = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    layer = attn + 2304 * 64 + 64 * 3 * 2304 * 896 + 2 * 2304
    assert round(attn / 1e6, 2) == 21.23 and sizes["conv_0"] == layer
    assert round(layer / 1e6, 1) == 417.7
    assert template["params"]["conv_3"]["feed_forward"]["w2"].shape == (64, 896, 2304)
    assert sizes["conv_embed"] == 98304 * 2304 and sizes["head_0"] == 98304 * 2305
    total = sum(sizes.values())
    assert round(total * 4 / 1e9, 2) == 8.50 and "8.50 GB" in config["parameters"]


def pytest_run_training_trains_the_family_through_the_loaders(tmp_path, monkeypatch):
    """``run_training`` on a ``model_type: "MELLUM"`` config: the benchmark's
    generator and configuration file at small sizes, the loaders' split,
    config completion (the two token tables), ``TrainingDriver``'s scanned
    epoch: the loss starts at ln(vocab), every value finite, the routing
    counters published."""
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
        num_conv_layers=LAYERS, num_experts_held=8,
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
    assert arch["output_dim"] == [V] and arch["head_loss"] == ["cross_entropy"]
    assert arch["token_minmax"] == [0.0, V - 1.0]
    assert telemetry.gauges_snapshot()["train/moe_rows_held_per_epoch"] > 0


# ----------------------------------------------------------------- the engine
def _requests(graphs):
    return [GraphSample(x=g.x, pos=g.pos) for g in graphs]


@pytest.fixture(scope="module")
def engine(setup):
    from hydragnn_tpu.serve import InferenceEngine

    model, graphs, batch, variables = setup
    eng = InferenceEngine(
        model, variables, max_batch_graphs=3, max_delay_ms=300.0, queue_limit=8,
        bucket_ladder=[32, 64], warmup=True, autostart=True,
    )
    yield eng
    eng.close()


def pytest_engine_reply_is_the_references_log_probabilities(setup, engine):
    """The whole stack through ``InferenceEngine`` as the cell runs it
    (``create_model`` -> engine -> ``warmup`` -> ``submit``), three documents
    co-batched in one rung so that a band crosses two boundaries, against the
    family's ``logprobs`` routed by each reply's own ``routing``."""
    from hydragnn_tpu.analysis.sentinel import compile_count

    model, graphs, batch, variables = setup
    assert engine._current_ladder() == [(32, 8), (64, 8)] and engine.compiled_buckets == 2
    assert engine._band_window == WINDOW
    before = compile_count()
    futures = [engine.submit(r) for r in _requests(graphs)]
    replies = [f.result(60) for f in futures]
    alone = engine.submit(_requests(graphs)[2])
    alone_reply = alone.result(60)
    assert compile_count() == before
    for g, reply, future in zip(graphs, replies, futures):
        assert len(reply) == 1 and reply[0].shape == (g.num_nodes, 1)
        assert reply[0][-1, 0] == 0.0  # a document's last token has no next
        assert future.routing.shape == (g.num_nodes, LAYERS * K)
        assert future.routing.dtype == np.int32
        want, report = plain.logprobs(
            model, variables["params"], {"x": g.x, "pos": g.pos}, future.routing
        )
        worst, rel, fail = plain.compare(reply[0], want)
        assert fail is None and worst < 5e-5 and rel < 1e-5 and report["route_margin"] < 1e-5
        assert report["rows_held"] == g.num_nodes * K * LAYERS
    # Alone, a document is routed and scored as it was co-batched.
    assert np.abs(alone_reply[0] - replies[2][0]).max() < 2e-5
    assert np.array_equal(alone.routing, futures[2].routing)
    snap = engine.metrics.snapshot()
    assert snap["moe_rows_held_total"] == (48 + 30) * K * LAYERS
    assert snap["moe_fallback_layers_total"] == 0


def pytest_engine_counts_the_key_blocks_of_both_kinds(setup, engine, monkeypatch):
    """A flush of 5 + 13 + 30 tokens in the 64-token rung, in blocks of 8
    rows here: ``attn_window_key_blocks_total`` is the band's blocks at the
    rung (window 8: one key block for the first query block, two for each of
    the other 7), whatever the documents; the two counters of a full layer
    are what they were (an engine on a CPU walks the triangle). A stack whose
    every layer is full counts no window block."""
    from hydragnn_tpu import telemetry
    from hydragnn_tpu.serve import InferenceEngine

    model, graphs, batch, variables = setup
    monkeypatch.setattr(token_attention, "ATTN_BLOCK", 8)
    names = ("attn_key_blocks_visited_total", "attn_key_blocks_causal_total",
             "attn_window_key_blocks_total")

    def flush(eng, requests):
        before = eng.metrics.read_counters(*names)
        for future in [eng.submit(r) for r in requests]:
            future.result(60)
        after = eng.metrics.read_counters(*names)
        return tuple(after[n] - before[n] for n in names)

    telemetry.configure(collect=True)
    try:
        assert flush(engine, _requests(graphs)) == (36, 36, 1 + 2 * 7)
        gauges = telemetry.gauges_snapshot()
    finally:
        telemetry.configure(collect=False)
    assert gauges["serve/attn_window_key_blocks"] == 15
    assert gauges["serve/attn_key_blocks_causal"] == 36
    # One short document alone lands in the 32-token rung: 4 query blocks.
    assert flush(engine, _requests(graphs)[:1]) == (10, 10, 1 + 2 * 3)
    text = engine.metrics.render_prometheus()
    assert all(f"hydragnn_serve_{name} " in text for name in names)
    assert set(names) <= set(engine.metrics.snapshot())
    full = _model(layers=1, layer_types=["full_attention"], mlp_layer_types=["sparse"])
    with InferenceEngine(full, shaken(init_model_variables(full, batch), 3), max_batch_graphs=1,
                         max_delay_ms=1.0, bucket_ladder=[32], warmup=True) as eng:
        assert eng._band_window is None
        assert flush(eng, _requests(graphs)[:1]) == (10, 10, 0)


def pytest_scopes_of_the_engines_executable(setup, engine):
    """The first served program with ``hydragnn.attn.window`` in it: the
    engine's own executable carries both kinds of core, the router, the
    experts and the reply, each under its module, and no ``hydragnn.`` name
    outside the table."""
    import re

    model, graphs, batch, variables = setup
    names = {scopes.ATTN_WINDOW, scopes.ATTN_FULL, scopes.MOE_ROUTE, scopes.MOE_EXPERTS,
             scopes.HEAD_LOGPROB}
    assert names <= scopes.VOCABULARY and scopes.VERSION == 1
    text = engine._jit.lower(
        variables["params"], variables.get("batch_stats", {}), engine._dummy_batch(64, 8)
    ).as_text(debug_info=True)
    for name in names:
        assert name in text, name
    for layer in range(3):
        assert f"conv_{layer}/self_attn/{scopes.ATTN_WINDOW}" in text
        assert f"conv_{layer}/self_attn/{scopes.ATTN_FULL}" not in text
    assert f"conv_3/self_attn/{scopes.ATTN_FULL}" in text
    assert f"conv_3/self_attn/{scopes.ATTN_WINDOW}" not in text
    assert f"{scopes.ATTN_FULL}/o_proj" not in text and "self_attn/o_proj" in text
    assert f"feed_forward/{scopes.MOE_ROUTE}" in text  # the experts' scope nests in it
    used = set(re.findall(r"hydragnn\.[a-z_0-9]+(?:\.[a-z_0-9]+)*", text))
    assert used <= scopes.VOCABULARY, used - scopes.VOCABULARY
    assert scopes.ATTN_LATENT not in used and scopes.MOE_SHARED not in used
    plain_forward = jax.jit(lambda p: model.apply({"params": p}, batch)).lower(
        variables["params"]
    ).as_text(debug_info=True)
    assert scopes.HEAD_LOGPROB not in plain_forward  # run_prediction returns logits
    assert isinstance(model, HydraGNN)
