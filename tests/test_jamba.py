"""AI21-Jamba2-3B's block (``model_type: "JAMBA"``,
hydragnn_tpu/models/jamba.py) on the CPU at small widths with the published
PATTERN (d 32, 4 layers: three Mamba mixers to one attention layer at layer
2; d_inner 64 x 4 states, a convolution of 4 taps, dt rank 6; 4 query heads
on ONE key-value head of 8; documents of 5, 13, 30 and 70 tokens in ONE batch,
the longest past a chunk of the scan's ``jax.numpy`` route): the block by
kind and the whole stack against the plain reference of
``graftbench/families/jamba.py``; a packed flush against each document alone,
and a document bit-equal whatever stands before it (no state, no convolution
tap and no attention pair crosses a boundary); the tied head; the attention
kernel at 20 query heads on one key-value head; the ids of a 65,536-row
vocabulary carried exactly; the scan's parameters at Mamba's starting point;
and the serving engine: the reply against the reference, ``routing`` None,
the two scan counters, the executable's scopes. Values and counts, never a
time."""

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
from graftbench.families import jamba as plain  # noqa: E402
from hydragnn_tpu.graphs import GraphSample  # noqa: E402
from hydragnn_tpu.models import create_model, init_model_variables  # noqa: E402
from hydragnn_tpu.models import jamba, token_attention  # noqa: E402
from hydragnn_tpu.models.base import HydraGNN  # noqa: E402
from hydragnn_tpu.models.layers import scaled_ids  # noqa: E402
from hydragnn_tpu.ops import block_attention, selective_scan  # noqa: E402
from hydragnn_tpu.telemetry import scopes  # noqa: E402
from tests import test_lfm2 as sibling  # noqa: E402
from tests.test_lfm2 import (  # noqa: E402, F401
    _collate, _sequences, compiled, programs,
)

V, D, LAYERS = sibling.V, 32, 4  # the sibling's sequences: ids under its V
CONFIG = os.path.join(REPO, "graftbench", "configs", "jamba2_3b.json")
with open(CONFIG) as _f:
    PUBLISHED = json.load(_f)["NeuralNetwork"]
ARCH = dict(
    attn_layer_period=4, attn_layer_offset=2, intermediate_size=48, num_attention_heads=4,
    num_key_value_heads=1, mamba_d_state=4, mamba_d_conv=4, mamba_dt_rank=6, mamba_expand=2,
    vocab_size=V, token_minmax=[0.0, V - 1.0],
)
HEADS = {"node": {"num_headlayers": 0, "dim_headlayers": [], "type": "mlp"}}
LENGTHS = (5, 13, 30, 70)


def _model(layers=LAYERS, **arch):
    return create_model(
        "JAMBA", 1, D, (V,), ("node",), HEADS, [1.0], layers,
        token_arch=dict(ARCH, **arch), head_loss=("cross_entropy",),
        class_minmax=([0.0, V - 1.0],),
    )


def _logits(model, variables, batch, purpose="logits"):
    return np.asarray(compiled(model, purpose, lambda params, batch: model.apply(
        {"params": params}, batch, train=False
    ))(variables["params"], batch)[0])


def _scores(model, variables, batch):
    return np.asarray(compiled(model, "scores", lambda params, batch: model.apply(
        {"params": params}, batch, method=HydraGNN.score_tokens
    ))(variables["params"], batch)[0])


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
    variables = shaken(init_model_variables(model, batch), 45)
    return model, graphs, batch, variables


def pytest_forward_against_the_plain_reference(setup):
    model, graphs, batch, variables = setup
    got = _logits(model, variables, batch)
    scored = _scores(model, variables, batch)
    assert got.shape == (128, V) and scored.shape == (128, 1)
    for g, rows in _documents(graphs):
        want, report = plain.logits(model, variables["params"], {"x": g.x, "pos": g.pos})
        assert np.abs(got[rows] - want).max() < 5e-5 * max(np.abs(want).max(), 1.0)
        assert report["route_margin"] == 0.0 and report["rows_held"] == 0
        logp, _ = plain.logprobs(model, variables["params"], {"x": g.x, "pos": g.pos})
        assert np.abs(scored[rows] - logp).max() < 5e-5 and scored[rows][-1, 0] == 0.0
    assert not scored[118:].any()  # the padding rows


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def pytest_one_block_of_a_kind_against_the_reference(kind):
    """A stack of ONE layer of the kind (the offset says which), and the same
    weights' shapes tell the kinds apart."""
    model = _model(layers=1, attn_layer_offset=1 if kind == "mamba" else 0)
    graphs = _sequences(LENGTHS, seed=3)
    batch = _collate(graphs)
    variables = shaken(init_model_variables(model, batch), 7)
    block = variables["params"]["conv_0"]
    assert ("mamba" in block) == (kind == "mamba") and ("self_attn" in block) == (kind != "mamba")
    got = _logits(model, variables, batch)
    for g, rows in _documents(graphs):
        want, _ = plain.logits(model, variables["params"], {"x": g.x, "pos": g.pos})
        assert np.abs(got[rows] - want).max() < 5e-5 * max(np.abs(want).max(), 1.0)


def pytest_layer_order_is_the_librarys_rule():
    cfg = jamba.JambaConfig.from_arch(PUBLISHED["Architecture"] | {"token_minmax": [0, 65535]}, 28)
    kinds = [cfg.scans(i) for i in range(28)]
    assert [i for i, s in enumerate(kinds) if not s] == [7, 21] and sum(kinds) == 26
    assert not any(cfg.routed(i) for i in range(28)) and cfg.norm_eps == 1e-6
    assert [plain.scans(cfg, i) for i in range(28)] == kinds
    model = _model()
    assert model.counts_routing is False and model.tied_head is True
    params = jax.eval_shape(
        lambda: init_model_variables(model, _collate(_sequences((5,))))
    )["params"]
    assert [("mamba" in params[f"conv_{i}"]) for i in range(4)] == [True, True, False, True]
    assert set(params["conv_0"]) == {"input_layernorm", "mamba", "pre_ff_layernorm", "feed_forward"}
    assert set(params["conv_0"]["mamba"]) == {
        "in_proj", "conv_kernel", "conv_bias", "x_proj", "dt_layernorm", "b_layernorm",
        "c_layernorm", "dt_proj", "A_log", "D", "out_proj",
    }
    assert set(params["conv_2"]["self_attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert params["conv_2"]["self_attn"]["k_proj"]["kernel"].shape == (D, 8)  # ONE head of 8
    assert params["conv_0"]["mamba"]["in_proj"]["kernel"].shape == (D, 4 * D)
    assert params["conv_0"]["mamba"]["x_proj"]["kernel"].shape == (2 * D, 6 + 2 * 4)


def pytest_the_initializer_is_mambas_starting_point():
    """A freshly initialized mixer: ``A_log``, ``D`` and ``b_dt`` are zeros,
    which the model reads as ``A = -(1..S)`` a channel, ``D = 1`` and first
    step sizes log-spaced over [1e-3, 1e-1]."""
    model = _model()
    batch = _collate(_sequences((5,)))
    mixer = init_model_variables(model, batch)["params"]["conv_0"]["mamba"]
    assert not np.asarray(mixer["A_log"]).any() and not np.asarray(mixer["D"]).any()
    assert not np.asarray(mixer["dt_proj"]["bias"]).any()
    first = np.asarray(jax.nn.softplus(jamba._first_steps(64)))
    assert np.allclose(first[[0, -1]], [1e-3, 1e-1], rtol=1e-4)
    assert np.allclose(np.diff(np.log(first)), np.log(100.0) / 63, rtol=1e-3)
    assert np.allclose(np.asarray(plain._first_steps(64)), np.asarray(jamba._first_steps(64)),
                       rtol=1e-5)


def pytest_a_packed_flush_is_each_document_alone_and_nothing_crosses_a_boundary(setup):
    """The four documents packed in one batch against each scored alone; then
    document A (the first) altered in every token: B, C and D come out
    BIT-EQUAL, so no state of the scan, no tap of the convolution and no pair
    of the attention reached across a boundary; A's own rows moved."""
    model, graphs, batch, variables = setup
    packed = _scores(model, variables, batch)
    logits = _logits(model, variables, batch)
    for g, rows in _documents(graphs):
        alone = _scores(model, variables, _collate([g], num_nodes_pad=128))
        assert np.abs(alone[: g.num_nodes] - packed[rows]).max() < 2e-5
    other = list(graphs)
    moved = copy.deepcopy(graphs[0])
    moved.x = ((np.round(moved.x * (V - 1.0)) + 3) % V / (V - 1.0)).astype(np.float32)
    other[0] = moved
    again = _logits(model, variables, _collate(other))
    assert np.array_equal(again[5:], logits[5:])
    assert not np.array_equal(again[:5], logits[:5])
    # And with the order turned round, each document's logits are the same
    # numbers at its new rows (to rounding: the chunks fall elsewhere).
    turned = _logits(model, variables, _collate(graphs[::-1]))
    start = 0
    for g, rows in reversed(list(_documents(graphs))):
        assert np.abs(turned[start : start + g.num_nodes] - logits[rows]).max() < 5e-5
        start += g.num_nodes


def pytest_a_dropped_reset_shows(setup, monkeypatch):
    """The scan told the whole batch is one run: every document but the first
    reads another answer (the benchmark's control rests on this)."""
    model, graphs, batch, variables = setup
    right = _logits(model, variables, batch)
    scan = jamba.selective_scan
    monkeypatch.setattr(
        jamba, "selective_scan",
        lambda u, dt, a, b, c, skip, node_graph: scan(
            u, dt, a, b, c, skip, jnp.zeros_like(node_graph)),
    )
    # Traced anew under the patch: the module's program was traced before it.
    wrong = _logits(model, variables, batch, purpose="logits with no reset")
    assert np.array_equal(wrong[:5], right[:5])
    for _, rows in list(_documents(graphs))[1:]:
        assert np.abs(wrong[rows] - right[rows]).max() > 1e-2


def pytest_the_tied_head_is_the_embeddings_table(setup):
    """``tie_word_embeddings``: the tree holds no head matrix, the logits are
    ``h E^T`` with ``h`` the stack's output, and an untied stack of the same
    sizes has a head of its own."""
    model, graphs, batch, variables = setup
    params = variables["params"]
    assert not [k for k in params if k.startswith("head")]
    assert set(params) == {f"conv_{i}" for i in range(LAYERS)} | {"conv_embed", "conv_norm"}
    h = np.asarray(jax.jit(
        lambda params: model.apply({"params": params}, batch, method=HydraGNN._encode_tokens)
    )(params))
    table = np.asarray(params["conv_embed"]["embedding"])
    assert table.shape == (V, D)
    assert np.allclose(_logits(model, variables, batch), h @ table.T, atol=1e-5)
    untied = _model(tie_word_embeddings=False)
    assert untied.tied_head is False
    shapes = jax.eval_shape(lambda: init_model_variables(untied, batch))["params"]
    assert shapes["head_0"]["mlp"]["dense_0"]["kernel"].shape == (D, V)
    # A tied head is ONE class head as wide as the vocabulary, of no hidden layer.
    with pytest.raises(ValueError, match="ties its one class head"):
        create_model(
            "JAMBA", 1, D, (V - 1,), ("node",), HEADS, [1.0], LAYERS, token_arch=ARCH,
            head_loss=("cross_entropy",), class_minmax=([0.0, V - 2.0],),
        ).init(jax.random.PRNGKey(0), batch, train=False)
    with pytest.raises(ValueError, match="ties its one class head"):
        create_model(
            "JAMBA", 1, D, (V,), ("node",),
            {"node": {"num_headlayers": 1, "dim_headlayers": [8], "type": "mlp"}}, [1.0], LAYERS,
            token_arch=ARCH, head_loss=("cross_entropy",), class_minmax=([0.0, V - 1.0],),
        ).init(jax.random.PRNGKey(0), batch, train=False)


def pytest_block_range_attention_at_twenty_heads_on_one():
    """The published head counts: 20 query heads share ONE key-value head, so
    a grid step takes 5 (8 does not divide 20) and they all read key-value
    head 0; the kernel (interpreted) against the dense mask over documents
    that start mid-block."""
    assert block_attention._heads_a_step(20, 1, block_attention.HEADS_A_STEP) == (5, 1)
    assert block_attention._heads_a_step(32, 4, 8) == (8, 1)  # the sibling cells' as they were
    assert block_attention._heads_a_step(32, 32, 8) == (8, 8)
    n, h, hd, block = 256, 20, 128, 128
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(n, h, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(n, 1, hd)), jnp.float32) for _ in range(2))
    ids = np.zeros(n, np.int32)
    ids[70:] = 1
    ids[200:] = 2
    got = np.asarray(block_attention.block_range_attention(
        q, k, v, jnp.asarray(ids), hd ** -0.5, block, interpret=True
    ))
    s = np.einsum("qhd,kd->hqk", np.asarray(q), np.asarray(k)[:, 0]) * hd ** -0.5
    keep = (ids[:, None] == ids[None, :]) & (np.arange(n)[None, :] <= np.arange(n)[:, None])
    s = np.where(keep[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hqk,kd->qhd", p / p.sum(-1, keepdims=True), np.asarray(v)[:, 0])
    assert np.abs(got - want).max() < 2e-5
    # The CPU's route of the entry point, the same call the model makes.
    here = np.asarray(
        token_attention.segment_causal_attention(q, k, v, jnp.asarray(ids))
    ).reshape(n, h, hd)
    assert np.abs(here - want).max() < 2e-5


def pytest_scaled_ids_carry_a_65536_row_vocabulary_exactly():
    count = 65536
    ids = np.arange(count)
    column = (ids / (count - 1.0)).astype(np.float32)
    got = np.asarray(scaled_ids(jnp.asarray(column), (0.0, count - 1.0), count))
    assert got.dtype == np.int32 and np.array_equal(got, ids)
    assert np.abs(column * np.float32(count - 1.0) - ids).max() < 0.01


def pytest_entry_points_refuse_what_the_family_cannot_run():
    make = lambda **kw: create_model(  # noqa: E731
        "JAMBA", 1, D, (V,), ("node",), HEADS, [1.0], LAYERS, **kw
    )
    with pytest.raises(ValueError, match="compute_dtype"):
        make(token_arch=ARCH, compute_dtype="bfloat16")
    with pytest.raises(ValueError, match="token_minmax"):
        make(token_arch={k: v for k, v in ARCH.items() if k != "token_minmax"})
    with pytest.raises(ValueError, match="mamba_dt_rank"):
        make(token_arch={k: v for k, v in ARCH.items() if k != "mamba_dt_rank"})
    with pytest.raises(ValueError, match="stack's sizes"):
        make()
    with pytest.raises(ValueError, match="num_experts 1"):
        _model(num_experts=16)
    with pytest.raises(ValueError, match="evenly"):
        _model(num_attention_heads=4, num_key_value_heads=3)
    from hydragnn_tpu.serve import InferenceEngine

    model = _model()
    variables = init_model_variables(model, _collate(_sequences((5,))))
    with pytest.raises(ValueError, match="float32 node"):
        InferenceEngine(model, variables, precision="bf16", autostart=False)


def pytest_published_parameter_count():
    """The configuration's ``parameters`` arithmetic against the tree the
    initializer would make (shapes alone): all 28 layers, the whole
    vocabulary, no head of its own."""
    from graftbench.drivers import serve_tokens

    with open(CONFIG) as f:
        config = json.load(f)
    model, template, _ = serve_tokens.init_model(serve_tokens.completed_arch(config))
    assert model.conv_type == "JAMBA" and model.num_conv_layers == 28 and model.tied_head
    sizes = {
        k: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(v))
        for k, v in template["params"].items()
    }
    counted = plain.parameters(config["NeuralNetwork"]["Architecture"])
    assert sizes["conv_0"] == counted["mamba_layer"] and round(sizes["conv_0"] / 1e6, 2) == 104.16
    assert sizes["conv_7"] == sizes["conv_21"] == counted["attention_layer"]
    assert round(sizes["conv_7"] / 1e6, 2) == 76.68
    assert sizes["conv_embed"] == 65536 * 2560 and "head_0" not in sizes
    assert template["params"]["conv_0"]["mamba"]["A_log"].shape == (5120, 16)
    assert template["params"]["conv_7"]["self_attn"]["k_proj"]["kernel"].shape == (2560, 128)
    total = sum(sizes.values())
    assert total == counted["whole"] and round(total / 1e6, 1) == 3029.3
    assert round(total * 4 / 1e9, 2) == 12.12 and "12.12 GB" in config["parameters"]


def pytest_run_training_trains_the_family_through_the_loaders(tmp_path, monkeypatch):
    """``run_training`` on a ``model_type: "JAMBA"`` config: the benchmark's
    generator and configuration file at small sizes, the loaders' split,
    config completion, ``TrainingDriver``'s scanned epoch with the scan under
    a gradient (the ``jax.numpy`` route): the loss starts at ln(vocab), falls,
    and every value is finite."""
    import hydragnn_tpu
    from graftbench import datasets

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
    nn_block["Training"].update(batch_size=4, num_epoch=2, learning_rate=0.01)
    config = {
        "Verbosity": {"level": 0}, "Dataset": block, "NeuralNetwork": nn_block,
        "Visualization": {"create_plots": 0},
    }
    history = hydragnn_tpu.run_training(config)
    losses = history["total_loss_train"]
    assert abs(losses[0] - np.log(V)) < 1.0 and np.isfinite(losses).all()
    assert losses[-1] < losses[0] and all(np.isfinite(history["total_loss_val"]))
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["output_dim"] == [V] and arch["head_loss"] == ["cross_entropy"]
    assert arch["token_minmax"] == [0.0, V - 1.0]


# ----------------------------------------------------------------- the engine
def _requests(graphs):
    return [GraphSample(x=g.x, pos=g.pos) for g in graphs]


@pytest.fixture(scope="module")
def engine(setup):
    from hydragnn_tpu.serve import InferenceEngine

    model, graphs, batch, variables = setup
    eng = InferenceEngine(
        model, variables, max_batch_graphs=4, max_delay_ms=300.0, queue_limit=8,
        bucket_ladder=[64, 128], warmup=True, autostart=True,
    )
    yield eng
    eng.close()


def pytest_engine_reply_is_the_references_and_nothing_is_routed(setup, engine):
    """The whole stack through ``InferenceEngine`` as the cell runs it
    (``create_model`` -> engine -> ``warmup`` -> ``submit``), four documents
    co-batched in one rung: the reply against the family's ``logprobs``,
    ``future.routing`` None (no routed layer: ``_count_routing`` never runs),
    and the two scan counters moved by the chunks at the rung and by 4."""
    from hydragnn_tpu import telemetry
    from hydragnn_tpu.analysis.sentinel import compile_count

    model, graphs, batch, variables = setup
    assert engine._current_ladder() == [(64, 8), (128, 8)] and engine.compiled_buckets == 2
    assert engine._scans is True and engine._band_window is None
    names = ("ssm_scan_chunks_total", "ssm_state_resets_total", "moe_rows_held_total")
    before, compiles = engine.metrics.read_counters(*names), compile_count()
    telemetry.configure(collect=True)
    try:
        futures = [engine.submit(r) for r in _requests(graphs)]
        replies = [f.result(60) for f in futures]
        gauges = telemetry.gauges_snapshot()
    finally:
        telemetry.configure(collect=False)
    assert compile_count() == compiles
    for g, reply, future in zip(graphs, replies, futures):
        assert len(reply) == 1 and reply[0].shape == (g.num_nodes, 1)
        assert reply[0][-1, 0] == 0.0 and future.routing is None
        want, report = plain.logprobs(model, variables["params"], {"x": g.x, "pos": g.pos}, None)
        worst, rel, fail = plain.compare(reply[0], want)
        assert fail is None and worst < 5e-5 and rel < 1e-5 and report["route_margin"] == 0.0
    after = engine.metrics.read_counters(*names)
    moved = tuple(after[n] - before[n] for n in names)
    # 118 tokens land in the rung of 128 rows: one chunk of the kernel's 256.
    assert moved == (selective_scan.scan_chunks(128), 4, 0) and moved[0] == 1
    assert gauges["serve/ssm_scan_chunks"] == 1 and gauges["serve/ssm_state_resets"] == 4
    alone = engine.submit(_requests(graphs)[1])
    assert np.abs(alone.result(60)[0] - replies[1][0]).max() < 2e-5
    assert engine.metrics.read_counters("ssm_state_resets_total")["ssm_state_resets_total"] \
        == after["ssm_state_resets_total"] + 1
    text = engine.metrics.render_prometheus()
    assert all(f"hydragnn_serve_{name} " in text for name in names)
    assert {"ssm_scan_chunks_total", "ssm_state_resets_total"} <= set(engine.metrics.snapshot())


def pytest_a_stack_with_no_scan_counts_none():
    from hydragnn_tpu.serve import InferenceEngine

    model = sibling._model()
    graphs = _sequences((5, 9))
    variables = init_model_variables(model, _collate(graphs))
    with InferenceEngine(model, variables, max_batch_graphs=2, max_delay_ms=1.0,
                         bucket_ladder=[32], warmup=True) as eng:
        assert eng._scans is False
        for f in [eng.submit(r) for r in _requests(graphs)]:
            f.result(60)
        snap = eng.metrics.snapshot()
        assert snap["ssm_scan_chunks_total"] == 0 and snap["ssm_state_resets_total"] == 0
        assert snap["attn_key_blocks_visited_total"] > 0


def pytest_scopes_of_the_engines_executable(setup, engine):
    """The first served program with a state-space layer: the engine's own
    executable carries the convolution, the dt chain and the scan under their
    names in the three Mamba layers and the causal core in the attention
    layer, the reply, and no ``hydragnn.`` name outside the table; the
    projections stay with their modules."""
    import re

    model, graphs, batch, variables = setup
    names = {scopes.SSM_CONV, scopes.SSM_DT, scopes.SSM_SCAN, scopes.ATTN_FULL, scopes.HEAD_LOGPROB}
    assert names <= scopes.VOCABULARY and scopes.VERSION == 1
    text = engine._jit.lower(
        variables["params"], variables.get("batch_stats", {}), engine._dummy_batch(64, 8)
    ).as_text(debug_info=True)
    for layer in (0, 1, 3):
        for name in (scopes.SSM_CONV, scopes.SSM_DT, scopes.SSM_SCAN):
            assert f"conv_{layer}/mamba/{name}" in text, (layer, name)
        assert f"conv_{layer}/self_attn" not in text
    assert f"conv_2/self_attn/{scopes.ATTN_FULL}" in text and "conv_2/mamba" not in text
    assert f"{scopes.SSM_DT}/dt_proj" in text  # W_dt is the chain's
    for module in ("in_proj", "x_proj", "out_proj"):
        assert f"mamba/{module}" in text and not re.search(rf"hydragnn\.ssm\.[a-z]+/{module}", text)
    used = set(re.findall(r"hydragnn\.[a-z_0-9]+(?:\.[a-z_0-9]+)*", text))
    assert used <= scopes.VOCABULARY, used - scopes.VOCABULARY
    assert not used & {scopes.MOE_ROUTE, scopes.MOE_EXPERTS, scopes.ATTN_WINDOW, scopes.LFM2_CONV}
