"""LFM2's block (``model_type: "LFM2"``, hydragnn_tpu/models/lfm2.py) on the
CPU at small widths (d 32, 4 layers: conv, conv, attention, conv; 8 experts,
2 a token, this rank holding 4 of them from expert 2): the program against the
plain reference of ``graftbench/families/lfm2.py`` for forward, loss and
gradients with the routing taken from the program and held to the margins;
the four shares of a routed layer adding up to the uncut layer; no mixing
across a graph boundary in a packed, padded batch of unequal lengths; padding
nodes routed nowhere and every gradient finite; token ids exact over the
whole slice; the scopes and counters; the family through ``run_training``.
Values and counts, never a time."""

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
from graftbench.families import lfm2 as plain  # noqa: E402
from hydragnn_tpu.graphs import GraphSample, collate_graphs  # noqa: E402
from hydragnn_tpu.models import create_model, init_model_variables  # noqa: E402
from hydragnn_tpu.models import lfm2  # noqa: E402
from hydragnn_tpu.models.loss import class_ids, multihead_rmse_loss  # noqa: E402
from hydragnn_tpu.telemetry import scopes  # noqa: E402

V, D = 64, 32
ARCH = dict(
    layer_types=["conv", "conv", "full_attention", "conv"], num_dense_layers=1,
    intermediate_size=48, moe_intermediate_size=24, num_experts=8,
    num_experts_per_tok=2, num_experts_held=4, experts_offset=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, vocab_size=V,
    token_minmax=[0.0, V - 1.0],
)
HEADS = {"node": {"num_headlayers": 0, "dim_headlayers": [], "type": "mlp"}}
ROUTED = ("conv_1", "conv_2", "conv_3")


def _sequences(sizes, seed=0):
    """Token sequences as the loaders hand them over: the scaled id column,
    the scaled next id as the node target, positions (i, 0, 0), the band."""
    rng = np.random.default_rng(seed)
    out = []
    for n in sizes:
        ids = rng.integers(0, V, n + 1)
        pos = np.zeros((n, 3), np.float32)
        pos[:, 0] = np.arange(n)
        i = np.arange(n - 1)
        ei = np.concatenate([np.stack([i, i + 1]), np.stack([i + 1, i])], 1)
        out.append(GraphSample(
            x=(ids[:-1, None] / (V - 1.0)).astype(np.float32), pos=pos,
            y=(ids[1:] / (V - 1.0)).astype(np.float32),
            y_loc=np.array([[0, n]], np.int64), edge_index=ei.astype(np.int32),
        ))
    return out


def _model(**arch):
    return create_model(
        "LFM2", 1, D, (V,), ("node",), HEADS, [1.0], 4, lfm2=dict(ARCH, **arch),
        head_loss=("cross_entropy",), class_minmax=([0.0, V - 1.0],),
    )


def _collate(graphs, **pads):
    return collate_graphs(graphs, ("node",), (1,), with_positions=True, **pads)


def _forward(model, variables, batch):
    out, sown = model.apply(
        {"params": variables["params"]}, batch, train=False,
        mutable=[lfm2.INTERMEDIATES],
    )
    routing, counters = lfm2.split_intermediates(sown[lfm2.INTERMEDIATES])
    return np.asarray(out[0]), jax.tree_util.tree_map(np.asarray, routing), counters


def _rows(routing, rows):
    return {k: {kk: vv[rows] for kk, vv in v.items()} for k, v in routing.items()}


@pytest.fixture(scope="module")
def setup():
    model = _model()
    graphs = _sequences((5, 9, 12))
    batch = _collate(graphs)
    variables = shaken(init_model_variables(model, batch), 31)
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
        # The program's choice IS a top-K of the reference's own scores here
        # (float32 both), and of the scores of its own router input.
        assert report["route_margin"] < 1e-6 and report["router_margin"] < 1e-6
        held += report["rows_held"]
    # The counter: rows routed to held experts, padding nodes routed nowhere.
    assert float(counters["moe_rows_held"]) == held > 0
    # Summed over the three routed layers: min <= mean (of 4 held) <= max.
    assert float(counters["moe_load_min"]) <= held / 4 <= float(counters["moe_load_max"])


def pytest_a_wrong_choice_fails_the_margin(setup):
    model, graphs, batch, variables = setup
    _, routing, _ = _forward(model, variables, batch)
    g, rows = graphs[0], slice(0, graphs[0].num_nodes)
    flipped = _rows(routing, rows)
    scores = jax.nn.sigmoid(
        flipped["conv_2"]["router_in"] @ variables["params"]["conv_2"]["feed_forward"]["gate"]
    ) + variables["params"]["conv_2"]["feed_forward"]["expert_bias"]
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


def pytest_loss_and_gradients_against_the_plain_reference(setup):
    model, graphs, batch, variables = setup
    _, routing, _ = _forward(model, variables, batch)
    params = jax.tree_util.tree_map(jnp.asarray, variables["params"])

    def program(p):
        out = model.apply({"params": p}, batch, train=False)
        return multihead_rmse_loss(
            out, batch, model.output_type, model.task_weights,
            head_loss=model.head_loss, class_minmax=model.class_minmax,
        )[0]

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

    with jax.default_matmul_precision("highest"):
        (got, g_got), (want, g_want) = (
            jax.value_and_grad(f)(params) for f in (program, reference)
        )
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
    # The expert bias is a buffer: it steers the choice and takes no gradient.
    assert not np.asarray(g_got["conv_2"]["feed_forward"]["expert_bias"]).any()
    assert np.asarray(g_got["conv_2"]["feed_forward"]["gate"]).any()


def pytest_four_shares_add_up_to_the_uncut_layer():
    """The share test: a routed layer cut over 4 ranks (2 of 8 experts each,
    the router 8 wide on every rank) -- what the four PROGRAM shares compute
    adds up to what the plain reference gives for the uncut 8-expert layer."""
    rng = np.random.default_rng(4)
    n, k, experts, f = 40, 2, 8, 24
    whole = lfm2.LFM2Config.from_arch(
        dict(ARCH, num_experts_held=experts, experts_offset=0), 4
    )
    x = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
    mask = jnp.ones((n,), bool)
    full = lfm2.RoutedFFN(D, whole).init(jax.random.PRNGKey(0), x, mask)["params"]
    full = dict(full, expert_bias=jnp.asarray(rng.normal(0, 0.05, experts), jnp.float32))
    report = dict(route_margin=0.0, router_margin=0.0, rows_held=0)
    want = plain._routed(full, x, whole, plain.Exact, None, report)
    assert int(report["rows_held"]) == n * k  # the uncut layer holds every expert
    total, seen = jnp.zeros_like(want), 0
    for rank in range(4):
        share = lfm2.LFM2Config.from_arch(
            dict(ARCH, num_experts_held=2, experts_offset=2 * rank), 4
        )
        held = slice(2 * rank, 2 * rank + 2)
        part = dict(full, w1=full["w1"][held], w3=full["w3"][held], w2=full["w2"][held])
        out, sown = lfm2.RoutedFFN(D, share).apply(
            {"params": part}, x, mask, mutable=[lfm2.INTERMEDIATES]
        )
        seen += int(sown[lfm2.INTERMEDIATES]["moe_rows_held"][-1])
        total = total + out
    assert seen == n * k  # every assignment is computed on exactly one rank
    assert np.abs(np.asarray(total - want)).max() < 1e-5 * np.abs(np.asarray(want)).max()


def pytest_no_mixing_across_a_graph_boundary_and_none_from_later_tokens(setup):
    """Conv and attention in a packed, padded batch of unequal lengths: a
    sequence's outputs do not move when ANOTHER sequence's tokens change, and
    a token's output does not move when a LATER token of its own does."""
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
    # 38 padding nodes more, not one row more routed.
    assert float(counters_wide["moe_rows_held"]) == float(counters["moe_rows_held"])

    def loss(p):
        out = model.apply({"params": p}, wide, train=True)
        return multihead_rmse_loss(
            out, wide, model.output_type, model.task_weights,
            head_loss=model.head_loss, class_minmax=model.class_minmax,
        )[0]

    value, grads = jax.value_and_grad(loss)(
        jax.tree_util.tree_map(jnp.asarray, variables["params"])
    )
    assert np.isfinite(float(value))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.isfinite(np.asarray(g)).all(), path


@pytest.mark.parametrize("lo,hi", [(0, 16383), (5, 16380)], ids=["slice", "table_inside"])
def pytest_token_ids_exact_over_the_whole_slice(lo, hi):
    """Every id of the slice through the data contract's scaling (float64
    divide, float32 storage: graftbench/datasets.py ``_scale``, as
    preprocess/raw_loader.py), the collator, and back: exact, for the model's
    input column and for the loss's target column."""
    ids = np.arange(lo, hi + 1)
    scaled = ((ids - float(lo)) / float(hi - lo)).astype(np.float32)
    cfg = lfm2.LFM2Config.from_arch(
        dict(ARCH, vocab_size=16384, token_minmax=[lo, hi]), 4
    )
    n = len(ids)
    pos = np.zeros((n, 3), np.float32)
    sample = GraphSample(
        x=scaled[:, None], pos=pos, y=scaled, y_loc=np.array([[0, n]], np.int64),
        edge_index=np.zeros((2, 0), np.int32),
    )
    batch = collate_graphs([sample], ("node",), (1,), with_positions=True)
    assert np.array_equal(np.asarray(lfm2.token_ids(batch.node_features[:n, 0], cfg)), ids)
    assert np.array_equal(
        np.asarray(class_ids(batch.targets[0][:n], (float(lo), float(hi)), 16384)), ids
    )


def pytest_cross_entropy_by_hand_and_rmse_untouched():
    rng = np.random.default_rng(0)
    graphs = _sequences((4, 6))
    batch = _collate(graphs)
    logits = jnp.asarray(rng.normal(size=(batch.node_features.shape[0], V)), jnp.float32)
    loss, per_head = multihead_rmse_loss(
        [logits], batch, ("node",), (1.0,),
        head_loss=("cross_entropy",), class_minmax=((0.0, V - 1.0),),
    )
    labels = np.round(np.concatenate([g.y for g in graphs]) * (V - 1.0)).astype(int)
    rows = np.asarray(logits[:10], np.float64)
    want = np.mean(
        np.log(np.exp(rows).sum(axis=1)) - rows[np.arange(10), labels]
    )
    assert abs(float(loss) - want) < 1e-5 and abs(float(per_head[0]) - want) < 1e-5
    # No head_loss: the historical RMSE, to the bit.
    pred = jnp.asarray(rng.normal(size=(batch.node_features.shape[0], 1)), jnp.float32)
    plain_rmse = multihead_rmse_loss([pred], batch, ("node",), (1.0,))
    named = multihead_rmse_loss([pred], batch, ("node",), (1.0,), head_loss=("rmse",),
                                class_minmax=(None,))
    assert float(plain_rmse[0]) == float(named[0])


def pytest_train_step_scopes_counters_and_other_families_untouched():
    """The compiled train step carries the four new scopes under the root and
    nothing outside the vocabulary; its metrics hold the three counters. A
    classic family's step has neither the collection nor the counters."""
    import re

    from hydragnn_tpu.train.trainer import create_train_state, make_train_step
    from hydragnn_tpu.utils.optimizer import select_optimizer

    model = _model()
    batch = _collate(_sequences((5, 9, 12)))
    opt = select_optimizer("AdamW", 1e-3)
    state = create_train_state(model, init_model_variables(model, batch), opt)
    assert state.batch_stats == {} and model.counts_routing
    step = make_train_step(model, opt, donate=False)
    text = step.lower(state, batch, jax.random.PRNGKey(0)).compile().as_text()
    used = set(re.findall(r"hydragnn\.[\w.]+", " ".join(re.findall(r'op_name="([^"]*)"', text))))
    assert {scopes.LFM2_CONV, scopes.LFM2_ATTN, scopes.MOE_ROUTE, scopes.MOE_EXPERTS,
            scopes.LOSS, scopes.OPTIMIZER, scopes.TRAIN_STEP} <= used
    assert used <= scopes.VOCABULARY
    backward = [n for n in re.findall(r'op_name="([^"]*)"', text) if "transpose(" in n]
    assert any(scopes.MOE_EXPERTS in n for n in backward)
    assert any(scopes.LFM2_ATTN in n for n in backward)
    new_state, metrics = step(state, batch, jax.random.PRNGKey(0))
    assert set(metrics) == {"loss", "rmses", "count", *lfm2.COUNTERS}
    assert 0 < float(metrics["moe_load_min"]) <= float(metrics["moe_load_max"])
    assert float(metrics["moe_rows_held"]) <= 3 * 26 * 2
    # The same path for a family that routes nothing: no counters.
    classic = create_model(
        "SAGE", 1, 8, (1,), ("node",),
        {"node": {"num_headlayers": 1, "dim_headlayers": [4], "type": "mlp"}}, [1.0], 2,
    )
    assert not classic.counts_routing and classic.head_loss == ()
    cbatch = collate_graphs(_sequences((5, 9)), ("node",), (1,))
    cstate = create_train_state(classic, init_model_variables(classic, cbatch), opt)
    _, cmetrics = make_train_step(classic, opt, donate=False)(
        cstate, cbatch, jax.random.PRNGKey(0)
    )
    assert set(cmetrics) == {"loss", "rmses", "count"}


def pytest_entry_points_refuse_what_the_family_cannot_run():
    with pytest.raises(ValueError, match="compute_dtype"):
        create_model("LFM2", 1, D, (V,), ("node",), HEADS, [1.0], 4, lfm2=ARCH,
                     compute_dtype="bfloat16")
    with pytest.raises(ValueError, match="token_minmax"):
        create_model("LFM2", 1, D, (V,), ("node",), HEADS, [1.0], 4,
                     lfm2={k: v for k, v in ARCH.items() if k != "token_minmax"})
    with pytest.raises(ValueError, match="not among"):
        _model(num_experts_held=4, experts_offset=6)
    with pytest.raises(ValueError, match="class_minmax"):
        create_model("LFM2", 1, D, (V,), ("node",), HEADS, [1.0], 4, lfm2=ARCH,
                     head_loss=("cross_entropy",))
    model = _model()
    with pytest.raises(ValueError, match="positions"):
        batch = collate_graphs(_sequences((5,)), ("node",), (1,))
        model.init(jax.random.PRNGKey(0), batch, train=False)


def pytest_run_training_trains_the_family_through_the_loaders(tmp_path, monkeypatch):
    """``run_training`` on a ``model_type: "LFM2"`` config: the serialized
    dataset of the benchmark's generator, the loaders' split and band graph,
    config completion (the head as wide as its classes, the loaders' targets
    one column, both tables read), ``TrainingDriver``'s scanned epoch. The
    loss falls from ln(vocab) and the counters are published."""
    import hydragnn_tpu
    from graftbench import datasets
    from hydragnn_tpu import telemetry

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("SERIALIZED_DATA_PATH", str(tmp_path))
    block, _ = datasets.materialize(
        {"generator": "token_chain", "graphs": 40, "tokens": 24, "vocab": V,
         "successors": 2}, 7, str(tmp_path / "cache"),
    )
    with open(os.path.join(REPO, "graftbench", "configs", "lfm2_8b_a1b_ep4.json")) as f:
        nn_block = json.load(f)["NeuralNetwork"]
    nn_block["Architecture"].update(
        {k: v for k, v in ARCH.items() if k != "token_minmax"}, hidden_dim=D,
        num_conv_layers=4,
    )
    nn_block["Variables_of_interest"]["num_classes"] = [V]
    nn_block["Training"].update(batch_size=4, num_epoch=6, learning_rate=0.01)
    config = {
        "Verbosity": {"level": 0}, "Dataset": block, "NeuralNetwork": nn_block,
        "Visualization": {"create_plots": 0},
    }
    history = hydragnn_tpu.run_training(config)
    losses = history["total_loss_train"]
    assert abs(losses[0] - np.log(V)) < 1.0 and losses[-1] < losses[0] - 1.0, losses
    assert all(np.isfinite(history["total_loss_val"]))
    arch = config["NeuralNetwork"]["Architecture"]
    assert arch["output_dim"] == [V] and arch["target_dim"] == [1]
    assert arch["head_loss"] == ["cross_entropy"]
    lo, hi = arch["token_minmax"]
    assert 0 <= lo < hi <= V - 1 and arch["class_minmax"][0][1] <= V - 1
    gauges = telemetry.gauges_snapshot()
    assert gauges["train/moe_rows_held_per_epoch"] > 0
    assert gauges["train/moe_load_max_per_epoch"] >= gauges["train/moe_load_min_per_epoch"]
