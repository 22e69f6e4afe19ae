"""LFM2's block (``model_type: "LFM2"``, hydragnn_tpu/models/lfm2.py) on the
CPU at small widths (d 32, 4 layers: conv, conv, attention, conv; 8 experts,
2 a token, this rank holding 4 of them from expert 2): the program against the
plain reference of ``graftbench/families/lfm2.py`` for forward, loss and
gradients with the routing taken from the program and held to the margins;
the four shares of a routed layer adding up to the uncut layer; no mixing
across a graph boundary in a packed, padded batch of unequal lengths; padding
nodes routed nowhere and every gradient finite; token ids exact over the
whole slice; the scopes and counters; the routed layer's compact ``[C, .]``
path against its ``[K N, .]`` fall-back, bit for bit; the family through
``run_training``. Values and counts, never a time.

A whole stack is run under ``jit``, once a model (the library's initializer
is one program already): op by op outside it every primitive of every shape
compiles alone, and that was a third of the suite's seconds (``compiled``
below, which the four sibling families' files import). What is about the
eager path itself (``RoutedFFN`` run as the initializer runs it) stays
eager."""

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
from hydragnn_tpu.models import lfm2, token_common, token_routed  # noqa: E402
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
        "LFM2", 1, D, (V,), ("node",), HEADS, [1.0], 4, token_arch=dict(ARCH, **arch),
        head_loss=("cross_entropy",), class_minmax=([0.0, V - 1.0],),
    )


def _collate(graphs, **pads):
    return collate_graphs(graphs, ("node",), (1,), with_positions=True, **pads)


_PROGRAMS = {}  # (id(model), purpose) -> (the model, its compiled callable)


@pytest.fixture(scope="module", autouse=True)
def programs():
    """What a module's tests compiled, kept for as long as the module runs:
    a model's forward or loss is traced and compiled ONCE (once a batch
    shape: ``jit``'s own cache), whichever test asks first. The sibling
    families' files import this fixture with the helpers below."""
    yield _PROGRAMS
    _PROGRAMS.clear()


def compiled(model, purpose, function):
    """``jax.jit(function)``, made once for this ``model`` object and
    ``purpose`` (later calls hand back the first one's). A test that patches
    what the program calls names a purpose of its own, or it would be handed
    the program traced before the patch."""
    key = (id(model), purpose)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = (model, jax.jit(function))  # the model kept alive: its id is the key
    return _PROGRAMS[key][1]


def apply_routed(model, params, batch):
    """(outputs, what the routed layers sowed) of the compiled forward."""
    return compiled(model, "forward", lambda params, batch: model.apply(
        {"params": params}, batch, train=False, mutable=[token_routed.INTERMEDIATES],
    ))(params, batch)


def loss_of(model, train):
    """``(params, batch) -> loss`` as the trainer computes it."""
    def loss(params, batch):
        out = model.apply({"params": params}, batch, train=train)
        return multihead_rmse_loss(
            out, batch, model.output_type, model.task_weights,
            head_loss=model.head_loss, class_minmax=model.class_minmax,
        )[0]
    return loss


def loss_and_grads(model, params, batch, train):
    """(loss, its gradient by every parameter), one compiled program."""
    return compiled(
        model, ("loss and gradients", train), jax.value_and_grad(loss_of(model, train)),
    )(jax.tree_util.tree_map(jnp.asarray, params), batch)


def _forward(model, variables, batch):
    out, sown = apply_routed(model, variables["params"], batch)
    routing, counters = token_routed.split_intermediates(sown[token_routed.INTERMEDIATES])
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
    full = token_routed.RoutedFFN(D, whole).init(jax.random.PRNGKey(0), x, mask)["params"]
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
        out, sown = token_routed.RoutedFFN(D, share).apply(
            {"params": part}, x, mask, mutable=[token_routed.INTERMEDIATES]
        )
        seen += int(sown[token_routed.INTERMEDIATES]["moe_rows_held"][-1])
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

    value, grads = loss_and_grads(model, variables["params"], wide, True)
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
    assert np.array_equal(np.asarray(token_common.token_ids(batch.node_features[:n, 0], cfg)), ids)
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


def steered_layer(k, held, experts, offset, to_held, one_expert=None, d=None, f=16):
    """(layer, params, x): a ``RoutedFFN`` (``k`` a token, ``held`` of
    ``experts`` from ``offset``) whose router sends node ``i`` to
    ``to_held[i]`` held experts (to ``one_expert`` if given) and to absent
    ones for the rest: the gate reads expert ``e``'s score off column ``e`` of
    ``x``, where a node's chosen experts stand out of the noise."""
    d = d or experts + 32
    cfg = lfm2.LFM2Config.from_arch(dict(
        ARCH, layer_types=["conv"], num_dense_layers=0, moe_intermediate_size=f,
        num_experts=experts, num_experts_per_tok=k, num_experts_held=held,
        experts_offset=offset,
    ), 1)
    layer = token_routed.RoutedFFN(d, cfg)
    rng = np.random.default_rng(5)
    n = len(to_held)
    x = 0.1 * rng.normal(size=(n, d)).astype(np.float32)
    absent = [e for e in range(experts) if not offset <= e < offset + held]
    for i, m in enumerate(to_held):
        here = [offset + (i * m + j) % held for j in range(m)]
        if one_expert is not None and m:
            here = [one_expert]
        away = [absent[(i * k + j) % len(absent)] for j in range(k - m)]
        x[i, here + away] = 4.0
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), jnp.zeros((8, d)), jnp.ones((8,), bool))
    gate = 0.01 * rng.normal(size=(d, experts)).astype(np.float32)
    gate[np.arange(experts), np.arange(experts)] = 1.0
    params = {"params": dict(params["params"], gate=jnp.asarray(gate))}
    return layer, params, jnp.asarray(x)


def at_capacity(layer, params, x, mask, capacity):
    """(output, gradients by parameter and by the input, counters) of the
    layer with row arrays of ``capacity`` rows."""
    def loss(params, x):
        y, sown = layer.apply(params, x, mask, capacity, mutable=[token_routed.INTERMEDIATES])
        return (y * jnp.cos(y)).sum(), (y, sown[token_routed.INTERMEDIATES])

    (_, (y, sown)), grads = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    )(params, x)
    return y, grads, {name: float(sown[name][0]) for name in token_routed.COUNTERS}


def assert_equal_to_rounding(got, want, rel=4e-7):
    """Float32 sums of the same terms in another order."""
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)
    ):
        a, b = np.asarray(a), np.asarray(b)
        scale = float(np.abs(b).max()) if b.size else 0.0
        assert a.shape == b.shape, path
        assert float(np.abs(a - b).max(initial=0.0)) <= rel * (scale or 1.0), path


def assert_bit_equal(got, want):
    assert_equal_to_rounding(got, want, rel=0.0)


# (k, held, experts, offset, nodes, padding nodes where a case has them): the
# two token cells' routing at a fortieth of their nodes; the rank's uniform
# share is 96 / 128 rows of 384 / 1024.
ROUTINGS = {
    "k4_8_of_32": (4, 8, 32, 8, 96, 24), "k8_32_of_256": (8, 32, 256, 0, 128, 64),
}
# name: (held experts a node, the capacity less the live rows, passes).
# Every capacity is a multiple of 16 as the layer's own is of its row tile,
# and every expert's rows are 12, 18 or 24 (K 4) or a power of two (K 8): the
# CPU's ``ragged_dot`` sums a weight gradient's rows in an order that follows
# the row count of the WHOLE array otherwise (1 ulp; the TPU's kernel sums by
# row tile, and the tiles are the same at every capacity).
COMPACT = {
    "live_well_under_capacity": (1, 96, 1),
    "live_equals_capacity": (2, 0, 1),
    "one_row_over_takes_a_second_pass": (2, -1, 2),
    "no_live_row_at_all": (0, 64, 1),
    "every_assignment_to_one_held_expert": (1, 32, 1),
    "padding_nodes_present": (2, 64, 1),
}


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
@pytest.mark.parametrize("case", sorted(COMPACT))
def pytest_compact_row_arrays_give_what_every_row_gives(routing, case):
    """``RoutedFFN`` over ``[C, .]`` row arrays against the same layer over
    ``[K N, .]`` (``capacity`` = K N: every assignment in one pass, no loop
    compiled). Where the live rows fit in ``C`` the output and every
    parameter's gradient are bit-equal, the input's gradient equal to the
    last bit but one (XLA adds its two parts, the router's and the rows', in
    another order round the loop), and all of them bit-equal between two
    capacities; the counter reads 1. One row over, a second pass takes the
    rest: nothing is dropped, sums of the same terms in another order, and
    the counter reads 0."""
    k, held, experts, offset, n, padding = ROUTINGS[routing]
    a_node, room, passes = COMPACT[case]
    real = n - padding if case == "padding_nodes_present" else n
    mask = jnp.arange(n) < real
    layer, params, x = steered_layer(
        k, held, experts, offset, [a_node] * n,
        one_expert=offset + 3 if "one_held_expert" in case else None,
    )
    live = a_node * real
    capacity = live + room
    assert capacity < n * k and (capacity % 16 == 0 or passes > 1)
    y, grads, counted = at_capacity(layer, params, x, mask, capacity)
    y_all, grads_all, counted_all = at_capacity(layer, params, x, mask, n * k)
    assert counted["moe_rows_held"] == counted_all["moe_rows_held"] == live
    assert counted["moe_layers_compact"] == int(passes == 1) == int(live <= capacity)
    assert counted_all["moe_layers_compact"] == 0  # no compact path compiled
    if "one_held_expert" in case:
        assert counted["moe_load_max"] == live and counted["moe_load_min"] == 0
    if passes == 1:
        assert_bit_equal((y, grads[0]), (y_all, grads_all[0]))
        wider = at_capacity(layer, params, x, mask, capacity + 48)
        assert_bit_equal((y, grads), wider[:2])
    assert_equal_to_rounding((y, grads), (y_all, grads_all))
    # Run eagerly, as the initializer runs the layer, the passes are a Python
    # loop over the live rows it can read: no ``while`` is compiled.
    assert_equal_to_rounding(layer.apply(params, x, mask, capacity), y)
    assert float(jnp.abs(y).max()) > 0 or live == 0
    assert not np.asarray(y[real:]).any()  # padding nodes receive nothing
    # The layer's own capacity: the rank's share times the factor, in tiles.
    assert token_routed._capacity(n * k, held, experts) == 256
    assert token_routed._capacity(33280, 32, 256) == token_routed._capacity(16640, 8, 32) == 6400


def pytest_train_step_scopes_counters_and_other_families_untouched():
    """The compiled train step carries the four new scopes under the root and
    nothing outside the vocabulary; its metrics hold the four counters. A
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
    program = step.lower(state, batch, jax.random.PRNGKey(0)).compile()
    text = program.as_text()
    used = set(re.findall(r"hydragnn\.[\w.]+", " ".join(re.findall(r'op_name="([^"]*)"', text))))
    assert {scopes.LFM2_CONV, scopes.LFM2_ATTN, scopes.MOE_ROUTE, scopes.MOE_EXPERTS,
            scopes.LOSS, scopes.OPTIMIZER, scopes.TRAIN_STEP} <= used
    assert used <= scopes.VOCABULARY
    backward = [n for n in re.findall(r'op_name="([^"]*)"', text) if "transpose(" in n]
    assert any(scopes.MOE_EXPERTS in n for n in backward)
    assert any(scopes.LFM2_ATTN in n for n in backward)
    new_state, metrics = program(state, batch, jax.random.PRNGKey(0))  # the one read above
    assert set(metrics) == {"loss", "rmses", "count", *token_routed.COUNTERS}
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
        create_model("LFM2", 1, D, (V,), ("node",), HEADS, [1.0], 4, token_arch=ARCH,
                     compute_dtype="bfloat16")
    with pytest.raises(ValueError, match="token_minmax"):
        create_model("LFM2", 1, D, (V,), ("node",), HEADS, [1.0], 4,
                     token_arch={k: v for k, v in ARCH.items() if k != "token_minmax"})
    with pytest.raises(ValueError, match="not among"):
        _model(num_experts_held=4, experts_offset=6)
    with pytest.raises(ValueError, match="class_minmax"):
        create_model("LFM2", 1, D, (V,), ("node",), HEADS, [1.0], 4, token_arch=ARCH,
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
