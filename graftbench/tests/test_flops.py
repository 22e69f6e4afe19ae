"""``flops.py`` and the family files' counts against cases worked out by
hand: two nodes, two edges."""

import json
import os

import pytest

from graftbench import flops
from graftbench.families import gat, pna

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_pna_conv_two_nodes_by_hand():
    # nodes 2, edges 2, f_in 3, f_out 5, no edge features.
    # pre-MLP: 2 edges x (2*3 -> 3): 2*2*6*3 = 72 multiply-adds as ops, + 2*3 bias
    pre = 72 + 6
    # aggregation: 5 passes over 2x3 messages = 30; 16 scaled blocks of 2x3 = 96
    agg = 30 + 96
    # post-MLP: 2 nodes x (17*3 -> 5): 2*2*51*5 = 1020, + 10 bias
    post = 1020 + 10
    # final linear: 2 x (5 -> 5): 2*2*5*5 = 100, + 10 bias
    lin = 100 + 10
    got = flops.total(pna.conv_counts(nodes=2, edges=2, f_in=3, f_out=5))
    assert got["ops"] == pre + agg + post + lin == 1344
    # rest, forward: pre (2*6 + 6*3 + 2*3) + scalers (read 4 blocks of 2x3,
    # write 16) + post (2*51 + 51*5 + 2*5) + lin (2*5 + 5*5 + 2*5), float32;
    # backward twice that.
    assert got["bytes"]["rest"]["fwd"] == 4 * (36 + 120 + 367 + 45)
    assert got["bytes"]["rest"]["bwd"] == 2 * got["bytes"]["rest"]["fwd"]


def pytest_pna_conv_bytes_by_scope_two_nodes_by_hand():
    got = flops.total(pna.conv_counts(nodes=2, edges=2, f_in=3, f_out=5))["bytes"]
    # Two gathers [2, 3] -> [2, 3]: each reads 2 rows of 3, writes 2 rows of
    # 3 and reads 2 indices: 6 + 6 + 2 = 14 words.
    assert got["gather"]["fwd"] == 4 * 2 * 14
    # Their scatter-adds: each reads 2 rows of 3 and 2 indices, writes 2
    # rows of 3: 6 + 2 + 6 = 14 words.
    assert got["gather"]["bwd"] == 4 * 2 * 14
    # Four passes (mean, min, max, squares): each reads 2 x 3 messages and 2
    # indices and writes 2 x 3: 14 words; the degree reads 2 indices, writes 2.
    assert got["agg"]["fwd"] == 4 * (4 * 14 + 4)
    # Backward of the four: each reads the 2 x 3 cotangent and 2 indices and
    # writes 2 x 3; the degree has none.
    assert got["agg"]["bwd"] == 4 * 4 * 14
    # The first layer gathers the raw input: no gradient flows back into it.
    first = flops.total(
        pna.conv_counts(nodes=2, edges=2, f_in=3, f_out=5, input_grad=False)
    )["bytes"]
    assert first["gather"] == {"fwd": 4 * 2 * 14, "bwd": 0}
    assert first["agg"] == got["agg"]  # the messages have weights behind them


def pytest_gatv2_conv_two_nodes_by_hand():
    # nodes 2, edges 2, f_in 3, 2 heads of 4: width 8.
    proj = 2 * (2 * 2 * 3 * 8 + 2 * 8)  # two projections, with bias
    terms = 2 + 2  # edges and self loops
    attn = terms * 8 * 6 + terms * 2 * 5
    assert flops.total(gat.conv_counts(2, 2, 3, 4, 2))["ops"] == proj + attn == 456


def pytest_gatv2_conv_bytes_by_scope_two_nodes_by_hand():
    got = flops.total(gat.conv_counts(2, 2, 3, 4, 2))["bytes"]
    # Forward gathers over the 2 edges: projected sources and destinations
    # (8 wide: 16 + 16 + 2 = 34 words each), the shift and the denominator
    # (2 wide: 4 + 4 + 2 = 10 each).
    assert got["gather"]["fwd"] == 4 * (2 * 34 + 2 * 10)
    # Scatter-adds: two 8 wide (read 16 + 2, write 16 = 34) and the
    # denominator's (read 4 + 2, write 4 = 10); the shift is under
    # stop_gradient.
    assert got["gather"]["bwd"] == 4 * (2 * 34 + 10)
    # Passes: the logits' max and the denominators (2 wide: 4 + 2 + 4 = 10
    # each), the weighted sum (8 wide: 16 + 2 + 16 = 34).
    assert got["agg"]["fwd"] == 4 * (10 + 10 + 34)
    # Backward: the denominators' (10) and the weighted sum's (34).
    assert got["agg"]["bwd"] == 4 * (10 + 34)


ARCH = {
    "model_type": "PNA", "input_dim": 1, "hidden_dim": 4,
    "num_conv_layers": 2, "edge_dim": None,
    "output_type": ["graph", "node"], "output_dim": [1, 1],
    "output_heads": {
        "graph": {"num_sharedlayers": 1, "dim_sharedlayers": 3,
                  "num_headlayers": 1, "dim_headlayers": [2]},
        "node": {"num_headlayers": 1, "dim_headlayers": [2], "type": "mlp"},
    },
}


def pytest_train_step_is_three_forwards_of_the_stack():
    fwd = flops.forward(ARCH, nodes=2, edges=2, graphs=1)
    convs = (
        flops.total(pna.conv_counts(2, 2, 1, 4))["ops"]
        + flops.total(pna.conv_counts(2, 2, 4, 4))["ops"]
    )
    bn = 2 * 4 * 2 * 4
    pool = 2 * 4
    graph_head = (2 * 4 * 3 + 3) + (2 * 3 * 2 + 2) + (2 * 2 * 1 + 1)
    node_head = 2 * ((2 * 4 * 2 + 2) + (2 * 2 * 1 + 1))
    assert fwd["ops"] == convs + bn + pool + graph_head + node_head
    assert flops.train_step(ARCH, 2, 2, 1)["ops"] == 3 * fwd["ops"]
    # Real rows only: twice the rows, twice the row-proportional work.
    assert flops.forward(ARCH, 4, 4, 2)["ops"] == 2 * fwd["ops"]


def pytest_train_step_bytes_by_scope_are_the_layers_summed():
    step = flops.train_step(ARCH, 2, 2, 1)["bytes"]
    layers = [
        flops.total(pna.conv_counts(2, 2, 1, 4, input_grad=False))["bytes"],
        flops.total(pna.conv_counts(2, 2, 4, 4))["bytes"],
    ]
    for scope in ("gather", "agg"):
        for direction in ("fwd", "bwd"):
            assert step[scope][direction] == sum(
                b[scope][direction] for b in layers
            )
    assert flops.forward(ARCH, 2, 2, 1)["bytes"]["gather"] == step["gather"]["fwd"]


@pytest.mark.parametrize("config, ops", [
    ("pna_multihead_h256", 522785642439),
    ("gatv2_h64x6_md17like", 36117953490),
])
def pytest_step_ops_are_the_parents_integers(config, ops):
    """``model_flops_util`` must read what it read before the counts moved
    into the family files (PR 25): ``train_step(...)["ops"]`` of commit
    6f9ff16 for each configuration's Architecture, completed as
    ``update_config`` completes it, at 8,607 nodes, 250,000 edges, 512
    graphs."""
    with open(os.path.join(REPO, "graftbench", "configs", config + ".json")) as f:
        nn = json.load(f)["NeuralNetwork"]
    voi = nn["Variables_of_interest"]
    arch = dict(
        nn["Architecture"], input_dim=len(voi["input_node_features"]),
        output_type=voi["type"], output_dim=[1] * len(voi["type"]),
        edge_dim=None,
    )
    assert flops.train_step(arch, 8607, 250000, 512)["ops"] == ops


def pytest_a_family_without_a_file_names_the_file_to_add():
    with pytest.raises(NotImplementedError, match="graftbench/families/cgcnn.py"):
        flops.forward(dict(ARCH, model_type="CGCNN"), 2, 2, 1)
