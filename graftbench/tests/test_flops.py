"""``flops.py`` against a case worked out by hand: two nodes, two edges."""

from graftbench import flops


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
    got = flops.pna_conv(nodes=2, edges=2, f_in=3, f_out=5)
    assert got["ops"] == pre + agg + post + lin == 1344
    # bytes: pre (2*6 + 6*3 + 2*3) + agg (2*2*3 + 2*3 + 5*4*2*3) +
    #        post (2*51 + 51*5 + 2*5) + lin (2*5 + 5*5 + 2*5), float32
    assert got["bytes"] == 4 * (36 + 138 + 367 + 45)


def pytest_gatv2_conv_two_nodes_by_hand():
    # nodes 2, edges 2, f_in 3, 2 heads of 4: width 8.
    proj = 2 * (2 * 2 * 3 * 8 + 2 * 8)  # two projections, with bias
    terms = 2 + 2  # edges and self loops
    attn = terms * 8 * 6 + terms * 2 * 5
    assert flops.gatv2_conv(2, 2, 3, 4, 2)["ops"] == proj + attn == 456


def pytest_train_step_is_three_forwards_of_the_stack():
    arch = {
        "model_type": "PNA", "input_dim": 1, "hidden_dim": 4,
        "num_conv_layers": 2, "edge_dim": None,
        "output_type": ["graph", "node"], "output_dim": [1, 1],
        "output_heads": {
            "graph": {"num_sharedlayers": 1, "dim_sharedlayers": 3,
                      "num_headlayers": 1, "dim_headlayers": [2]},
            "node": {"num_headlayers": 1, "dim_headlayers": [2], "type": "mlp"},
        },
    }
    fwd = flops.forward(arch, nodes=2, edges=2, graphs=1)
    convs = (
        flops.pna_conv(2, 2, 1, 4)["ops"] + flops.pna_conv(2, 2, 4, 4)["ops"]
    )
    bn = 2 * 4 * 2 * 4
    pool = 2 * 4
    graph_head = (2 * 4 * 3 + 3) + (2 * 3 * 2 + 2) + (2 * 2 * 1 + 1)
    node_head = 2 * ((2 * 4 * 2 + 2) + (2 * 2 * 1 + 1))
    assert fwd["ops"] == convs + bn + pool + graph_head + node_head
    assert flops.train_step(arch, 2, 2, 1)["ops"] == 3 * fwd["ops"]
    # Real rows only: twice the rows, twice the row-proportional work.
    assert flops.forward(arch, 4, 4, 2)["ops"] == 2 * fwd["ops"]
