"""Model factory (reference /root/reference/hydragnn/models/create.py:28-178).

Builds a HydraGNN flax module + initialized variables from the completed
Architecture config block. The reference seeds torch.manual_seed(0) at creation
(create.py:75); here initialization is keyed on PRNGKey(seed) with seed 0 default.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from flax.core import FrozenDict

from ..graphs.batch import GraphBatch
from ..graphs.collate import collate_graphs
from ..telemetry import graftel as telemetry
from .base import HydraGNN
from .convs import pna_degree_averages
from .families import CONV_TYPES, TOKEN_STACKS
from .loss import normalize_task_weights


@telemetry.setup_phase("create_model")
def create_model_config(
    config: Dict[str, Any], verbosity: int = 0, use_gpu: bool = True
) -> HydraGNN:
    return create_model(
        model_type=config["model_type"],
        input_dim=config["input_dim"],
        hidden_dim=config["hidden_dim"],
        output_dim=config["output_dim"],
        output_type=config["output_type"],
        output_heads=config["output_heads"],
        task_weights=config["task_weights"],
        num_conv_layers=config["num_conv_layers"],
        freeze_conv=config.get("freeze_conv_layers", False),
        initial_bias=config.get("initial_bias"),
        num_nodes=config.get("num_nodes"),
        max_neighbours=config.get("max_neighbours"),
        edge_dim=config.get("edge_dim"),
        pna_deg=config.get("pna_deg"),
        radius=config.get("radius"),
        num_radial=config.get("num_radial"),
        token_arch=config if config["model_type"] in TOKEN_STACKS else None,
        head_loss=config.get("head_loss") or (),
        class_minmax=config.get("class_minmax") or (),
        compute_dtype=config.get("compute_dtype"),
        remat=config.get("remat", False),
        verbosity=verbosity,
    )


def create_model(
    model_type: str,
    input_dim: int,
    hidden_dim: int,
    output_dim: Sequence[int],
    output_type: Sequence[str],
    output_heads: Dict[str, Any],
    task_weights: Sequence[float],
    num_conv_layers: int,
    freeze_conv: bool = False,
    initial_bias: Optional[float] = None,
    num_nodes: Optional[int] = None,
    max_neighbours: Optional[int] = None,
    edge_dim: Optional[int] = None,
    pna_deg: Optional[Sequence[float]] = None,
    radius: Optional[float] = None,
    num_radial: Optional[int] = None,
    token_arch: Optional[Dict[str, Any]] = None,
    head_loss: Sequence[str] = (),
    class_minmax: Sequence[Any] = (),
    compute_dtype: Optional[str] = None,
    remat: bool = False,
    verbosity: int = 0,
) -> HydraGNN:
    """``token_arch``: for a token family (``model_type`` one of
    models/families.py ``TOKEN_STACKS``), the ``Architecture`` block's keys
    that size the stack, named as the family's source names them (the
    family's sizes class is built ``from_arch`` of it). ``head_loss``: "rmse"
    or "cross_entropy" a head (empty: rmse throughout); ``class_minmax``: for
    a cross-entropy head the (min, max) of its target column in the dataset's
    table, None for the others."""
    if len(task_weights) != len(output_dim):
        raise ValueError(
            f"Inconsistent number of loss weights and tasks: {len(task_weights)} "
            f"VS {len(output_dim)}"
        )
    if model_type not in CONV_TYPES:
        raise ValueError("Unknown model_type: {0}".format(model_type))
    kwargs: Dict[str, Any] = {}
    if model_type == "PNA":
        assert pna_deg is not None, "PNA requires degree input."
        avg_log, avg_lin = pna_degree_averages(pna_deg)
        kwargs.update(pna_deg_avg_log=avg_log, pna_deg_avg_lin=avg_lin)
    elif model_type == "MFC":
        assert max_neighbours is not None, "MFC requires max_neighbours input."
        kwargs.update(mfc_max_degree=int(max_neighbours))
    elif model_type == "CGCNN":
        hidden_dim = input_dim  # CGCNN preserves channels (CGCNNStack.py:31-42)
    elif model_type == "PAINN":
        if radius is None or num_radial is None:
            raise ValueError(
                "PAINN requires radius (the cutoff) and num_radial (the "
                "number of radial basis functions) in Architecture."
            )
        kwargs.update(radius=float(radius), num_radial=int(num_radial))
    elif model_type in TOKEN_STACKS:
        if token_arch is None:
            raise ValueError(
                f"{model_type} requires the stack's sizes (create_model("
                "token_arch=the Architecture block))"
            )
        if compute_dtype:
            raise ValueError(
                f"{model_type} reads token ids from a float32 node column; "
                "compute_dtype would round it"
            )
        kwargs.update(token_cfg=TOKEN_STACKS[model_type][0].from_arch(
            token_arch, int(num_conv_layers)
        ))
    loss_kinds = tuple(head_loss) or ("rmse",) * len(output_dim)
    unknown = set(loss_kinds) - {"rmse", "cross_entropy"}
    if unknown or len(loss_kinds) != len(output_dim):
        raise ValueError(f"head_loss {tuple(head_loss)!r}: one of 'rmse', "
                         "'cross_entropy' a head")
    if "cross_entropy" in loss_kinds:
        ranges = tuple(
            None if r is None else tuple(float(v) for v in r) for r in class_minmax
        )
        if len(ranges) != len(loss_kinds) or any(
            (k == "cross_entropy") != (r is not None)
            for k, r in zip(loss_kinds, ranges)
        ):
            raise ValueError(
                "a cross_entropy head needs class_minmax: the (min, max) of "
                "its target column (config completion reads the dataset's)"
            )
        kwargs.update(head_loss=loss_kinds, class_minmax=ranges)
    return HydraGNN(
        conv_type=model_type,
        input_dim=input_dim,
        hidden_dim=hidden_dim,
        output_dim=tuple(output_dim),
        output_type=tuple(output_type),
        config_heads=_frozen(output_heads),
        num_conv_layers=num_conv_layers,
        task_weights=normalize_task_weights(task_weights),
        freeze_conv=bool(freeze_conv),
        num_nodes=num_nodes,
        initial_bias=initial_bias,
        edge_dim=edge_dim,
        compute_dtype=compute_dtype,
        remat=bool(remat),
        **kwargs,
    )


def _frozen(value):
    """A configuration block as a hashable value (dicts frozen, lists as
    tuples): the model that carries it can then key a compiled program."""
    if isinstance(value, (dict, FrozenDict)):
        return FrozenDict({k: _frozen(v) for k, v in value.items()})
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


@functools.partial(jax.jit, static_argnums=(0,))
def _init_program(model: HydraGNN, batch: GraphBatch, seed):
    rngs = {"params": jax.random.PRNGKey(seed), "dropout": jax.random.PRNGKey(seed + 1)}
    return model.init(rngs, batch, train=False)


@telemetry.setup_phase("init_variables", until_ready=True)
def init_model_variables(
    model: HydraGNN, example_batch: GraphBatch, seed: int = 0
) -> Dict[str, Any]:
    """The model's variables, drawn by ONE compiled program: ``model.init``
    is traced with the batch as its argument, so a model of any family costs
    one compile request and not one a primitive (PNA: 149), and an equal
    model over equal shapes (a reload, a restart inside one process) none."""
    return _init_program(model, example_batch, int(seed))


def make_example_batch(
    input_dim: int,
    output_dim: Sequence[int],
    output_type: Sequence[str],
    edge_dim: Optional[int] = None,
    num_nodes: int = 4,
    with_positions: bool = False,
) -> GraphBatch:
    """A tiny structurally-valid batch for shape inference / init
    (``with_positions`` for the families of ``families.POSITION_FAMILIES``)."""
    from ..graphs.sample import GraphSample

    n = num_nodes
    x = np.ones((n, input_dim), dtype=np.float32)
    ei = np.stack(
        [np.arange(n, dtype=np.int32), (np.arange(n, dtype=np.int32) + 1) % n]
    )
    ea = np.ones((n, max(edge_dim or 1, 1)), dtype=np.float32)
    total = sum(
        d if t == "graph" else d * n for d, t in zip(output_dim, output_type)
    )
    y = np.zeros((total,), dtype=np.float32)
    y_loc = np.zeros((1, len(output_dim) + 1), dtype=np.int64)
    off = 0
    for i, (d, t) in enumerate(zip(output_dim, output_type)):
        off += d if t == "graph" else d * n
        y_loc[0, i + 1] = off
    # Atoms a unit apart on a line: no edge of length 0.
    pos = np.zeros((n, 3), np.float32)
    pos[:, 0] = np.arange(n)
    s = GraphSample(x=x, pos=pos, y=y, y_loc=y_loc, edge_index=ei, edge_attr=ea)
    return collate_graphs(
        [s],
        head_types=output_type,
        head_dims=output_dim,
        edge_dim=edge_dim,
        with_positions=with_positions,
    )
