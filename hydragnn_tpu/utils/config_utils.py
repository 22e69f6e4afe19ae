"""Data-driven config completion.

Accepts the reference's JSON schema (/root/reference/hydragnn/utils/
config_utils.py:17-195 describes the contract: infer output_dim/output_type
from the packed y_loc of the first training sample, input_dim from the
selected node features, the PNA degree histogram from the train set, edge_dim
from the declared edge features, then apply defaults) and produces the same
completed config — pinned by the golden tests in
tests/test_config_completion.py.

The implementation is organized as a completion PIPELINE over a small context:
each stage is a function of (config, ctx) run in order by ``update_config``,
with per-head logic driven by a kind→handler dispatch table and the trailing
defaults/log-name encoding declared as data.
"""

from __future__ import annotations

import os
import pickle
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

from ..preprocess.graph_build import check_if_graph_size_variable
from ..telemetry import graftel as telemetry
from .model import calculate_PNA_degree

# Conv stacks that consume per-edge feature vectors.
_EDGE_FEATURE_MODELS = frozenset({"PNA", "CGCNN"})

# Trailing defaults: (path into config, key, default value).
_DEFAULTS = (
    (("NeuralNetwork", "Architecture"), "freeze_conv_layers", False),
    (("NeuralNetwork", "Architecture"), "initial_bias", None),
    (("NeuralNetwork", "Training"), "optimizer", "AdamW"),
    # Per-epoch shuffle granularity: "sample" (reference DistributedSampler
    # parity) or "batch" (frozen membership; enables collation + device
    # batch caching across epochs — see preprocess/dataloader.py).
    (("NeuralNetwork", "Training"), "reshuffle", "sample"),
)

# Log-name encoding: "<tag><value>" segments in this order, then the two
# list-valued trailers appended by get_log_name_config.
_LOG_NAME_FIELDS = (
    ("", ("NeuralNetwork", "Architecture"), "model_type"),
    ("-r-", ("NeuralNetwork", "Architecture"), "radius"),
    ("-mnnn-", ("NeuralNetwork", "Architecture"), "max_neighbours"),
    ("-ncl-", ("NeuralNetwork", "Architecture"), "num_conv_layers"),
    ("-hd-", ("NeuralNetwork", "Architecture"), "hidden_dim"),
    ("-ne-", ("NeuralNetwork", "Training"), "num_epoch"),
    ("-lr-", ("NeuralNetwork", "Training"), "learning_rate"),
    ("-bs-", ("NeuralNetwork", "Training"), "batch_size"),
    ("-data-", ("Dataset",), "name"),
)


def _at(config: Dict[str, Any], path) -> Dict[str, Any]:
    for key in path:
        config = config[key]
    return config


@dataclass
class _Ctx:
    """Everything the completion stages read besides the config itself."""

    loaders: tuple
    sample: Any  # first training sample
    spans: List[int]  # per-head slice widths in the packed y vector
    variable_size: bool


def _head_spans(sample) -> List[int]:
    offsets = [int(v) for v in sample.y_loc[0]]
    return [b - a for a, b in zip(offsets, offsets[1:])]


# ------------------------------------------------------------- per-head kinds
def _head_dim(kind: str, span: int, ctx: _Ctx, arch: Dict[str, Any]) -> int:
    if kind == "graph":
        return span
    if kind == "node":
        if (
            ctx.variable_size
            and arch["output_heads"]["node"]["type"] == "mlp_per_node"
        ):
            raise ValueError(
                "node head type 'mlp_per_node' needs every graph in the "
                "dataset to have the same node count; switch NeuralNetwork."
                "Architecture.output_heads.node.type to 'mlp' or 'conv'."
            )
        return span // ctx.sample.num_nodes
    raise ValueError(f"unrecognized head kind: {kind!r}")


# ----------------------------------------------------------- pipeline stages
def _stage_check_declared_dims(config, ctx):
    """Cross-check y_loc-derived widths against Dataset.*_features.dim."""
    if "Dataset" not in config:
        return
    voi = _at(config, ("NeuralNetwork", "Variables_of_interest"))
    declared = {
        "graph": lambda span, i: span
        == config["Dataset"]["graph_features"]["dim"][i],
        "node": lambda span, i: span // ctx.sample.num_nodes
        == config["Dataset"]["node_features"]["dim"][i],
    }
    for kind, index, span in zip(voi["type"], voi["output_index"], ctx.spans):
        check = declared.get(kind)
        if check is not None and not check(span, index):
            raise AssertionError(
                f"head of kind {kind!r} at output_index {index} does not match "
                "the declared Dataset feature dimension"
            )


def _stage_infer_heads(config, ctx):
    arch = _at(config, ("NeuralNetwork", "Architecture"))
    voi = _at(config, ("NeuralNetwork", "Variables_of_interest"))
    if len(voi["type"]) != len(ctx.spans):
        raise ValueError(
            f"config declares {len(voi['type'])} heads but the data's y_loc "
            f"packs {len(ctx.spans)}"
        )
    arch["output_dim"] = [
        _head_dim(kind, span, ctx, arch)
        for kind, span in zip(voi["type"], ctx.spans)
    ]
    arch["output_type"] = voi["type"]
    arch["num_nodes"] = ctx.sample.num_nodes


def _stage_classification_heads(config, ctx):
    """``Variables_of_interest.loss`` names a loss kind a head ("rmse" where
    it says nothing). A "cross_entropy" head is as wide as its number of
    classes (``Variables_of_interest.num_classes``), not as its target, which
    is ONE column holding the class id: the loaders keep the target's
    dimension (``target_dim``), the model takes ``output_dim``, and the id is
    un-scaled in the loss from the dataset's own table (``class_minmax``).
    A token stack (``families.TOKEN_FAMILIES``) reads its input column the same way
    (``token_minmax``). Nothing is written for a config without either."""
    arch = _at(config, ("NeuralNetwork", "Architecture"))
    voi = _at(config, ("NeuralNetwork", "Variables_of_interest"))
    kinds = list(voi.get("loss") or [])
    classify = "cross_entropy" in kinds
    from ..models.families import TOKEN_FAMILIES

    tokens = arch["model_type"] in TOKEN_FAMILIES
    if not classify and not tokens:
        return
    tables = _minmax_tables(_serialized_dataset_path(config))
    if tokens:
        arch["token_minmax"] = tables["node"][
            :, voi["input_node_features"][0]
        ].tolist()
    if not classify:
        return
    if len(kinds) != len(voi["type"]):
        raise ValueError("Variables_of_interest.loss names one kind a head")
    arch["target_dim"] = list(arch["output_dim"])
    arch["head_loss"] = kinds
    arch["class_minmax"] = [None] * len(kinds)
    for i, (kind, head, index) in enumerate(
        zip(kinds, voi["type"], voi["output_index"])
    ):
        if kind != "cross_entropy":
            continue
        if arch["output_dim"][i] != 1:
            raise ValueError(
                f"head {i}: a cross_entropy head's target is one column "
                f"holding the class id, not {arch['output_dim'][i]}"
            )
        arch["output_dim"][i] = int(voi["num_classes"][i])
        arch["class_minmax"][i] = tables[head][:, index].tolist()


def _stage_denormalize(config, ctx):
    voi = _at(config, ("NeuralNetwork", "Variables_of_interest"))
    if voi.get("denormalize_output"):
        update_config_minmax(_serialized_dataset_path(config), voi)
    else:
        voi["denormalize_output"] = False


def _stage_input_dim(config, ctx):
    arch = _at(config, ("NeuralNetwork", "Architecture"))
    voi = _at(config, ("NeuralNetwork", "Variables_of_interest"))
    arch["input_dim"] = len(voi["input_node_features"])


def _stage_pna_degree(config, ctx):
    arch = _at(config, ("NeuralNetwork", "Architecture"))
    arch["pna_deg"] = (
        calculate_PNA_degree(
            ctx.loaders[0].dataset, arch["max_neighbours"]
        ).tolist()
        if arch["model_type"] == "PNA"
        else None
    )


def _stage_edge_dim(config, ctx):
    arch = _at(config, ("NeuralNetwork", "Architecture"))
    features = arch.get("edge_features")
    if features:
        assert arch["model_type"] in _EDGE_FEATURE_MODELS, (
            "edge features are only supported by the "
            f"{'/'.join(sorted(_EDGE_FEATURE_MODELS))} stacks"
        )
        arch["edge_dim"] = len(features)
    elif arch["model_type"] == "CGCNN":
        # CGCNN's gate MLP needs an integer edge width even with no features.
        arch["edge_dim"] = 0
    else:
        arch["edge_dim"] = None


def _stage_defaults(config, ctx):
    for path, key, value in _DEFAULTS:
        _at(config, path).setdefault(key, value)


def _stage_push_head_spec(config, ctx):
    """Loaders need the inferred head spec to emit per-head dense targets,
    and the model family to know what else a batch carries."""
    from ..models.families import POSITION_FAMILIES

    arch = _at(config, ("NeuralNetwork", "Architecture"))
    for loader in ctx.loaders:
        loader.set_head_spec(
            arch["output_type"], arch.get("target_dim", arch["output_dim"])
        )
        loader.edge_dim = arch["edge_dim"]
        loader.with_positions = arch["model_type"] in POSITION_FAMILIES


_PIPELINE = (
    _stage_check_declared_dims,
    _stage_infer_heads,
    _stage_classification_heads,
    _stage_denormalize,
    _stage_input_dim,
    _stage_pna_degree,
    _stage_edge_dim,
    _stage_defaults,
    _stage_push_head_spec,
)


@telemetry.setup_phase("complete_config")
def update_config(config, train_loader, val_loader, test_loader):
    """Complete a user config from the training data (the reference's
    data-driven completion contract; output pinned by golden tests)."""
    loaders = (train_loader, val_loader, test_loader)
    sample = train_loader.dataset[0]
    ctx = _Ctx(
        loaders=loaders,
        sample=sample,
        spans=_head_spans(sample),
        variable_size=check_if_graph_size_variable(
            *(loader.dataset for loader in loaders)
        ),
    )
    for stage in _PIPELINE:
        stage(config, ctx)
    return config


# ------------------------------------------------------------------- minmax
def _serialized_dataset_path(config) -> str:
    """Where the min/max tables live: a GSHD dataset's manifest (train split
    preferred), the configured .pkl directly, or the serialized dataset
    derived from SERIALIZED_DATA_PATH + dataset name (the train shard when
    the config has per-split paths)."""
    from ..datasets.shards import is_gshd_path

    paths = config["Dataset"]["path"]
    first = next(iter(paths.values()))
    if is_gshd_path(first):
        return paths.get("train", first)
    if first.endswith(".pkl"):
        return first
    stem = config["Dataset"]["name"] + ("" if "total" in paths else "_train")
    return os.path.join(
        os.environ["SERIALIZED_DATA_PATH"], "serialized_dataset", stem + ".pkl"
    )


def _minmax_tables(dataset_path: str) -> Dict[str, Any]:
    """The dataset's min/max tables, ``{"node", "graph"}`` -> [2, columns]:
    pickled ahead of the serialized samples — or, for a GSHD dataset, what
    the conversion preserved in the manifest."""
    from ..datasets.shards import is_gshd_path, read_manifest

    if is_gshd_path(dataset_path):
        manifest = read_manifest(dataset_path)
        node = manifest.get("minmax_node_feature")
        graph = manifest.get("minmax_graph_feature")
        if node is None or graph is None:
            raise ValueError(
                f"{dataset_path}: manifest has no min/max tables — re-run "
                "`python -m hydragnn_tpu.datasets convert` from the pickle "
                "corpus to carry them over"
            )
        tables = {"node": np.asarray(node), "graph": np.asarray(graph)}
    else:
        with open(dataset_path, "rb") as f:
            # graftlint: disable=pickle-load-outside-compat(legacy minmax-table shim for pre-GSHD corpora — the shard manifest branch above is the supported path)
            tables = {"node": pickle.load(f), "graph": pickle.load(f)}
    return {k: np.asarray(v) for k, v in tables.items()}


def update_config_minmax(dataset_path: str, config: Dict[str, Any]):
    """Fill x_minmax/y_minmax from the dataset's min/max tables."""
    tables = _minmax_tables(dataset_path)
    config["x_minmax"] = [
        tables["node"][:, i].tolist() for i in config["input_node_features"]
    ]
    y_minmax = []
    for kind, index in zip(config["type"], config["output_index"]):
        if kind not in tables:
            raise ValueError(f"unrecognized head kind: {kind!r}")
        y_minmax.append(tables[kind][:, index].tolist())
    config["y_minmax"] = y_minmax
    return config


# ----------------------------------------------------------------- log name
def get_log_name_config(config: Dict[str, Any]) -> str:
    """Hyperparameter-encoding log/checkpoint directory name (identical string
    to the reference's encoding — checkpoints must resolve across both)."""
    arch = _at(config, ("NeuralNetwork", "Architecture"))
    voi = _at(config, ("NeuralNetwork", "Variables_of_interest"))
    segments = [
        f"{tag}{_at(config, path)[key]}" for tag, path, key in _LOG_NAME_FIELDS
    ]
    segments.append(
        "-node_ft-" + "".join(str(f) for f in voi["input_node_features"])
    )
    segments.append(
        "-task_weights-" + "".join(f"{w}-" for w in arch["task_weights"])
    )
    return "".join(segments)
