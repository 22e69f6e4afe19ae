"""Dataset loading & splitting orchestration
(reference /root/reference/hydragnn/preprocess/load_data.py:34-183).

Flow: raw→serialized conversion if paths are not .pkl (rank 0 + barrier) →
"total"→train/val/test pkl split → per-split SerializedDataLoader →
GraphDataLoader construction (sharded per process when running multi-process,
replacing DistributedSampler)."""

from __future__ import annotations

import os
import pickle
from typing import Dict, Tuple

from ..parallel.distributed import barrier, get_comm_size_and_rank
from ..telemetry import graftel as telemetry
from ..utils.time_utils import Timer
from .dataloader import GraphDataLoader, share_eval_pads
from .raw_loader import RawDataLoader
from .serialized_loader import SerializedDataLoader
from .splitting import split_dataset


@telemetry.setup_phase("load_data")
def dataset_loading_and_splitting(config: Dict):
    # Streaming data plane (docs/DATA_PLANE.md): when every split path is a
    # GSHD dataset, nothing is materialized in host RAM — the loaders stream
    # shards through the decode-ahead ring. This branch must precede the
    # raw/pickle plumbing below, which assumes pickle-era paths.
    paths = config["Dataset"]["path"]
    from ..datasets.shards import is_gshd_path

    if "total" not in paths and all(is_gshd_path(p) for p in paths.values()):
        return create_streaming_dataloaders(config)
    if not list(config["Dataset"]["path"].values())[0].endswith(".pkl"):
        transform_raw_data_to_serialized(config["Dataset"])
    if "total" in config["Dataset"]["path"].keys():
        total_to_train_val_test_pkls(config)
    trainset, valset, testset = load_train_val_test_sets(config)
    # Config-driven fault drills (Training.faults) must reach the LOADERS
    # too — corrupt_sample injection happens at loader construction, and the
    # loaders only consult the HYDRAGNN_FAULTS env on their own. Env wins
    # when both are set (same precedence as run_training's driver plan).
    import os as _os

    from ..faults.plan import FaultPlan

    fault_plan = None
    spec = config["NeuralNetwork"]["Training"].get("faults")
    if spec and not _os.environ.get("HYDRAGNN_FAULTS"):
        fault_plan = FaultPlan(spec)
    return create_dataloaders(
        trainset,
        valset,
        testset,
        batch_size=config["NeuralNetwork"]["Training"]["batch_size"],
        num_buckets=config["Dataset"].get("num_buckets", 1),
        reshuffle=config["NeuralNetwork"]["Training"].get("reshuffle", "sample"),
        # Corrupt-sample quarantine budget (docs/FAULT_TOLERANCE.md); 0 =
        # no validation, the historical behavior.
        skip_budget=config["Dataset"].get("skip_budget", 0),
        fault_plan=fault_plan,
        # Graph packing + pad round-up ladder (docs/INPUT_PIPELINE.md
        # "Graph packing"): packing densifies train batches by FFD
        # bin-packing; ladder_step names the pad round-up ("pow2",
        # "mult64"); absent, the static pad rounds up to the kernels' tile
        # (graphs/collate.py compute_pad_sizes_from_counts).
        packing=bool(config["Dataset"].get("packing", False)),
        ladder_step=config["Dataset"].get("ladder_step"),
    )


def create_dataloaders(trainset, valset, testset, batch_size, num_buckets=1,
                       reshuffle="sample", skip_budget=0, fault_plan=None,
                       packing=False, ladder_step=None):
    """Three GraphDataLoaders; multi-process runs shard every split by process
    (the DistributedSampler analog). Returns (train, val, test, sampler_list) for
    reference API parity — the loaders are their own samplers here.

    Documented divergence: the reference shuffles val/test too
    (load_data.py:75-84), which silently misaligns its Visualizer's
    dataset-order node features with eval-order predictions. Eval loaders
    here keep dataset order — shuffling eval batches has no training effect.

    Documented divergence: ``batch_size`` is the GLOBAL batch — each process
    takes batch_size/world_size graphs per step, so the optimizer trajectory
    (steps per epoch, gradient noise scale) is invariant under the process
    count. The reference's batch_size is per-rank (DistributedSampler halves
    steps and doubles the effective batch at 2 ranks), which shifts
    convergence for the same config as ranks change."""
    world_size, rank = get_comm_size_and_rank()
    shard_batch = max(1, -(-batch_size // world_size))
    if shard_batch * world_size != batch_size:
        print(
            f"WARNING: batch_size {batch_size} is not divisible by "
            f"{world_size} processes; using {shard_batch}/process "
            f"(effective global batch {shard_batch * world_size})"
        )
    loaders = []
    for ds, shuffle in ((trainset, True), (valset, False), (testset, False)):
        loaders.append(
            GraphDataLoader(
                ds,
                batch_size=shard_batch,
                shuffle=shuffle,
                num_shards=world_size,
                shard_rank=rank,
                # Bucketing reorders iteration bucket-major; only the train
                # loader may do that — eval loaders keep exact dataset order
                # (run_prediction rows must align with the test set).
                num_buckets=num_buckets if shuffle else 1,
                # Per-epoch reshuffle granularity (Training.reshuffle):
                # "sample" = reference DistributedSampler parity; "batch"
                # freezes membership so collation + device transfer cache
                # across epochs (train loader only — eval never shuffles).
                reshuffle=reshuffle if shuffle else "sample",
                skip_budget=skip_budget,
                fault_plan=fault_plan,
                # Packing reorders batch membership by size — train only;
                # eval loaders must keep exact dataset order
                # (run_prediction rows align with the test set).
                packing=packing if shuffle else False,
                ladder_step=ladder_step,
            )
        )
    train_loader, val_loader, test_loader = loaders
    if ladder_step is None:
        share_eval_pads(val_loader, test_loader)
    sampler_list = loaders if world_size > 1 else []
    return train_loader, val_loader, test_loader, sampler_list


def create_streaming_dataloaders(config: Dict):
    """Three StreamingGraphLoaders over GSHD split datasets — the out-of-core
    analog of ``create_dataloaders``, with identical split/sharding/knob
    semantics (global batch divided across processes, train-only buckets/
    packing/reshuffle, eval loaders in exact dataset order). Corruption
    handling is shard-granular (``Dataset.skip_budget`` counts shards);
    ``Training.faults`` corrupt_sample injection is an in-memory-loader drill
    and does not apply — on-disk corruption is drilled by flipping real shard
    bytes (benchmarks/stream_bench.py)."""
    from ..datasets.stream import StreamingGraphLoader

    world_size, rank = get_comm_size_and_rank()
    batch_size = config["NeuralNetwork"]["Training"]["batch_size"]
    shard_batch = max(1, -(-batch_size // world_size))
    if shard_batch * world_size != batch_size:
        print(
            f"WARNING: batch_size {batch_size} is not divisible by "
            f"{world_size} processes; using {shard_batch}/process "
            f"(effective global batch {shard_batch * world_size})"
        )
    ds = config["Dataset"]
    reshuffle = config["NeuralNetwork"]["Training"].get("reshuffle", "sample")
    loaders = []
    for split, shuffle in (("train", True), ("validate", False), ("test", False)):
        loaders.append(
            StreamingGraphLoader(
                ds["path"][split],
                batch_size=shard_batch,
                shuffle=shuffle,
                num_shards=world_size,
                shard_rank=rank,
                num_buckets=ds.get("num_buckets", 1) if shuffle else 1,
                reshuffle=reshuffle if shuffle else "sample",
                skip_budget=ds.get("skip_budget", 0),
                packing=bool(ds.get("packing", False)) if shuffle else False,
                ladder_step=ds.get("ladder_step"),
                ring_depth=ds.get("ring_depth", 2),
                resident_shards=ds.get("resident_shards", 8),
            )
        )
    train_loader, val_loader, test_loader = loaders
    if ds.get("ladder_step") is None:
        share_eval_pads(val_loader, test_loader)
    sampler_list = loaders if world_size > 1 else []
    return train_loader, val_loader, test_loader, sampler_list


def load_train_val_test_sets(config: Dict):
    timer = Timer("load_data")
    timer.start()
    dataset_list = []
    datasetname_list = []
    for dataset_name, raw_data_path in config["Dataset"]["path"].items():
        if raw_data_path.endswith(".pkl"):
            files_dir = raw_data_path
        else:
            files_dir = (
                f"{os.environ['SERIALIZED_DATA_PATH']}/serialized_dataset/"
                f"{config['Dataset']['name']}_{dataset_name}.pkl"
            )
        loader = SerializedDataLoader(config)
        dataset_list.append(loader.load_serialized_data(dataset_path=files_dir))
        datasetname_list.append(dataset_name)
    trainset = dataset_list[datasetname_list.index("train")]
    valset = dataset_list[datasetname_list.index("validate")]
    testset = dataset_list[datasetname_list.index("test")]
    timer.stop()
    return trainset, valset, testset


def transform_raw_data_to_serialized(dataset_config: Dict):
    _, rank = get_comm_size_and_rank()
    if rank == 0:
        loader = RawDataLoader(dataset_config)
        loader.load_raw_data()
    barrier("raw_to_serialized")


def total_to_train_val_test_pkls(config: Dict):
    _, rank = get_comm_size_and_rank()
    if list(config["Dataset"]["path"].values())[0].endswith(".pkl"):
        file_dir = config["Dataset"]["path"]["total"]
    else:
        file_dir = (
            f"{os.environ['SERIALIZED_DATA_PATH']}/serialized_dataset/"
            f"{config['Dataset']['name']}.pkl"
        )
    from .serialized_loader import warn_pickle_corpus_once

    warn_pickle_corpus_once()
    with open(file_dir, "rb") as f:
        minmax_node_feature = pickle.load(f)  # graftlint: disable=pickle-load-outside-compat(legacy HydraGNN corpus shim gated behind warn_pickle_corpus_once — the GSHD shard path is the supported reader)
        minmax_graph_feature = pickle.load(f)  # graftlint: disable=pickle-load-outside-compat(legacy corpus shim, see above)
        dataset_total = pickle.load(f)  # graftlint: disable=pickle-load-outside-compat(legacy corpus shim, see above)

    trainset, valset, testset = split_dataset(
        dataset=dataset_total,
        perc_train=config["NeuralNetwork"]["Training"]["perc_train"],
        stratify_splitting=config["Dataset"]["compositional_stratified_splitting"],
    )
    serialized_dir = os.path.dirname(file_dir)
    config["Dataset"]["path"] = {}
    for dataset_type, dataset in zip(
        ["train", "validate", "test"], [trainset, valset, testset]
    ):
        serial_data_name = config["Dataset"]["name"] + "_" + dataset_type + ".pkl"
        config["Dataset"]["path"][dataset_type] = (
            serialized_dir + "/" + serial_data_name
        )
        if rank == 0:
            with open(os.path.join(serialized_dir, serial_data_name), "wb") as f:
                pickle.dump(minmax_node_feature, f)
                pickle.dump(minmax_graph_feature, f)
                pickle.dump(dataset, f)
    barrier("total_split")
