"""Distributed runtime — XLA-collective replacement for the reference's
torch.distributed/NCCL layer (/root/reference/hydragnn/utils/distributed.py).

The reference wires DDP over NCCL/Gloo with env-var rendezvous (OpenMPI/SLURM/LSF)
and wraps the model (distributed.py:110-226). Here the distribution contract is the
pjit/shard_map train step itself (SURVEY.md §7 pillar 2): this module only owns
process bootstrap (jax.distributed), the device mesh, host barriers, and rank
helpers. There is no model wrapper — gradient allreduce is a psum inside the
compiled step, riding ICI/DCN.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import numpy as np


def init_comm_size_and_rank() -> Tuple[int, int]:
    """World size / rank from the same scheduler env the reference parses
    (OpenMPI, SLURM — distributed.py:77-94), else single process."""
    world_size, world_rank = 1, 0
    if os.getenv("OMPI_COMM_WORLD_SIZE") and os.getenv("OMPI_COMM_WORLD_RANK"):
        world_size = int(os.environ["OMPI_COMM_WORLD_SIZE"])
        world_rank = int(os.environ["OMPI_COMM_WORLD_RANK"])
    elif os.getenv("SLURM_NPROCS") and os.getenv("SLURM_PROCID"):
        world_size = int(os.environ["SLURM_NPROCS"])
        world_rank = int(os.environ["SLURM_PROCID"])
    return world_size, world_rank


def parse_slurm_nodelist(nodelist: str) -> list:
    """Expand a SLURM compressed hostlist into individual node names — the
    rendezvous-address source on SLURM clusters (reference
    /root/reference/hydragnn/utils/distributed.py:43-74, used at :126-132).

    Handles single nodes, bracketed groups, zero-padded ranges, and multiple
    comma-separated blocks: ``"gpu-a,node[01,03-05]"`` →
    ``["gpu-a", "node01", "node03", "node04", "node05"]``.
    """
    # Split on commas OUTSIDE brackets only.
    blocks, depth, cur = [], 0, []
    for ch in nodelist:
        if ch == "[":
            depth += 1
        elif ch == "]":
            depth -= 1
        if ch == "," and depth == 0:
            blocks.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        blocks.append("".join(cur))

    nodes = []
    for block in blocks:
        block = block.strip()
        if block:
            nodes.extend(_expand_hostlist_block(block))
    return nodes


def _expand_hostlist_block(block: str) -> list:
    """Expand ONE hostlist block, recursing past the first bracket group so
    multi-dimension names ("rack[1-2]n[1-4]") and suffixes ("tux[1-2]-ib")
    expand instead of crashing."""
    i = block.find("[")
    if i < 0:
        return [block]
    j = block.index("]", i)
    prefix, group, rest = block[:i], block[i + 1 : j], block[j + 1 :]
    tails = _expand_hostlist_block(rest) if rest else [""]
    out = []
    for item in group.split(","):
        lo, _, hi = item.partition("-")
        if hi:
            width = len(lo)
            mids = [f"{k:0{width}d}" for k in range(int(lo), int(hi) + 1)]
        else:
            mids = [item]
        out.extend(prefix + mid + tail for mid in mids for tail in tails)
    return out


def resolve_coordinator_address() -> str:
    """Coordinator (rendezvous master) address, resolved the way the reference
    picks MASTER_ADDR (distributed.py:120-132): explicit env wins, then the
    LSF batch hostlist (first compute host — LSB_HOSTS[0] is the batch node),
    then the first SLURM node, else localhost. Port from MASTER_PORT or the
    reference's default 8889."""
    addr = os.getenv("MASTER_ADDR")
    if not addr and os.getenv("LSB_HOSTS"):
        hosts = os.environ["LSB_HOSTS"].split()
        addr = hosts[1] if len(hosts) > 1 else hosts[0]
    if not addr and os.getenv("SLURM_NODELIST"):
        nodes = parse_slurm_nodelist(os.environ["SLURM_NODELIST"])
        addr = nodes[0] if nodes else None
    if not addr:
        addr = "127.0.0.1"
    return f"{addr}:{os.getenv('MASTER_PORT', '8889')}"


def get_local_rank() -> int:
    """Process index within its host (reference local-rank selection,
    distributed.py:181-189) — picks this process's slot among the host's local
    devices in multi-process-per-host launches."""
    fam = _local_family()
    if fam is not None:  # a complete rank+size family wins over a lone var
        return fam[0]
    for var in ("OMPI_COMM_WORLD_LOCAL_RANK", "SLURM_LOCALID"):
        if os.getenv(var):
            return int(os.environ[var])
    return 0


def _tasks_per_node_counts(val: str) -> list:
    """Per-node task counts from SLURM_NTASKS_PER_NODE's compressed grammar:
    "4" → [4]; "4(x2)" → [4, 4]; "4(x2),3" → [4, 4, 3] (heterogeneous)."""
    counts = []
    for part in val.split(","):
        n, _, rep = part.partition("(x")
        counts.extend([int(n)] * (int(rep.rstrip(")")) if rep else 1))
    return counts


def _local_family():
    """(local_rank, max tasks-per-node) read from ONE launcher family — mixing
    (e.g. SLURM size with an OMPI rank) silently misplaces processes. None if
    no family is fully present or its size grammar doesn't parse."""
    for rank_var, size_var in (
        ("OMPI_COMM_WORLD_LOCAL_RANK", "OMPI_COMM_WORLD_LOCAL_SIZE"),
        ("SLURM_LOCALID", "SLURM_NTASKS_PER_NODE"),
    ):
        if os.getenv(rank_var) and os.getenv(size_var):
            try:
                counts = _tasks_per_node_counts(os.environ[size_var])
                return int(os.environ[rank_var]), max(counts)
            except ValueError:
                return None
    return None


def get_local_size() -> int:
    """Processes launched per host — the max over nodes on heterogeneous
    allocations (1 when the scheduler doesn't say or the value is garbled)."""
    fam = _local_family()
    if fam is not None:
        return fam[1]
    for var in ("OMPI_COMM_WORLD_LOCAL_SIZE", "SLURM_NTASKS_PER_NODE"):
        if os.getenv(var):
            try:
                return max(_tasks_per_node_counts(os.environ[var]))
            except ValueError:
                return 1
    return 1


def _local_device_slot():
    """Local-device slot for this process, or None for JAX's default (claim
    all local devices). Slot mode only when the launcher says several
    processes share a host (local rank > 0 is itself proof)."""
    fam = _local_family()
    if fam is not None and (fam[0] > 0 or fam[1] > 1):
        return fam[0]
    return None


def tpu_expected() -> bool:
    """Whether this process will come up on the TPU backend, decided WITHOUT
    initialising it (a process that has touched the backend holds the chips,
    and the checks that call this must run before that): the platform list
    (``JAX_PLATFORMS`` / ``jax_platforms``) names ``tpu``, or names nothing
    and a TPU chip is attached over PCI — JAX's own probe."""
    platforms = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    if platforms:
        return "tpu" in platforms.split(",")
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0] > 0


def _distributed_active() -> bool:
    """Whether jax.distributed.initialize already ran — checked WITHOUT
    touching jax.process_count(), which would initialize the XLA backend and
    make a later initialize() impossible."""
    try:
        from jax._src import distributed as _dist

        return _dist.global_state.client is not None
    except Exception:
        return False


def setup_ddp(coordinator_address: Optional[str] = None) -> Tuple[int, int]:
    """Process-group bootstrap (reference setup_ddp, distributed.py:110-158).

    Multi-process: jax.distributed.initialize with scheduler-env rendezvous.
    Single-process (or rendezvous env missing): sequential fallback, like the
    reference's try/except (distributed.py:134-157).
    """
    world_size, world_rank = init_comm_size_and_rank()
    if world_size > 1 and not _distributed_active():
        kwargs = {}
        slot = _local_device_slot()
        if slot is not None:
            if tpu_expected() and not os.getenv("TPU_VISIBLE_CHIPS"):
                # local_device_ids restricts CUDA/ROCm devices only. On TPU
                # every rank would claim every chip of the host: the first to
                # start takes them and the rest hang.
                raise RuntimeError(
                    f"rank {world_rank}: {get_local_size()} processes share "
                    "this TPU host, but a TPU chip can only be hidden from a "
                    "process BEFORE JAX starts. Launch one process per host "
                    "(it drives all local chips through the mesh), or have "
                    "the launcher set TPU_VISIBLE_CHIPS=<local rank> (with "
                    "the matching TPU_PROCESS_BOUNDS / "
                    "TPU_CHIPS_PER_PROCESS_BOUNDS) for each rank."
                )
            # Reference 1-rank-per-device placement (distributed.py:181-189):
            # with several processes per host each claims its own
            # local-device slot instead of all of them.
            kwargs["local_device_ids"] = [slot]
        try:
            if coordinator_address is None:
                coordinator_address = resolve_coordinator_address()
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=world_size,
                process_id=world_rank,
                **kwargs,
            )
        except Exception as e:
            # DIVERGENCE from the reference's silent sequential fallback
            # (distributed.py:155-157): once the scheduler env promised
            # world_size > 1, peers are already connecting to the coordinator
            # — one rank quietly going sequential leaves the rest blocked at
            # rendezvous until timeout. Fail loudly instead.
            raise RuntimeError(
                f"jax.distributed.initialize failed for rank {world_rank}/"
                f"{world_size} at {coordinator_address}: {e}. Check the "
                "rendezvous env (MASTER_ADDR/LSB_HOSTS/SLURM_NODELIST) and "
                "that the local device slot exists on this host."
            ) from e
    return get_comm_size_and_rank()


def get_comm_size_and_rank() -> Tuple[int, int]:
    return jax.process_count(), jax.process_index()


def barrier(name: str = "hydragnn_barrier") -> None:
    """Host-level barrier (reference dist.barrier around data prep/log dirs)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def get_device_list():
    return jax.local_devices()


def mesh_descriptor(mesh) -> str:
    """Canonical axis-layout string of a mesh — ``"data:4xgraph:2"``. The
    graftmesh CacheKey component (docs/COMPILE_CACHE.md): two shard_map
    programs over different axis factorizations of the SAME device count
    compile different collectives and must never hydrate each other."""
    return "x".join(f"{name}:{int(size)}" for name, size in mesh.shape.items())


def config_graph_axis(config: dict) -> int:
    """The JSON config's edge-sharding request — ``Training.graph_axis``
    (>1 shards each graph's edges over that many devices; absent/falsy means
    1). ONE definition consumed by run_training AND run_prediction so the
    same config can never build different meshes for the two."""
    return int(
        config.get("NeuralNetwork", {}).get("Training", {}).get("graph_axis", 1)
        or 1
    )


def make_mesh(
    data_axis: Optional[int] = None,
    graph_axis: int = 1,
    devices=None,
) -> jax.sharding.Mesh:
    """Device mesh for the train step: 'data' (batch/DP) × 'graph'
    (intra-graph node/edge sharding — the long-context analog axis).

    ``devices``: explicit device list (e.g. ``jax.devices("cpu")`` to build a
    virtual CPU mesh on a TPU-attached host); defaults to ``jax.devices()``.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    if graph_axis < 1 or graph_axis > n:
        raise ValueError(
            f"graph_axis={graph_axis} must be in [1, {n}] (device count)"
        )
    if data_axis is None:
        if n % graph_axis != 0:
            raise ValueError(
                f"device count {n} is not divisible by graph_axis={graph_axis}; "
                "pass data_axis explicitly to use a subset of devices"
            )
        data_axis = n // graph_axis
    if data_axis * graph_axis > n:
        raise ValueError(
            f"mesh {data_axis}x{graph_axis} needs {data_axis * graph_axis} "
            f"devices but only {n} are available"
        )
    grid = np.asarray(devices[: data_axis * graph_axis]).reshape(
        data_axis, graph_axis
    )
    return jax.sharding.Mesh(grid, ("data", "graph"))
