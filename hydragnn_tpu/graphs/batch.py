"""Padded, statically-shaped graph batch container — the TPU-native replacement for
torch_geometric's ragged ``Batch`` (reference: hydragnn/preprocess + Base.forward,
/root/reference/hydragnn/models/Base.py:225-269).

Design (jraph-style, but multi-head-target aware):

* A batch packs ``G`` real graphs into fixed-size node/edge/graph arrays
  ``(num_nodes_pad, num_edges_pad, num_graphs_pad)`` so XLA compiles one executable
  per bucket, not per batch.
* At least one padding node and one padding graph are ALWAYS reserved; every padding
  edge connects padding-node → padding-node, so message passing can run unmasked:
  garbage only ever lands on padding rows, which are excluded from batch-norm
  statistics, pooling denominators, and the loss by the masks carried here.
* Multi-head targets are dense per-head arrays (graph heads: ``[num_graphs_pad, dim]``,
  node heads: ``[num_nodes_pad, dim]``) with validity given by ``graph_mask`` /
  ``node_mask``. This replaces the reference's packed ``data.y`` + ``data.y_loc``
  prefix-offset contract (serialized_dataset_loader.py:220-261) and makes the
  per-batch python index math of ``get_head_indices``
  (train_validate_test.py:177-205) disappear into static shapes.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
from flax import struct


@struct.dataclass
class GraphBatch:
    """A fixed-shape batch of graphs.

    Attributes:
      node_features:  [N_pad, F] float — input node features (padding rows zero).
      edge_features:  [E_pad, D] float or None — edge attributes (e.g. lengths).
      senders:        [E_pad] int32 — source node index of each edge.
      receivers:      [E_pad] int32 — destination node index of each edge.
      node_graph:     [N_pad] int32 — graph id owning each node; padding nodes point
                      at a padding graph slot.
      node_mask:      [N_pad] bool — True for real nodes.
      edge_mask:      [E_pad] bool — True for real edges.
      graph_mask:     [G_pad] bool — True for real graphs.
      targets:        tuple, one entry per head: graph-level heads are
                      [G_pad, dim]; node-level heads are [N_pad, dim].
      row_ptr:        [N_pad + 1] int32 or None — CSR boundaries over the
                      destination-sorted ``receivers`` (graphs/csr.py):
                      ``row_ptr[n]`` is the first edge targeting node >= n.
                      Computed once per batch at collation so the sorted-path
                      segment ops consume precomputed boundaries instead of
                      re-searching ids every layer.
      graph_ptr:      [G_pad + 1] int32 or None — the same boundaries over
                      ``node_graph`` (node→graph readout pooling).
      positions:      [N_pad, 3] float32 or None — node coordinates (padding
                      rows zero), carried only for the families that compute
                      their edge geometry inside the step
                      (``models/families.py:POSITION_FAMILIES``). None is an
                      empty subtree: every other family's batch, program and
                      host-to-device bytes are what they were without it.
      num_graphs_pad: static python int (G_pad). Needed as a static segment count.
    """

    node_features: jnp.ndarray
    edge_features: Optional[jnp.ndarray]
    senders: jnp.ndarray
    receivers: jnp.ndarray
    node_graph: jnp.ndarray
    node_mask: jnp.ndarray
    edge_mask: jnp.ndarray
    graph_mask: jnp.ndarray
    targets: Tuple[jnp.ndarray, ...] = ()
    row_ptr: Optional[jnp.ndarray] = None
    graph_ptr: Optional[jnp.ndarray] = None
    positions: Optional[jnp.ndarray] = None
    num_graphs_pad: int = struct.field(pytree_node=False, default=0)

    @property
    def num_nodes_pad(self) -> int:
        return self.node_features.shape[0]

    @property
    def num_edges_pad(self) -> int:
        return self.senders.shape[0]

    def count_real_nodes(self) -> jnp.ndarray:
        return jnp.sum(self.node_mask)

    def count_real_graphs(self) -> jnp.ndarray:
        return jnp.sum(self.graph_mask)
