"""HydraGNN multi-headed GNN — the flax re-design of the reference architecture
core (/root/reference/hydragnn/models/Base.py:20-372 plus the per-conv Stack
subclasses). One module covers all eight families; the conv flavor is a static
field, so each (conv_type, dims) combination compiles to one XLA program.

Architecture (mirrors reference semantics under padding):
  encoder:   batch → [N, enc_dim]. The six classic families:
             num_conv_layers × [conv → MaskedBatchNorm → ReLU] over ONE
             array. PaiNN: num_conv_layers × [message → update] over a scalar
             and a vector state, the edge geometry computed once from
             ``batch.positions``; no norm, no ReLU (models/painn.py). The
             read-out and the heads read the scalar state. A token stack
             (models/families.py ``TOKEN_STACKS``): a token embedding,
             num_conv_layers × the family's block [operator → feed-forward]
             with RMSNorm and a residual round each, a final RMSNorm: no
             edge list is read.
  readout:   masked segment-mean over nodes per graph (global_mean_pool analog)
  heads:     graph heads = shared MLP ("graph_shared") + per-head MLP;
             node heads = shared MLPNode ('mlp' / 'mlp_per_node') or a conv chain
             ('conv'), exactly the reference's three node-head modes
             (Base._multihead, Base.py:152-223).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..graphs.batch import GraphBatch
from ..ops import aggregate
from ..ops import segment as seg
from ..telemetry import scopes
from .layers import MLP, MaskedBatchNorm
from . import painn
from .convs import CGConv, GATv2Conv, GINConv, MFCConv, PNAConv, SAGEConv
from .families import CONV_TYPES, POSITION_FAMILIES, TOKEN_STACKS
from .token_common import RMSNorm, token_ids

# Rows of a block of ``HydraGNN.score_tokens``: a class head's logits exist a
# block at a time ([512, classes]), never as [N, classes].
LOGPROB_BLOCK = 512


class MLPNode(nn.Module):
    """Node-level decoder head (reference MLPNode, Base.py:321-372).

    'mlp': one MLP shared across nodes. 'mlp_per_node': a distinct MLP per node
    slot — only valid for fixed-size graphs; implemented as degree-style weight
    gather over the node's position inside its graph rather than the reference's
    python loop over node indices."""

    hidden_dims: Tuple[int, ...]
    out_dim: int
    node_type: str  # 'mlp' | 'mlp_per_node'
    num_nodes: Optional[int] = None
    precision: Any = None  # matmul precision, as ``MLP.precision``

    @nn.compact
    def __call__(self, x: jnp.ndarray, batch: GraphBatch) -> jnp.ndarray:
        dims = tuple(self.hidden_dims) + (self.out_dim,)
        if self.node_type == "mlp":
            return MLP(dims, precision=self.precision, name="mlp")(x)
        assert self.num_nodes is not None, "mlp_per_node requires fixed graph size"
        n, f = x.shape
        # Node position within its graph: nodes are contiguous per graph by
        # collation, so pos = arange - start_of_my_graph.
        with jax.named_scope(scopes.POOL):
            counts = seg.segment_count(batch.node_graph, batch.num_graphs_pad)
            starts = jnp.concatenate([jnp.zeros(1), jnp.cumsum(counts)[:-1]])
            pos = (jnp.arange(n) - starts[batch.node_graph]).astype(jnp.int32)
            pos = jnp.clip(pos, 0, self.num_nodes - 1)
        h = x
        in_dim = f
        for li, d in enumerate(dims):
            w = self.param(
                f"w_{li}", nn.initializers.lecun_normal(), (self.num_nodes, in_dim, d)
            )
            b = self.param(f"b_{li}", nn.initializers.zeros, (self.num_nodes, d))
            with jax.named_scope(scopes.GATHER):  # per-slot weight rows
                w_n, b_n = w[pos], b[pos]
            h = jnp.einsum("nf,nfo->no", h, w_n, precision=self.precision) + b_n
            if li < len(dims) - 1:
                h = nn.relu(h)
            in_dim = d
        return h


class HydraGNN(nn.Module):
    """Static configuration mirrors create_model's signature
    (/root/reference/hydragnn/models/create.py:55-178)."""

    conv_type: str
    input_dim: int
    hidden_dim: int
    output_dim: Tuple[int, ...]
    output_type: Tuple[str, ...]
    config_heads: Dict[str, Any]
    num_conv_layers: int
    task_weights: Tuple[float, ...] = ()  # normalized to Σ|w|=1 (Base.py:74-75)
    freeze_conv: bool = False
    dropout: float = 0.25
    num_nodes: Optional[int] = None
    initial_bias: Optional[float] = None
    ilossweights_nll: int = 0
    # Mesh axis name for edge-sharded graph parallelism (None = off).
    graph_axis: Optional[str] = None
    # Mixed precision: 'bfloat16' runs the network in bf16 on the MXU with
    # float32 master weights, loss, and BatchNorm statistics (trainer casts;
    # None = full float32). Not a reference feature — TPU-native addition.
    compute_dtype: Optional[str] = None
    # Rematerialize conv layers in the backward pass (jax.checkpoint):
    # activations of the encoder are recomputed instead of stored, trading
    # FLOPs for HBM on large graphs. TPU-native addition.
    remat: bool = False
    # Conv-family-specific static parameters.
    edge_dim: Optional[int] = None
    pna_deg_avg_log: float = 1.0
    pna_deg_avg_lin: float = 1.0
    mfc_max_degree: int = 10
    gat_heads: int = 6  # create.py:113
    gat_negative_slope: float = 0.05  # create.py:114
    # PaiNN: the cutoff (Architecture.radius) and the number of radial basis
    # functions (Architecture.num_radial).
    radius: Optional[float] = None
    num_radial: Optional[int] = None
    # A token stack (``TOKEN_STACKS``): its sizes, keyed as the source names
    # them (the family's own dataclass, built ``from_arch``; what the shared
    # code reads off it: models/token_common.py). None for the others.
    token_cfg: Optional[Any] = None
    # Loss kind a head ("rmse" | "cross_entropy"; () = rmse throughout) and,
    # for a cross-entropy head, the dataset's (min, max) of its target column,
    # from which the class ids are un-scaled (models/loss.py).
    head_loss: Tuple[str, ...] = ()
    class_minmax: Tuple[Any, ...] = ()

    @property
    def counts_routing(self) -> bool:
        """Whether a step sows counters (the routed experts' loads): the train
        step then asks for the collection (train/trainer.py)."""
        cfg = self.token_cfg
        return cfg is not None and any(
            cfg.routed(i) for i in range(self.num_conv_layers)
        )

    # The four names graftbench/families/*.py read the sizes under (the
    # benchmark's files are not a program PR's to edit). They go when the
    # families read ``model.token_cfg``: ROADMAP D25.
    @property
    def lfm2(self):
        return self.token_cfg if self.conv_type == "LFM2" else None

    @property
    def laguna(self):
        return self.token_cfg if self.conv_type == "LAGUNA" else None

    @property
    def mistral4(self):
        return self.token_cfg if self.conv_type == "MISTRAL4" else None

    @property
    def mellum(self):
        return self.token_cfg if self.conv_type == "MELLUM" else None

    @property
    def tied_head(self) -> bool:
        """Whether the class head is the token embedding transposed (a token
        stack whose sizes say ``tie_word_embeddings``): the one node head then
        has no matrix and no bias of its own, its logits are ``h E^T``."""
        cfg = self.token_cfg
        return cfg is not None and getattr(cfg, "tie_word_embeddings", False)

    @property
    def use_edge_attr(self) -> bool:
        return self.edge_dim is not None and self.edge_dim > 0

    @property
    def head_precision(self):
        """Matmul precision of the heads: PaiNN's pooled state passes no norm
        layer (rms 1.1-1.4 on outputs of O(0.3) at F 128), and one bf16 pass
        over it in the shared and head MLPs alone reads 2e-3 to 6.7e-3 from
        the plain reference (models/painn.py has the chip's readings)."""
        return painn.PRECISION if self.conv_type == "PAINN" else None

    @property
    def needs_positions(self) -> bool:
        """Whether the batch must carry ``positions`` (the loaders and the
        serving engine ask)."""
        return self.conv_type in POSITION_FAMILIES

    @property
    def enc_dim(self) -> int:
        """Width of the encoder output (hidden_dim except CGCNN, which preserves
        channels — CGCNNStack.py:31-42)."""
        return self.input_dim if self.conv_type == "CGCNN" else self.hidden_dim

    def _make_conv(self, in_dim: int, out_dim: int, name: str, concat: bool = True):
        ct = self.conv_type
        ax = self.graph_axis

        def cls(c):
            # static_argnums: `train` (last positional arg) is a python bool.
            return nn.remat(c, static_argnums=(7,)) if self.remat else c

        if ct == "SAGE":
            return cls(SAGEConv)(out_dim, axis_name=ax, name=name)
        if ct == "GIN":
            return cls(GINConv)(out_dim, axis_name=ax, name=name)
        if ct == "MFC":
            return cls(MFCConv)(out_dim, self.mfc_max_degree, axis_name=ax, name=name)
        if ct == "GAT":
            return cls(GATv2Conv)(
                out_dim,
                heads=self.gat_heads,
                negative_slope=self.gat_negative_slope,
                concat=concat,
                dropout=self.dropout,
                axis_name=ax,
                name=name,
            )
        if ct == "CGCNN":
            return cls(CGConv)(edge_dim=self.edge_dim or 0, axis_name=ax, name=name)
        if ct == "PNA":
            return cls(PNAConv)(
                out_dim,
                deg_avg_log=self.pna_deg_avg_log,
                deg_avg_lin=self.pna_deg_avg_lin,
                edge_dim=self.edge_dim,
                axis_name=ax,
                name=name,
            )
        raise ValueError(f"Unknown conv_type {ct}")

    # The encoder's helpers are ``nowrap``: flax would write a wrapped
    # method's name into every operation's name stack, and the module column
    # of the device-time table (graftbench/xplane_scopes.py) reads that stack.
    @nn.nowrap
    def _setup_conv_encoder(self):
        """The classic families: ``conv_<i>`` and ``bn_<i>``."""
        gat = self.conv_type == "GAT"
        h = self.gat_heads

        # --- encoder (Base._init_conv, Base.py:99-105; GAT override
        # GATStack.py:35-46: concat widths on all but the last layer) ---
        convs, bns = [], []
        if gat:
            convs.append(self._make_conv(self.input_dim, self.hidden_dim, "conv_0"))
            bns.append(MaskedBatchNorm(self.hidden_dim * h, name="bn_0"))
            for i in range(1, max(self.num_conv_layers - 1, 1)):
                convs.append(
                    self._make_conv(self.hidden_dim * h, self.hidden_dim, f"conv_{i}")
                )
                bns.append(MaskedBatchNorm(self.hidden_dim * h, name=f"bn_{i}"))
            i = max(self.num_conv_layers - 1, 1)
            convs.append(
                self._make_conv(
                    self.hidden_dim * h, self.hidden_dim, f"conv_{i}", concat=False
                )
            )
            bns.append(MaskedBatchNorm(self.hidden_dim, name=f"bn_{i}"))
        else:
            dims = [self.input_dim] + [self.enc_dim] * self.num_conv_layers
            for i in range(self.num_conv_layers):
                convs.append(self._make_conv(dims[i], dims[i + 1], f"conv_{i}"))
                bns.append(MaskedBatchNorm(dims[i + 1], name=f"bn_{i}"))
        self.convs = convs
        self.batch_norms = bns

    @nn.nowrap
    def _setup_token_encoder(self):
        """A token stack: the token embedding, one block a layer (``conv_<i>``,
        so that ``freeze_conv_layers`` freezes them as the others), the final
        norm."""
        block, cfg = TOKEN_STACKS[self.conv_type][1], self.token_cfg
        if self.remat:
            block = nn.remat(block)
        self.conv_embed = nn.Embed(cfg.vocab_size, self.hidden_dim)
        self.convs = [
            block(self.hidden_dim, cfg, i, name=f"conv_{i}")
            for i in range(self.num_conv_layers)
        ]
        self.conv_norm = RMSNorm(cfg.norm_eps)

    @nn.nowrap
    def _setup_painn_encoder(self):
        """PaiNN: a Dense of the input features for s⁰ and one block a layer.
        The names keep the ``conv_`` prefix so that ``freeze_conv_layers``
        (utils/optimizer.py) freezes the whole encoder, as for the others."""
        block = nn.remat(painn.PaiNNBlock) if self.remat else painn.PaiNNBlock
        self.conv_embed = painn.Dense(self.hidden_dim)
        self.convs = [
            block(self.hidden_dim, axis_name=self.graph_axis, name=f"conv_{i}")
            for i in range(self.num_conv_layers)
        ]

    def setup(self):
        if self.conv_type not in CONV_TYPES:
            raise ValueError(f"Unknown conv_type {self.conv_type}")
        gat = self.conv_type == "GAT"
        h = self.gat_heads
        if self.conv_type == "PAINN":
            self._setup_painn_encoder()
        elif self.conv_type in TOKEN_STACKS:
            self._setup_token_encoder()
        else:
            self._setup_conv_encoder()

        node_head_idx = [i for i, t in enumerate(self.output_type) if t == "node"]
        self.node_nn_type = (
            self.config_heads.get("node", {}).get("type") if node_head_idx else None
        )

        # --- node-head conv chain (Base._init_node_conv, Base.py:120-150; GAT
        # override GATStack.py:48-86; CGCNN forbids 'conv' CGCNNStack.py:53-75) ---
        nch, ncb, nco, ncob = [], [], [], []
        if node_head_idx and self.node_nn_type == "conv":
            if self.conv_type in ("CGCNN", "PAINN", *TOKEN_STACKS):
                # CGCNN preserves channels; a PaiNN block has two states and
                # a token stack's block a residual stream: no width to narrow
                # to a head's output.
                raise ValueError(
                    f'"conv" node decoder is not supported for {self.conv_type}; '
                    'use "mlp" or "mlp_per_node"'
                )
            hd = list(self.config_heads["node"]["dim_headlayers"])
            nlayers = self.config_heads["node"]["num_headlayers"]
            # GAT concat widens hidden chain widths by `heads` and disables
            # concat on the output conv (GATStack.py:48-86); mult=1 otherwise.
            mult = h if gat else 1
            nch.append(self._make_conv(self.enc_dim, hd[0], "node_conv_0"))
            ncb.append(MaskedBatchNorm(hd[0] * mult, name="node_bn_0"))
            for i in range(nlayers - 1):
                nch.append(
                    self._make_conv(hd[i] * mult, hd[i + 1], f"node_conv_{i + 1}")
                )
                ncb.append(MaskedBatchNorm(hd[i + 1] * mult, name=f"node_bn_{i + 1}"))
            for k, ih in enumerate(node_head_idx):
                nco.append(
                    self._make_conv(
                        hd[-1] * mult,
                        self.output_dim[ih],
                        f"node_out_conv_{k}",
                        concat=False,
                    )
                )
                ncob.append(
                    MaskedBatchNorm(self.output_dim[ih], name=f"node_out_bn_{k}")
                )
        self.convs_node_hidden = nch
        self.batch_norms_node_hidden = ncb
        self.convs_node_output = nco
        self.batch_norms_node_output = ncob

        # --- heads (Base._multihead, Base.py:152-223) ---
        if "graph" in self.config_heads and any(
            t == "graph" for t in self.output_type
        ):
            gcfg = self.config_heads["graph"]
            # shared_layout "framework" (default): ReLU between every pair of
            # shared Linears. "reference": the reference's exact Sequential
            # grammar — NO inner ReLU, only the trailing one (Base.py:155-162)
            # — required for exact forward parity of imported torch
            # checkpoints with num_sharedlayers > 1 (utils/torch_import.py).
            layout = gcfg.get("shared_layout", "framework")
            if layout not in ("framework", "reference"):
                raise ValueError(
                    f"output_heads.graph.shared_layout must be 'framework' "
                    f"or 'reference', got {layout!r}"
                )
            self.graph_shared = MLP(
                tuple([gcfg["dim_sharedlayers"]] * gcfg["num_sharedlayers"]),
                activate_final=True,
                inner_activation=layout != "reference",
                precision=self.head_precision,
                name="graph_shared",
            )

        heads = []
        for ihead, (htype, hdim) in enumerate(zip(self.output_type, self.output_dim)):
            if htype == "graph":
                gcfg = self.config_heads["graph"]
                dims = tuple(gcfg["dim_headlayers"][: gcfg["num_headlayers"]]) + (
                    hdim + self.ilossweights_nll,
                )
                heads.append(
                    MLP(
                        dims,
                        final_bias_value=self.initial_bias,
                        precision=self.head_precision,
                        name=f"head_{ihead}",
                    )
                )
            elif htype == "node":
                if self.tied_head:
                    ncfg = self.config_heads["node"]
                    if (
                        self.node_nn_type != "mlp" or ncfg["num_headlayers"]
                        or hdim != self.token_cfg.vocab_size
                        or len(self.output_type) != 1
                    ):
                        raise ValueError(
                            f"{self.conv_type} ties its one class head to the "
                            "embedding: one 'mlp' node head of no hidden layer, "
                            f"as wide as the vocabulary ({self.token_cfg.vocab_size})"
                        )
                    heads.append(None)  # the embedding's own table: _node_head
                elif self.node_nn_type in ("mlp", "mlp_per_node"):
                    ncfg = self.config_heads["node"]
                    heads.append(
                        MLPNode(
                            tuple(ncfg["dim_headlayers"][: ncfg["num_headlayers"]]),
                            hdim,
                            self.node_nn_type,
                            num_nodes=self.num_nodes,
                            precision=self.head_precision,
                            name=f"head_{ihead}",
                        )
                    )
                elif self.node_nn_type == "conv":
                    heads.append(None)  # handled via convs_node_* chains
                else:
                    raise ValueError(
                        f"Unknown node head type {self.node_nn_type}; use 'mlp', "
                        "'mlp_per_node' or 'conv'"
                    )
            else:
                raise ValueError(f"Unknown head type {htype}")
        self.heads_nn = heads

    @nn.nowrap
    def _encode_convs(self, batch: GraphBatch, train: bool):
        x = batch.node_features
        edge_attr = batch.edge_features if self.use_edge_attr else None
        # Reference encoder loop: x = relu(bn(conv(x))) (Base.py:236-243).
        for conv, bn in zip(self.convs, self.batch_norms):
            # train passed positionally: nn.remat static_argnums needs it
            # positional to keep the python-bool branch static. row_ptr (the
            # CSR batch contract) rides behind it so every layer consumes
            # collation's precomputed segment boundaries.
            c = conv(
                x,
                batch.senders,
                batch.receivers,
                edge_attr,
                batch.edge_mask,
                batch.node_mask,
                train,
                batch.row_ptr,
            )
            x = nn.relu(bn(c, batch.node_mask, train))
        return x

    @nn.nowrap
    def _encode_painn(self, batch: GraphBatch):
        if batch.positions is None:
            raise ValueError(
                "PAINN reads GraphBatch.positions: collate with "
                "with_positions=True (config completion and the serving "
                "engine do, from the model family)"
            )
        geom = painn.edge_geometry(
            batch.positions, batch.senders, batch.receivers, batch.edge_mask,
            self.radius, self.num_radial,
        )
        s = self.conv_embed(batch.node_features)
        v = jnp.zeros((s.shape[0], 3 * self.hidden_dim), s.dtype)
        for block in self.convs:
            s, v = block(s, v, geom, batch.senders, batch.receivers, batch.row_ptr)
        # Padding rows at zero, as the batch norms leave them for the others.
        return jnp.where(batch.node_mask[:, None], s, 0.0)

    @nn.nowrap
    def _encode_tokens(self, batch: GraphBatch):
        if batch.positions is None:
            raise ValueError(
                f"{self.conv_type} reads each node's place in its sequence from "
                "GraphBatch.positions[:, 0]: collate with with_positions=True "
                "(config completion and the serving engine do, from the "
                "model family)"
            )
        # senders, receivers, row_ptr and the edge mask are not read: the
        # token mixers work from node_graph and the node order
        # (models/token_attention.py).
        h = self.conv_embed(token_ids(batch.node_features[:, 0], self.token_cfg))
        place = batch.positions[:, 0]
        for block in self.convs:
            h = block(h, batch.node_graph, place, batch.node_mask)
        # Padding rows at zero, as the batch norms leave them for the others.
        return jnp.where(batch.node_mask[:, None], self.conv_norm(h), 0.0)

    def __call__(self, batch: GraphBatch, train: bool = False):
        if self.conv_type == "PAINN":
            x = self._encode_painn(batch)
        elif self.conv_type in TOKEN_STACKS:
            x = self._encode_tokens(batch)
        else:
            x = self._encode_convs(batch, train)
        return self._heads(x, batch, train)

    @property
    def scored_heads(self) -> Tuple[int, ...]:
        """The heads ``score_tokens`` answers with log-probabilities: a token
        family's node heads under a cross-entropy loss, one shared MLP."""
        if self.conv_type not in TOKEN_STACKS:
            return ()
        node_type = self.config_heads.get("node", {}).get("type")
        return tuple(
            i for i, (kind, loss) in enumerate(zip(self.output_type, self.head_loss))
            if kind == "node" and loss == "cross_entropy" and node_type == "mlp"
        )

    @nn.nowrap
    def score_tokens(self, batch: GraphBatch):
        """What the serving engine's executable returns for a token family
        (``model.apply(..., method=HydraGNN.score_tokens)``): each of
        ``scored_heads`` as ``[N, 1]``, ``log softmax(logits)[i, token_{i+1}]``
        with the next token read from the node column inside the same
        sequence, 0 for a sequence's last token and for padding; every other
        head as ``__call__`` gives it. The ``[N, classes]`` logits are taken
        ``LOGPROB_BLOCK`` rows at a time and never held whole."""
        x = self._encode_tokens(batch)
        ids = token_ids(batch.node_features[:, 0], self.token_cfg)
        follows = jnp.concatenate([
            (batch.node_graph[1:] == batch.node_graph[:-1])
            & batch.node_mask[1:] & batch.node_mask[:-1],
            jnp.zeros((1,), bool),
        ])
        nxt = jnp.concatenate([ids[1:], ids[:1]])
        return self._heads(x, batch, False, score=(nxt, follows))

    @nn.nowrap
    def _node_head(self, ihead: int):
        """The node head ``ihead`` as a function of rows alone, unbound (so
        that ``lax.map`` may call it): its MLP, or for a tied head the
        embedding's table transposed (``nn.Embed.attend``: ``x E^T``)."""
        if self.tied_head:
            embed, variables = self.conv_embed.unbind()
            return lambda rows: embed.apply(variables, rows, method="attend")
        head, variables = self.heads_nn[ihead].unbind()
        return lambda rows: head.apply(variables, rows, None)

    @nn.nowrap
    def _next_token_logprob(self, ihead: int, x, nxt, follows):
        head = self._node_head(ihead)
        n, classes = x.shape[0], self.output_dim[ihead]
        block = min(LOGPROB_BLOCK, n)
        pad = -n % block

        def rows(args):
            xb, tb = args
            logits = head(xb).astype(jnp.float32)
            top = jnp.max(logits, axis=-1, keepdims=True)
            lse = top[:, 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
            # The next token's logit by a compare against an iota: a gather
            # of one scalar a row would cost a row each.
            hit = tb[:, None] == jnp.arange(classes)[None, :]
            return jnp.sum(jnp.where(hit, logits, 0.0), axis=-1) - lse

        with jax.named_scope(scopes.HEAD_LOGPROB):
            xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[1])
            ts = jnp.pad(jnp.clip(nxt, 0, classes - 1), (0, pad)).reshape(-1, block)
            logp = jax.lax.map(rows, (xs, ts)).reshape(-1)[:n]
            return jnp.where(follows, logp, 0.0)[:, None]

    @nn.nowrap
    def _heads(self, x, batch: GraphBatch, train: bool, score=None):
        """Read-out and heads over the encoder's output ``x``; ``score``
        (``score_tokens``): the next token and where one follows."""
        # Masked global mean pool (Base.py:247-250); graph_ptr is the CSR
        # boundary array over node_graph (nodes are contiguous per graph).
        with jax.named_scope(scopes.POOL):
            x_graph = aggregate.fused_segment_mean(
                x, batch.node_graph, batch.num_graphs_pad, mask=batch.node_mask,
                row_ptr=batch.graph_ptr,
            )

        outputs = []
        inode = 0
        for ihead, htype in enumerate(self.output_type):
            if htype == "graph":
                xg = self.graph_shared(x_graph)
                outputs.append(self.heads_nn[ihead](xg))
            else:
                if self.node_nn_type == "conv":
                    xn = x
                    chain = list(
                        zip(self.convs_node_hidden, self.batch_norms_node_hidden)
                    ) + [
                        (
                            self.convs_node_output[inode],
                            self.batch_norms_node_output[inode],
                        )
                    ]
                    for conv, bn in chain:
                        xn = conv(
                            xn,
                            batch.senders,
                            batch.receivers,
                            None,
                            batch.edge_mask,
                            batch.node_mask,
                            train,
                            batch.row_ptr,
                        )
                        # Reference applies relu(bn(.)) through the output layer
                        # too (Base.forward, Base.py:261-265).
                        xn = nn.relu(bn(xn, batch.node_mask, train))
                    inode += 1
                    outputs.append(xn)
                elif score is not None and ihead in self.scored_heads:
                    outputs.append(self._next_token_logprob(ihead, x, *score))
                elif self.tied_head:
                    outputs.append(self.conv_embed.attend(x))
                else:
                    outputs.append(self.heads_nn[ihead](x, batch))
        return outputs
