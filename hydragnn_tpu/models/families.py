"""The registry of model families: which ``model_type`` values exist and what
each kind of family needs of the batch. It holds no layer. A token family is
ONE line of ``TOKEN_STACKS`` here and one file beside this one; every set
below, ``create_model``'s one ``token_arch`` argument and ``HydraGNN``'s one
``token_cfg`` field follow from that line, and nothing else in the program
spells the family's name (``HydraGNN``'s four benchmark-pinned properties
apart: ROADMAP D25).
"""

from __future__ import annotations

from .jamba import JambaBlock, JambaConfig
from .laguna import LagunaBlock, LagunaConfig
from .lfm2 import LFM2Block, LFM2Config
from .mellum import MellumBlock, MellumConfig
from .mistral4 import Mistral4Block, Mistral4Config

# The token stacks: a sequence as a graph, a token a node, the node column a
# min-max-scaled token id (``Architecture.token_minmax`` from completion).
# Each is (the dataclass of its sizes, built ``from_arch``; its block). What
# the shared code reads off the sizes: models/token_common.py.
TOKEN_STACKS = {
    "LFM2": (LFM2Config, LFM2Block),
    "LAGUNA": (LagunaConfig, LagunaBlock),
    "MISTRAL4": (Mistral4Config, Mistral4Block),
    "MELLUM": (MellumConfig, MellumBlock),
    "JAMBA": (JambaConfig, JambaBlock),
}
TOKEN_FAMILIES = frozenset(TOKEN_STACKS)
# Every valid ``model_type``: the six convolutions of models/convs.py, PaiNN
# (models/painn.py) and the token stacks.
CONV_TYPES = ("PNA", "MFC", "GIN", "GAT", "CGCNN", "SAGE", "PAINN", *TOKEN_STACKS)
# Conv families whose aggregation rides the sorted/CSR edge layout end to end
# (every family since PR 7 — GAT's sort-breaking [edges; self-loops] concat
# was replaced by an explicit self-attention term). check_config consults
# this registry: a future family missing here would silently fall back to
# the unsorted scatter path on TPU, which the contract checker now rejects
# instead (analysis/contracts.py). The token stacks read no edge list: no
# aggregation to fall back.
SORTED_PATH_FAMILIES = (
    frozenset({"SAGE", "GIN", "MFC", "GAT", "CGCNN", "PNA", "PAINN"}) | TOKEN_FAMILIES
)
# Families that read ``GraphBatch.positions`` inside the step (PaiNN its edge
# geometry, a token stack each node's place in its sequence); the loaders carry
# positions for these alone (utils/config_utils.py, serve/engine.py).
POSITION_FAMILIES = frozenset({"PAINN"}) | TOKEN_FAMILIES
