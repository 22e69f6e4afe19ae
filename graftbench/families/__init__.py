"""One module a model family, named by the program's ``model_type`` in lower
case (``pna.py``, ``gat.py``) and found by that name, as ``run.py`` finds
drivers, generators and metric readers. A family module holds

* ``encode(model, params, stats, graph) -> [n, enc]``: the plain float32
  encoder of ONE graph. ``graph`` is a dict of what a host ``GraphSample``
  holds (``x`` [n, F], ``pos`` [n, 3], ``send`` and ``recv`` [E], ``edge_attr``
  [E, D] or None), so a family that needs positions, or a state beyond one
  array, writes its own loop; the loop the classic families share is
  ``reference.conv_stack``. Pooling and the heads are ``reference.py``'s.
* ``counts(arch, nodes, edges) -> (parts, enc_width)``: the encoder's
  operations and bytes over REAL rows as ``flops.part`` pieces, under the
  byte convention written in ``flops.py``. Pool and heads are ``flops.py``'s.
* optionally ``ATOL``, ``RTOL`` with the reason beside them; a family that
  gives none takes ``reference.py``'s.
"""

import importlib


def load(model_type: str):
    """The family module of ``model_type``; a family with no file fails with
    the name of the file to add."""
    stem = str(model_type).lower()
    try:
        return importlib.import_module(f"graftbench.families.{stem}")
    except ModuleNotFoundError as e:
        if e.name != f"graftbench.families.{stem}":
            raise
        raise NotImplementedError(
            f"no plain reference and no operation count for model_type "
            f"{model_type!r}: add graftbench/families/{stem}.py with encode() "
            "and counts() (graftbench/README.md, 'Adding things')"
        ) from None
