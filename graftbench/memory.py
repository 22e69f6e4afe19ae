"""How much of the chip's memory a cell needs.

``device.memory_stats()["peak_bytes_in_use"]`` counts the buffers the
allocator handed out: parameters, optimizer state, batches, outputs, and the
intermediates of whatever ran eagerly (the program's initializer: 1.42 GB for
PNA, 5.0 GB for GATv2). It does not count what a running program needs beside
its arguments: the compiler assigns each program's temporaries itself, the
runtime reserves them while the program runs, and a program whose requirement
does not fit is refused (PNA at batch 2048 asked for 25.13 GB of 15.75 GB and
did not compile, while the allocator's peak with batch 512 and a jitted
initializer read 0.15 GB; my chip runs, PR 22). So a cell reports two parts,

    allocator_peak_bytes   measured: the allocator's peak over the process
    program_temp_bytes     the compiler's buffer assignment for the largest
                           program run: ``memory_analysis().temp_size_in_bytes``
                           of the same function lowered for the same shapes

and as the peak on the fullest chip the larger of the allocator's peak and
what the allocator holds after the window (state and staged batches) plus
those temporaries: the two peaks are not added, since the initializer's
intermediates are gone before the first step runs. The temporaries cover the
PADDED shapes the program is compiled for (PNA's bucket of 32768 x 524288 is
65% padding rows): that is what the chip must hold, not what the data needs.

A driver hands ``ProgramMemory.watch`` the jitted functions the program
calls; during warm-up each distinct argument shape is recorded as it passes
(the last call's layout wins: on a mesh the first call sees a fresh state, the
later ones a laid-out one), and after the window each is lowered with abstract
arguments and the compiler asked. The answers are kept in
``graftbench/.cache/memory/`` by cell and shapes, so only a checkout's first
run of a cell asks.
"""

from __future__ import annotations

import hashlib
import json
import os


def _abstract(args):
    import jax

    def leaf(a):
        if isinstance(a, jax.Array):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return jax.tree_util.tree_map(leaf, args)


def _signature(name, args) -> str:
    import jax

    leaves, tree = jax.tree_util.tree_flatten(args)
    text = name + str(tree) + ";".join(f"{l.shape}{l.dtype}" for l in leaves)
    return hashlib.sha256(text.encode()).hexdigest()[:20]


class ProgramMemory:
    def __init__(self, cell):
        self._dir = os.path.join(cell.cache_dir, "memory", cell.name)
        self._seen = {}  # signature -> (name, fn, abstract args)
        self.recording = True  # a driver clears it where its window begins

    def watch(self, name: str, fn):
        """``fn`` as before, noting each new argument shape it is called with."""

        def watched(*args):
            if self.recording:
                self.note(name, fn, *args)
            return fn(*args)

        # The program lowers through the same attribute when its own
        # executable store is on.
        watched.lower = fn.lower
        return watched

    def note(self, name: str, fn, *args) -> None:
        abstract = _abstract(args)
        self._seen[_signature(name, abstract)] = (name, fn, abstract)

    def temp_bytes(self) -> dict:
        """{program name: largest temporary size over its shapes}."""
        out = {}
        for sig, (name, fn, abstract) in self._seen.items():
            path = os.path.join(self._dir, sig + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    temp = json.load(f)["temp_size_in_bytes"]
            else:
                analysis = fn.lower(*abstract).compile().memory_analysis()
                temp = int(analysis.temp_size_in_bytes)
                os.makedirs(self._dir, exist_ok=True)
                with open(path + ".tmp", "w") as f:
                    json.dump({"program": name, "temp_size_in_bytes": temp}, f)
                os.replace(path + ".tmp", path)
            out[name] = max(out.get(name, 0), temp)
        return out



def peak(devices, temps: dict) -> dict:
    """The fullest chip's figures after the window (module docstring)."""
    temp = max(temps.values(), default=0)
    rows = []
    for d in devices:
        stats = d.memory_stats() or {}
        top = stats.get("peak_bytes_in_use") or 0
        now = stats.get("bytes_in_use") or 0
        rows.append((max(top, now + temp), top))
    return {
        "peak_bytes": max(r[0] for r in rows),
        "allocator_peak_bytes": max(r[1] for r in rows),
        "program_temp_bytes": temp,
    }
