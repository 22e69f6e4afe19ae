"""Temporaries of the largest program the cell ran, in GB: the compiler's
buffer assignment (``memory_analysis().temp_size_in_bytes``) for the padded
shapes the program is compiled for, not a reading of the allocator
(``graftbench/memory.py``). None where the driver watched no program."""


def read(run):
    return run.memory["program_temp_bytes"] / 1e9 or None
