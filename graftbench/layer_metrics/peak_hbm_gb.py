"""The allocator's ``memory_stats()["peak_bytes_in_use"]`` of the fullest
chip after the window, in GB: measured. It leaves out the temporaries a
running program reserves (``program_temp_gb``, ``graftbench/memory.py``)."""


def read(run):
    return run.memory["allocator_peak_bytes"] / 1e9
