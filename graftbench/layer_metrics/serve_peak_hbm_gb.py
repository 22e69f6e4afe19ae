"""The allocator's ``peak_bytes_in_use`` after the serving window, in GB, as
``peak_hbm_gb``: weights, staged batches and outputs; the forward's
temporaries are beside it in ``device.program_temp_bytes``."""


def read(run):
    return run.memory["allocator_peak_bytes"] / 1e9
