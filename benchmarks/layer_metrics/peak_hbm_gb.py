"""Peak device memory on the fullest chip, in GB (the line's
``memory_peak_bytes``: the larger of the allocator's peak and the live
buffers plus the training program's temporaries). Read beside steps/s:
speed bought with memory shows here."""

NAME = "peak_hbm_gb"
CHIP_ONLY = True  # the CPU's allocator reports nothing


def read(run):
    return run.memory["memory_peak_bytes"] / 1e9 or None
