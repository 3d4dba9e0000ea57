"""The device allocator's own peak on the fullest chip, in GB
(``memory_stats()["peak_bytes_in_use"]``), read when the window has closed:
the buffers the measured session held, without the scratch of a running
program and before the reference check builds anything."""

NAME = "hbm_allocator_gb"
CHIP_ONLY = True  # the CPU's allocator reports nothing


def read(run):
    return run.memory["allocator_peak_bytes"] / 1e9 or None
