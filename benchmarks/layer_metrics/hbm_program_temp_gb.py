"""Temporaries of the largest program the launch loaded, per chip, in
GB, as the runtime records them for the loaded executable
(``get_compiled_memory_stats``), read by the harness at the first stamp."""

NAME = "hbm_program_temp_gb"


def read(run):
    return run.memory["program_temp_bytes"] / 1e9 or None
