"""Programs the persistent compile cache did not hold
(``utils/compat.py::compile_cache_counts``): 0 from the second run of a
cell in a checkout on."""

NAME = "cache_misses"


def read(run):
    return float(run.cache["misses"]) if run.cache else None
