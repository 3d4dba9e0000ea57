"""Seams over the one installed JAX (0.9.0): the names product code
imports for ``shard_map``/``axis_size``/``device_kind``, and the one
function that decides where the persistent XLA compile cache lives.
Nothing here catches an exception: with one installation these calls
either work or are a bug.
"""

from __future__ import annotations

import os
import re

from jax import shard_map  # noqa: F401 — call sites import it from here

from surreal_tpu.session.telemetry import launch_add, launch_add_interval


def axis_size(axis_name) -> int:
    """Static size of a named mapped axis."""
    import jax

    return int(jax.lax.axis_size(axis_name))


def device_kind() -> str:
    """``device_kind`` of the default backend's first device, as JAX
    reports it (the key of session/costs.py's peak table)."""
    import jax

    return str(jax.devices()[0].device_kind)


# -- persistent XLA compile cache ---------------------------------------------

# Where the cache lives unless JAX_COMPILATION_CACHE_DIR places it from
# outside: one fixed, git-ignored directory next to the package. A
# directory that moves never hits, so it is never built from a session
# folder, a temporary name, a pid or the time.
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

_CACHE_COUNTS = {"hits": 0, "misses": 0}
_LISTENERS_INSTALLED = False


# JAX's own timing events (jax 0.9.0: _src/dispatch.py:60-62,
# _src/compiler.py:452) -> the counter of the innermost open launch span
# each adds to (session/telemetry.py): which span traced, lowered,
# compiled and read the cache for how long. The three of dispatch.py come
# with their start and end, which is what tells a function traced inside
# another's trace from one traced after it; the cache's read comes as a
# duration alone. The backend's event spans compile_or_get_cached, so on
# a hit it is the read over again.
_LAUNCH_INTERVALS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _count_cache_event(event: str, **_kwargs) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE_COUNTS["hits"] += 1
        launch_add("cache_hits", 1)
    elif event == "/jax/compilation_cache/cache_misses":
        _CACHE_COUNTS["misses"] += 1
        launch_add("cache_misses", 1)


def _count_compile_interval(event: str, start: float, end: float,
                            **_kwargs) -> None:
    counter = _LAUNCH_INTERVALS.get(event)
    if counter is not None:
        launch_add_interval(counter, float(start), float(end))


def _count_cache_read(event: str, duration: float, **_kwargs) -> None:
    if event == _CACHE_READ_EVENT:
        launch_add("cache_read_s", float(duration))


def compile_cache_active() -> bool:
    """True when compiles of this process go through a persistent cache —
    the signal session/costs.py uses to decide an extra AOT compile
    (memory_analysis) is a disk deserialize rather than a second compile."""
    import jax

    return bool(
        jax.config.jax_enable_compilation_cache
        and jax.config.jax_compilation_cache_dir
    )


def compile_cache_counts() -> dict:
    """Process-global compile-cache hit/miss counts since
    :func:`enable_compile_cache` first ran (zeros before)."""
    return dict(_CACHE_COUNTS)


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it
    stands and no directory is set here; where it is not, the cache lives
    at the fixed ``.jax_cache`` of this checkout. Every program is
    eligible (an RL session compiles a handful of large programs, and the
    small ones are what a warm start otherwise waits for one by one).
    Returns None, touching no setting, where JAX's own switch
    (``jax_enable_compilation_cache`` / ``JAX_ENABLE_COMPILATION_CACHE``)
    has the cache off — tests/conftest.py does that for the CPU suite.
    Every entry point (CLI, SessionHooks, chip_smoke.py) calls
    this; may be called any number of times, before or after the
    process's first compile."""
    global _LISTENERS_INSTALLED
    import jax

    if not _LISTENERS_INSTALLED:
        # with the cache off too: a launch's trace, lowering and compile
        # seconds are counted either way
        jax.monitoring.register_event_listener(_count_cache_event)
        jax.monitoring.register_event_time_span_listener(
            _count_compile_interval
        )
        jax.monitoring.register_event_duration_secs_listener(_count_cache_read)
        _LISTENERS_INSTALLED = True
    if not jax.config.jax_enable_compilation_cache:
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # an op's phase is read from the op_name metadata of the COMPILED
    # program (session/profile.py); without this an executable cached
    # before a scope was added or moved comes back with the old names.
    # The metadata then holds the op's own source line and not the stack
    # of its callers, with the checkout's own path taken off, so that one
    # program traced from two call sites (the cost accountant's lowering
    # and the first dispatch, the CLI and the benchmark) or from a second
    # checkout is still one cache entry
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        "^" + re.escape(_CHECKOUT) + "/",
    )
    return jax.config.jax_compilation_cache_dir
