"""JAX's persistent compile cache and the compile clock.

Copied from the program (``repro.bench.setup_compile_cache`` and
``chip_smoke.CompileClock``) so that the benchmark's cache policy cannot
move with the program it measures.
"""

from __future__ import annotations

import os
import re

#: The root of the checkout: ``benchmarks/chip/chipbench`` is three
#: levels below it.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
#: The cache when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: directory in the checkout (a path that moves is never found again).
COMPILE_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compile cache before the first compile.

    A ``JAX_COMPILATION_CACHE_DIR`` set from outside wins (JAX reads it
    itself); otherwise the cache is :data:`COMPILE_CACHE_DIR`.  Compiles
    of 0.1 s and up are kept.  A Pallas kernel carries its source file's
    path into the program and so into the cache key, so the checkout's
    root is cut from source paths and every checkout shares the entries.
    Returns the directory in use."""
    import jax

    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return where


class CompileClock:
    """Sums JAX's backend compile time (a load from the persistent cache
    included) and counts compiles and cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits
