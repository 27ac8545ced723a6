"""Where XLA's persistent compilation cache lives — one rule, one place.

Every entry point that compiles calls :func:`configure_compile_cache`
first thing.  ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads the
variable itself, so the code sets nothing); otherwise the cache is one
fixed directory inside the checkout, derived from this file's location
so every process of a checkout agrees on it.  The chip tool keeps only
its output directory between calls, so a run that should reuse compiled
programs points the variable there from outside.

It also registers the process's one ``jax.monitoring`` listener
(``telemetry/startup.py::listen_to_jax``): every entry point comes through
here before it compiles, so what JAX traced, lowered, compiled or fetched
from this cache is counted from the first program on.
"""

from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (git-ignored).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Apply the rule above; returns the cache directory in use."""
    import jax

    from distributed_machine_learning_tpu.telemetry.startup import (
        listen_to_jax,
    )

    listen_to_jax()

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    # Cache every program that took real time to compile, however small
    # its serialized form (the defaults skip sub-1 s / tiny entries,
    # which is most of the CPU test suite).
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    return placed or str(DEFAULT_CACHE_DIR)
