"""Persistent XLA compilation cache placement.

Every chip-tool call is a fresh machine and a ~1B-parameter train step
plus the serving engine's bucket programs take minutes to compile, so
every entry point that compiles for the chip (``chip_smoke.py``,
``serving/replica_main.py``) calls :func:`enable_compile_cache` first.
It is NOT called at ``import paddle_tpu``: library users and the CPU
test suite keep JAX's own default (no persistent cache).

The directory can be placed from outside: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX itself reads it and this module
sets no directory; otherwise the cache lives at ``<checkout>/.jax_cache``
— a fixed path derived from the package location (never a temp dir, pid
or timestamp), so a second run finds what the first one wrote.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory. Call before the first compilation (JAX initializes the
    cache once, lazily, from the config as it stands then)."""
    import jax

    # the engine's small bucket programs and helper jits compile in
    # under JAX's 1 s default admission threshold; a warm run should
    # find them too (the entry-size threshold already defaults to 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
