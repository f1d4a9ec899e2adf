"""The persistent XLA compilation cache shared by this repo's entry points.

Compiling the cache kernels and the serving model takes most of a cold
run's start-up.  Every command-line entry point (``chip_smoke.py``,
``repro.launch.serve``, ``repro.launch.train``, ``benchmarks.run``) calls
``enable()`` under its ``__main__`` guard, so a second run on the same
machine reads its compiled programs back instead of compiling again.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed in-checkout cache directory (gitignored); the path is part of
#: the cache key, so it must not move between runs
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no directory is set here.  Otherwise the cache goes to ``DEFAULT_DIR``
    inside the checkout.

    The cache key includes each op's metadata (its name stack, the
    ``jax.named_scope`` names among them).  JAX leaves it out by default,
    and then a program read back from the cache carries the op names of
    whichever source compiled the same instructions first: a profile would
    name the phases of another version of the code, or none.
    """
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
