"""Where the entry points keep JAX's persistent compile cache.

One rule for every CLI (`chip_smoke.py`, `bench.py`, `python -m
madsim_tpu.{explore,campaign,tune,repro}`): when `JAX_COMPILATION_CACHE_DIR`
is set, JAX reads it itself and no code here sets another directory;
otherwise the cache lives at a fixed, git-ignored path inside the checkout,
so a cold process (a chip run, a repro, a service restart) pays seconds
instead of a compile. The path is part of the cache's key: a directory that
moves never hits, hence a fixed one.

The test suite keeps its own per-uid /tmp cache (tests/conftest.py): tests
run on the CPU only, and their entries stay out of the checkout.
"""

from __future__ import annotations

import os

# <repo>/.jax_cache — listed in .gitignore
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at the checkout's fixed path
    unless `JAX_COMPILATION_CACHE_DIR` already names one. Returns the
    directory in use. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    # only programs worth caching: the sweep segments take seconds
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    return REPO_CACHE_DIR
