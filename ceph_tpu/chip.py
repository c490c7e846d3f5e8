"""Start-up of the entry points that hold the chip.

A chip belongs to one process. The entry points that take it
(chip_smoke.py, tools/crush_bench.py, tools/ec_bench.py) call
use_compile_cache() before their first JAX computation, so a second run
on the same machine reads its executables back instead of compiling from
cold. Tests never call it: a compile for a described chip
(tests/test_chip_compile.py) would be written to the cache and could not
be read back without one.
"""

from __future__ import annotations

import os

#: the cache's home when JAX_COMPILATION_CACHE_DIR is unset: a fixed path
#: (gitignored), since the path is part of what a cache hit matches
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path:
    JAX_COMPILATION_CACHE_DIR where it is set, else <repo>/.jax_cache."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # the served path compiles many small bucket shapes, each well under
    # JAX's default one-second floor; keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
