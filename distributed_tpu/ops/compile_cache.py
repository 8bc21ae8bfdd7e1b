"""The one place that points JAX's persistent compilation cache at a
directory.

A cold process compiles every co-processor kernel it dispatches, and on
the chip each compile takes seconds.  The persistent cache lets the next
process (the next bench config child, the next ``chip_smoke.py`` run, a
restarted scheduler) load those programs instead.  Its directory is part
of what makes an entry findable, so it never moves: the deployment's
``JAX_COMPILATION_CACHE_DIR`` when set, otherwise ``.jax_cache`` at the
root of this checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache before the first compile
    and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it, and this
    sets nothing.  Otherwise it sets ``jax_compilation_cache_dir`` to
    :data:`DEFAULT_DIR`, and no other option.  Idempotent."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
