"""Where JAX's persistent compilation cache lives.

Every process that compiles — the CLI, the benches, the dry-run legs, the
test session and the children they launch — calls
:func:`enable_compile_cache` before its first compile, so a relaunched
child, a second run, or a serving process started after a training process
finds what the first one built.

``JAX_COMPILATION_CACHE_DIR`` moves the cache from outside: JAX reads that
variable itself, so when it is set this module sets nothing.  Otherwise the
cache is ``<checkout>/.jax_cache`` — one fixed path, because the path is
part of how a deployment finds its cache again and a name that moves
(temp dir, pid, time) never hits.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Touches ``jax.config`` only — no backend is initialized."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
