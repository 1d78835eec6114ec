"""Where compiled device programs are kept between runs.

A chip machine starts every call with no compiled code, and the bulk
CRUSH program alone takes about a minute to compile, so every entry
point places JAX's persistent compilation cache before its first
kernel.  The directory is part of the cache key: it is either what the
environment names or one fixed path inside the checkout, never a
temporary name.
"""

from __future__ import annotations

import os

#: the checkout that holds this package (``.jax_cache/`` is git-ignored)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it.  With ``JAX_COMPILATION_CACHE_DIR`` set JAX reads the
    variable itself and no directory is set in code; otherwise the cache
    lives in ``<checkout>/.jax_cache``.  Call it first thing in an entry
    point, before anything compiles.

    Either way Python tracebacks are kept out of MLIR locations.  A
    Pallas kernel is serialized into its program with its debug info,
    which JAX cannot strip when it hashes the program for the cache, so
    with full tracebacks (the default) the key depends on the call stack
    that first traced the kernel: the same CRUSH program compiled for a
    minute each from ``crush_test``, from a second ``BatchMapper`` and
    from a dispatch-engine thread, in one process, and never hit
    (chip run, PR 24).  Locations then name the innermost frame only."""
    import jax
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
