"""Where XLA's persistent compilation cache lives.

The unrolled GPT-2 124M train step takes most of a cold run to compile
(PERF.md, Bring-up), so every entry point that compiles for the chip calls
:func:`configure_compile_cache` before its first compile.  The directory is
placed from outside with ``JAX_COMPILATION_CACHE_DIR`` (jax reads it itself);
when that is not set the cache goes to one fixed directory beside the package,
never to a temporary one — an entry under a path that moves is never found
again.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` (listed in .gitignore).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point jax at the persistent compile cache and return its directory.

    A directory that is already configured — ``JAX_COMPILATION_CACHE_DIR`` in
    the environment, or ``jax_compilation_cache_dir`` set by the caller — is
    left alone; no code path of this package sets another.
    """
    import jax

    # jax leaves HLO metadata out of the cache's key by default, so an entry
    # written by another tree (the same instructions under other names, or
    # none) would hand that tree's names to profiles and to
    # ``TrainStep.anatomy()``.  The names are part of what this program
    # caches; the price is a recompile when a traced line moves.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        # jax latches "is the cache in use" at the first compile of the
        # process; if one already ran with no directory, un-latch it.
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    return jax.config.jax_compilation_cache_dir
