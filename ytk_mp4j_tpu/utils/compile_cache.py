"""Where the entry points keep jax's persistent compile cache.

The directory is part of the cache key, so it must not move between
runs: where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself
and this module sets nothing; where it is unset the cache lives at
``<checkout>/.jax_cache``, derived from this file's location alone.
Op metadata (source lines, ``jax.named_scope`` stacks) is part of the key
here, so that a profile never shows another commit's names.

Called from entry points (``chip_smoke.py`` and the ``check``
programs' ``main()``), never from ``import ytk_mp4j_tpu``: a
library import must not decide where a user's process writes files.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compilation_cache() -> str:
    """Point jax at the persistent compile cache; returns the directory
    in use. Call before the first compilation of the process."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    # jax leaves op metadata out of the cache key by default, so a
    # program read from an entry that another commit wrote carries THAT
    # commit's name stacks (measured, PERF.md section 6, PR 24: the
    # parent's FFM step reported this tree's ``ffm.table_*`` scopes).
    # The scopes are what the device trace is read by: a stale name is a
    # wrong measurement, a miss is only a compile.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.config.jax_compilation_cache_dir
