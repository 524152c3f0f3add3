"""Where JAX's persistent compilation cache lives — decided once, from outside.

Every entry point that compiles (the ``fedtpu`` CLI, ``chip_smoke.py``,
``benchmark/run.py``) calls :func:`place_compile_cache` before its first
compilation. The directory is part of the cache key's environment, so it
must not move between processes or runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  set in code.
* unset: ``<checkout>/.jax_cache`` (git-ignored) — never a temp dir, a pid
  or a timestamp, which would make every run a cold one.

Importing this module imports no jax: the aggregation tiers (``serve``,
``route``, ``relay``) go through the same CLI ``main`` and stay numpy +
sockets. The choice is published through the environment variable, which
JAX reads at import and every child process inherits.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    # <checkout>/<package>/utils/compile_cache.py -> <checkout>/.jax_cache
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def place_compile_cache() -> str:
    """Settle the cache directory for this process and its children;
    returns it. Call before the first compilation: JAX decides once per
    process whether the cache is in use."""
    path = os.environ.setdefault(ENV_VAR, default_cache_dir())
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax was imported before the variable was (possibly) defaulted
        # above and read it then; hand it the same answer.
        jax.config.update("jax_compilation_cache_dir", path)
    return path
