"""Where JAX's persistent compilation cache lives: one rule, in one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, every process of the program
(driver scripts, the workers ``core/node.py`` spawns, the tests) keeps its
compiled programs in that directory and nothing in code names another.
Where it is not set, the cache is ``.jax_cache/`` beside the ``ray_tpu``
package: a fixed, git-ignored path inside the checkout. A directory named
after a pid, a timestamp or a temporary file would never be found again by
the next process, which is the whole point of the cache.

JAX reads the variable when it is imported, so :func:`configure` must run
before ``import jax`` wherever that can be arranged; where jax is already
loaded it sets the same directory through ``jax.config``.
"""

from __future__ import annotations

import os
import sys
from typing import MutableMapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir(environ: MutableMapping[str, str] = os.environ) -> str:
    """The directory this program's compile cache lives in."""
    return environ.get(ENV_VAR) or DEFAULT_DIR


def configure(environ: MutableMapping[str, str] = os.environ) -> str:
    """Pin the cache directory into ``environ`` (inherited by every child
    process) and, if jax is already imported here, into its config."""
    path = compile_cache_dir(environ)
    environ[ENV_VAR] = path
    jax = sys.modules.get("jax")
    if environ is os.environ and jax is not None \
            and jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
