"""Where JAX's persistent compilation cache lives and what of a source
location reaches its keys: one rule, in one place.

THE DIRECTORY. Where ``JAX_COMPILATION_CACHE_DIR`` is set, every process of
the program (driver scripts, the workers ``core/node.py`` spawns, the tests)
keeps its compiled programs in that directory and nothing in code names
another. Where it is not set, the cache is ``.jax_cache/`` beside the
``ray_tpu`` package: a fixed, git-ignored path inside the checkout. A
directory named after a pid, a timestamp or a temporary file would never be
found again by the next process, which is the whole point of the cache.

THE LOCATIONS. jax strips source locations from the program it hashes for
the cache, but not from a Mosaic kernel: the kernel's serialized body is the
custom call's ``backend_config``, and it holds a location for every operation
of the kernel. Two settings, constants of this module and not options,
decide what such a location is:

- ``jax_traceback_in_locations_limit = 1``. jax's default writes the call
  stack an operation was traced under, up to ten frames of file paths and
  line numbers, so a line put in above a kernel's call site in
  ``models/llama.py`` or in any frame above it missed the cache for every
  program that holds a kernel. At one frame jax writes the innermost
  outside jax: the line of the kernel's body and the function it stands in.
  A kernel's key is then the kernel, its own file's lines and nothing of
  its callers'. What it costs: a Mosaic compile error names the kernel's
  line and not the stack that called it. (NOT
  ``jax_include_full_tracebacks_in_locations = False``, which also writes
  one frame but without the function's name: XLA names a kernel's
  instruction after that name, ``%flash_prefill`` and not
  ``%tpu_custom_call``, and the benchmark's trace reducers and
  ``tests/test_tpu_compile.py`` find the kernels by it.)
- ``jax_hlo_source_file_canonicalization_regex`` = what stands before the
  ``ray_tpu`` package directory of this checkout. That one frame's file is
  then ``ray_tpu/ops/...`` wherever the checkout lies, and two checkouts
  that share a cache directory share the programs they have in common.

JAX reads all three from the environment when it is imported, so
:func:`configure` must run before ``import jax`` wherever that can be
arranged; where jax is already loaded it sets the same values through
``jax.config``.
"""

from __future__ import annotations

import os
import re
import sys
from typing import MutableMapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")
PATH_REGEX = "^" + re.escape(_CHECKOUT + os.sep)
# jax's name of each setting and the value this program pins it to; the
# environment's name is the same in capitals
LOCATIONS = {"jax_traceback_in_locations_limit": 1,
             "jax_hlo_source_file_canonicalization_regex": PATH_REGEX}


def compile_cache_dir(environ: MutableMapping[str, str] = os.environ) -> str:
    """The directory this program's compile cache lives in."""
    return environ.get(ENV_VAR) or DEFAULT_DIR


def configure(environ: MutableMapping[str, str] = os.environ) -> str:
    """Pin the cache directory and the two location settings into
    ``environ`` (inherited by every child process) and, if jax is already
    imported here, into its config."""
    path = compile_cache_dir(environ)
    settings = {"jax_compilation_cache_dir": path, **LOCATIONS}
    for name, value in settings.items():
        environ[name.upper()] = str(value)
    jax = sys.modules.get("jax")
    if environ is os.environ and jax is not None:
        for name, value in settings.items():
            if getattr(jax.config, name) != value:
                jax.config.update(name, value)
    return path
