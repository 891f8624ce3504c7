"""Loader for the native plasma arena allocator.

Compiles ``plasma_alloc.cpp`` with the system g++ on first import and
caches the shared object beside the source under a name that carries the
source's content hash: a copy of the tree that scrambles mtimes, or one
that brings a stale git-ignored ``.so`` along, can never load a build of
some other source. Concurrent builds from parallel worker starts serialize
on a file lock. Raises ImportError-like failures to the caller
(``object_store._make_allocator``), which says so and keeps its Python
free-list allocator when no toolchain is available.
"""

from __future__ import annotations

import glob
import hashlib
import importlib.util
import os
import subprocess
import sysconfig

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "plasma_alloc.cpp")
_EXT = sysconfig.get_config_var("EXT_SUFFIX") or ".so"

with open(_SRC, "rb") as _f:
    _SRC_HASH = hashlib.sha256(_f.read()).hexdigest()[:12]
_SO = os.path.join(_DIR, f"_plasma_native_{_SRC_HASH}{_EXT}")


def _build() -> None:
    import fcntl

    lock_path = os.path.join(_DIR, "_plasma_native.lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if os.path.exists(_SO):
            return  # another process built it while we waited
        include = sysconfig.get_paths()["include"]
        tmp = _SO + f".tmp.{os.getpid()}"
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
             f"-I{include}", _SRC, "-o", tmp],
            check=True, capture_output=True)
        os.replace(tmp, _SO)  # atomic: importers never see a partial .so
        for stale in glob.glob(os.path.join(_DIR, "_plasma_native*" + _EXT)):
            if stale != _SO:
                os.unlink(stale)  # builds of earlier sources


if not os.path.exists(_SO):
    _build()

_spec = importlib.util.spec_from_file_location("_plasma_native", _SO)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

NativeAllocator = _mod.NativeAllocator
