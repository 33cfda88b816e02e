"""Build + load the native sum-tree via ctypes.

No pybind11 in the image (environment constraint), so the C++ side is a
plain ``extern "C"`` shared object compiled with g++ on first use into
``<checkout>/.native_build/`` (git-ignored), named by the hash of the
source it was built from — a library from another checkout or an older
source can never be picked up. Callers should catch
``NativeBuildError`` and fall back to the pure-NumPy sum-tree
(``components/host_replay.PySumTree``) when no toolchain is present.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

_SRC = os.path.join(os.path.dirname(__file__), "sumtree.cpp")
_BUILD_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".native_build")
_LIB_CACHE = {}


class NativeBuildError(RuntimeError):
    pass


def _build_lib() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_BUILD_DIR, f"libsumtree-{digest}.so")
    if os.path.exists(so_path):
        return so_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        detail = getattr(e, "stderr", str(e))
        raise NativeBuildError(f"g++ build failed: {detail}") from e
    os.replace(tmp, so_path)
    return so_path


def load_sumtree() -> ctypes.CDLL:
    """→ CDLL with typed signatures; raises NativeBuildError when no g++."""
    if "lib" in _LIB_CACHE:
        return _LIB_CACHE["lib"]
    lib = ctypes.CDLL(_build_lib())
    c = ctypes
    lib.sumtree_create.restype = c.c_void_p
    lib.sumtree_create.argtypes = [c.c_int64]
    lib.sumtree_free.argtypes = [c.c_void_p]
    lib.sumtree_set.argtypes = [c.c_void_p, c.c_int64, c.c_double]
    lib.sumtree_set_batch.argtypes = [
        c.c_void_p, c.POINTER(c.c_int64), c.POINTER(c.c_double), c.c_int64]
    lib.sumtree_total.restype = c.c_double
    lib.sumtree_total.argtypes = [c.c_void_p]
    lib.sumtree_get.restype = c.c_double
    lib.sumtree_get.argtypes = [c.c_void_p, c.c_int64]
    lib.sumtree_get_batch.argtypes = [
        c.c_void_p, c.POINTER(c.c_int64), c.c_int64,
        c.POINTER(c.c_double)]
    lib.sumtree_find.restype = c.c_int64
    lib.sumtree_find.argtypes = [c.c_void_p, c.c_double]
    lib.sumtree_sample.argtypes = [
        c.c_void_p, c.POINTER(c.c_double), c.c_int64,
        c.POINTER(c.c_int64), c.POINTER(c.c_double)]
    _LIB_CACHE["lib"] = lib
    return lib
