"""Shared loader for the repo's native C++ libraries (native/*.so).

One code path for every binding (comm/native.py wire byte-path,
data/native_tokenizer.py WordPiece encoder): lazily build via
native/build.py, load with ctypes, hand the CDLL to a configure callback
that declares argtypes/restypes, and cache the result — returning None
(pure-Python twin) when no toolchain exists or anything fails, with the
reason kept for :func:`native_status`.
"""

from __future__ import annotations

import ctypes
import os
from typing import Callable

_CACHE: dict[str, ctypes.CDLL | None] = {}
#: soname -> why its Python twin is live (entries exist only for failures).
_WHY_NOT: dict[str, str] = {}


def repo_native_dir() -> str:
    # <repo>/<package>/utils/native.py -> <repo>/native
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), "native")


def build_module():
    """``native/build.py`` as a module (it lives outside the package)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fedtpu_native_build", os.path.join(repo_native_dir(), "build.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_native(
    src: str, soname: str, configure: Callable[[ctypes.CDLL], None]
) -> ctypes.CDLL | None:
    """Build (if stale) + load + configure ``native/<src>`` -> ``<soname>``.

    The first outcome — loaded library or None — is cached per soname;
    failures never raise (callers keep their pure-Python twin) but are
    remembered, so a run can say which path it actually took."""
    if soname in _CACHE:
        return _CACHE[soname]
    lib: ctypes.CDLL | None = None
    try:
        so_path = build_module().build_lib(src, soname)
        if so_path is None:
            _WHY_NOT[soname] = "no C++ toolchain, or the compile failed"
        else:
            lib = ctypes.CDLL(so_path)
            configure(lib)
    except Exception as e:
        lib = None
        _WHY_NOT[soname] = f"{type(e).__name__}: {e}"
    _CACHE[soname] = lib
    return lib


def native_status() -> dict[str, str]:
    """Per library loaded so far: ``"native"``, or ``"python twin
    (<reason>)"`` — which byte path this process is actually on."""
    return {
        soname: "native" if lib is not None else f"python twin ({_WHY_NOT.get(soname, 'forced')})"
        for soname, lib in sorted(_CACHE.items())
    }
