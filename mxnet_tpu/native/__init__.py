"""Loader for the native runtime components (C++ .so via ctypes).

The reference's hot paths are C++ (src/io, src/engine); here the native
layer is built from mxnet_tpu/native/*.cc. The binaries are not checked
in (.gitignore): the library is compiled on first use (g++ and make are
part of the supported toolchain). A build or load that fails raises
MXNetError carrying the compiler's output — there is no Python stand-in
to fall back to, and a silent ``None`` hid the reason.
"""
from __future__ import annotations

import ctypes
import os
import subprocess

from ..base import MXNetError

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB = None


def _load(target):
    """ctypes handle of ``target``, built with make if absent."""
    from .. import telemetry
    with telemetry.setup_phase("native"):
        return _build_and_load(target)


def _build_and_load(target):
    path = os.path.join(_DIR, target)
    if not os.path.exists(path):
        # one target at a time: the engine must not become unavailable
        # because the io lib's -ljpeg link failed
        proc = subprocess.run(["make", "-C", _DIR, target],
                              capture_output=True, text=True, timeout=120)
        if proc.returncode:
            raise MXNetError("native build of %s failed:\n%s%s"
                             % (target, proc.stdout, proc.stderr))
    try:
        return ctypes.CDLL(path)
    except OSError as e:
        raise MXNetError("native library %s does not load: %s"
                         % (target, e)) from e


def load_io_lib():
    """Return the libmxtpu_io ctypes handle, building it if needed."""
    global _LIB
    if _LIB is not None:
        return _LIB
    lib = _load("libmxtpu_io.so")
    lib.MXIOGetLastError.restype = ctypes.c_char_p
    lib.MXIOCreateImageRecordIter.restype = ctypes.c_void_p
    lib.MXIOCreateImageRecordIter.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint64]
    lib.MXIONext.restype = ctypes.c_int
    lib.MXIONext.argtypes = [ctypes.c_void_p,
                             ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
                             ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
                             ctypes.POINTER(ctypes.c_int)]
    lib.MXIOReset.argtypes = [ctypes.c_void_p]
    lib.MXIOFree.argtypes = [ctypes.c_void_p]
    _LIB = lib
    return _LIB


def last_error() -> str:
    return (load_io_lib().MXIOGetLastError() or b"").decode()


_ENGINE_LIB = None


def load_engine_lib():
    """Return the libmxtpu_engine ctypes handle (MXEngine*/MXGetVersion
    C ABI), building on demand."""
    global _ENGINE_LIB
    if _ENGINE_LIB is not None:
        return _ENGINE_LIB
    lib = _load("libmxtpu_engine.so")
    lib.MXGetLastError.restype = ctypes.c_char_p
    lib.MXGetVersion.argtypes = [ctypes.POINTER(ctypes.c_int)]
    lib.MXEngineCreate.restype = ctypes.c_void_p
    lib.MXEngineCreate.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.MXEngineFree.argtypes = [ctypes.c_void_p]
    lib.MXEngineNewVar.restype = ctypes.c_uint64
    lib.MXEngineNewVar.argtypes = [ctypes.c_void_p]
    lib.MXEngineDeleteVar.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.MXEnginePushAsync.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int]
    lib.MXEngineWaitForVar.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.MXEngineWaitForAll.argtypes = [ctypes.c_void_p]
    _ENGINE_LIB = lib
    return _ENGINE_LIB
