"""ctypes bindings for the native runtime (src/ C++ -> libmxtpu_io.so).

Mirrors the reference's layering: Python rides a flat C ABI over the native
library (reference: python/mxnet/base.py check_call over libmxnet.so). The
library is built on demand with `make -C src` the first time it's needed, and
again whenever a file under src/ is newer than it; environments without a
toolchain fall back to pure-Python paths where one exists (callers check
`available()`).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_LIB_DIR = os.path.join(os.path.dirname(__file__), "_lib")
_LIB_PATH = os.path.join(_LIB_DIR, "libmxtpu_io.so")
_SRC_DIR = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", "src"))

_lib = None
_lock = threading.Lock()
_build_error = None


def _build():
    global _build_error
    try:
        subprocess.run(["make", "-C", _SRC_DIR, "-s"], check=True,
                       capture_output=True, text=True)
        return True
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        _build_error = getattr(e, "stderr", str(e)) or str(e)
        return False


def _stale():
    """True when the library is missing or any file under src/ is newer
    than it — a checkout updated under a built library must not keep
    loading the old ABI."""
    try:
        built = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    for root, _, files in os.walk(_SRC_DIR):
        for name in files:
            if os.path.getmtime(os.path.join(root, name)) > built:
                return True
    return False


def _load():
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        if _stale() and not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            global _build_error
            _build_error = str(e)
            return None
        lib.MXTIOGetLastError.restype = ctypes.c_char_p
        lib.MXTIOCreateImageRecordIter.restype = ctypes.c_void_p
        lib.MXTIOCreateImageRecordIter.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint,
            ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int]
        lib.MXTIOCreateImageRecordIterEx.restype = ctypes.c_void_p
        lib.MXTIOCreateImageRecordIterEx.argtypes = (
            lib.MXTIOCreateImageRecordIter.argtypes
            + [ctypes.POINTER(ctypes.c_float)])
        lib.MXTIOCreateImageRecordIterEx2.restype = ctypes.c_void_p
        lib.MXTIOCreateImageRecordIterEx2.argtypes = (
            lib.MXTIOCreateImageRecordIterEx.argtypes + [ctypes.c_int])
        lib.MXTIOCreateImageDetRecordIter.restype = ctypes.c_void_p
        lib.MXTIOCreateImageDetRecordIter.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint,
            ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int, ctypes.c_float, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int]
        lib.MXTIODetLabelWidth.restype = ctypes.c_int
        lib.MXTIODetLabelWidth.argtypes = [ctypes.c_void_p]
        lib.MXTIOScanDetLabelWidth.restype = ctypes.c_int
        lib.MXTIOScanDetLabelWidth.argtypes = [ctypes.c_char_p]
        lib.MXTIONext.restype = ctypes.c_int
        lib.MXTIONext.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_float),
                                  ctypes.POINTER(ctypes.c_float)]
        lib.MXTIONextU8.restype = ctypes.c_int
        lib.MXTIONextU8.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_uint8),
                                    ctypes.POINTER(ctypes.c_float)]
        lib.MXTIOReset.argtypes = [ctypes.c_void_p]
        lib.MXTIONumSamples.restype = ctypes.c_longlong
        lib.MXTIONumSamples.argtypes = [ctypes.c_void_p]
        lib.MXTIOFree.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available():
    return _load() is not None


def build_error():
    return _build_error


def get_lib():
    lib = _load()
    if lib is None:
        raise RuntimeError("native io library unavailable: %s"
                           % (_build_error or "unknown"))
    return lib


def last_error():
    lib = get_lib()
    return lib.MXTIOGetLastError().decode("utf-8", "replace")
