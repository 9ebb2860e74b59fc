"""Builds libshmring.so on first use with g++ (cached next to the source;
no pip/pybind11 — plain C ABI consumed via ctypes)."""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "shm_ring.cpp")
_LIB = os.path.join(_HERE, "libshmring.so")
_lock = threading.Lock()
_lib = None


def _compile() -> str:
    # pid-unique output: concurrent ranks may build simultaneously and
    # os.replace must publish only a COMPLETE library
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++14", _SRC,
           "-o", tmp, "-lrt", "-pthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, _LIB)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return _LIB


def load_shm_ring():
    """Returns the bound ctypes library, building it from
    ``shm_ring.cpp`` when it is missing or older than the source (git
    commits no binary); raises RuntimeError when it cannot be built."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_LIB) or (
                os.path.getmtime(_LIB) < os.path.getmtime(_SRC)):
            try:
                _compile()
            except (subprocess.CalledProcessError, FileNotFoundError) as e:
                raise RuntimeError(f"cannot build libshmring.so: {e}")
        lib = ctypes.CDLL(_LIB)
        lib.rb_create.restype = ctypes.c_void_p
        lib.rb_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.rb_attach.restype = ctypes.c_void_p
        lib.rb_attach.argtypes = [ctypes.c_char_p]
        lib.rb_push.restype = ctypes.c_int
        lib.rb_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint64, ctypes.c_int]
        lib.rb_next_len.restype = ctypes.c_int64
        lib.rb_next_len.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.rb_pop.restype = ctypes.c_int
        lib.rb_pop.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                               ctypes.c_uint64]
        lib.rb_close_producer.argtypes = [ctypes.c_void_p]
        lib.rb_used.restype = ctypes.c_uint64
        lib.rb_used.argtypes = [ctypes.c_void_p]
        lib.rb_detach.argtypes = [ctypes.c_void_p]
        lib.rb_unlink.argtypes = [ctypes.c_char_p]
        _lib = lib
        return lib
