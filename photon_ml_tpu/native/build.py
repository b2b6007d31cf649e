"""Compile-on-demand loader for the native C++ libraries.

No reference analogue: the reference shipped JVM bytecode and leaned on
PalDB/off-heap JNI jars; this build's native components compile from
vendored C++ at first use instead.

Each library is built with the system g++ into ``_build/`` beside this
file (git-ignored) under a name keyed by a hash of the source BYTES and
the compiler command, so a checkout, a copy or a touch never changes the
name, an edited source or flag always does, and a library whose key does
not match the source on disk is never loaded. A build for a new key
removes that source's older builds.

A missing compiler or a failed build raises from the ``load_*`` functions;
the ``*_available()`` probes turn that into False and a WARNING naming the
cause, and callers then take their (much slower) pure-Python paths.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import shutil
import subprocess
import tempfile
import threading
from typing import Callable

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
BUILD_DIR = os.path.join(_DIR, "_build")
_LOCK = threading.Lock()
_LIBS: dict[str, ctypes.CDLL] = {}
_FAILED: dict[str, str] = {}

_CXX_FLAGS = ["-O2", "-std=c++17", "-shared", "-fPIC"]
#: per-source extra link flags (only the Avro decoder needs zlib; coupling
#: every native build to libz would let a missing dev link silently degrade
#: the others to their Python fallbacks)
_LINK_FLAGS = {"avro_decoder.cpp": ["-lz"]}


def _link_flags(source: str) -> list[str]:
    return _LINK_FLAGS.get(os.path.basename(source), [])


def _lib_path(source: str) -> str:
    digest = hashlib.sha256()
    with open(source, "rb") as f:
        digest.update(f.read())
    digest.update("\0".join(_CXX_FLAGS + _link_flags(source)).encode())
    stem = os.path.splitext(os.path.basename(source))[0]
    return os.path.join(BUILD_DIR, f"{stem}-{digest.hexdigest()[:16]}.so")


def _compile(source: str, out_path: str) -> None:
    gxx = shutil.which("g++") or shutil.which("c++")
    if gxx is None:
        raise RuntimeError("no C++ compiler found")
    os.makedirs(BUILD_DIR, exist_ok=True)
    # build into a temp file then atomically rename (concurrent test workers)
    fd, tmp = tempfile.mkstemp(suffix=".so.tmp", dir=BUILD_DIR)
    os.close(fd)
    try:
        subprocess.run(
            [gxx, *_CXX_FLAGS, source, "-o", tmp, *_link_flags(source)],
            check=True,
            capture_output=True,
            text=True,
        )
        os.replace(tmp, out_path)
    except subprocess.CalledProcessError as e:
        os.unlink(tmp)
        raise RuntimeError(f"g++ failed: {e.stderr}") from e
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    stem = os.path.splitext(os.path.basename(source))[0]
    for stale in glob.glob(os.path.join(BUILD_DIR, f"{stem}-*.so")):
        if stale != out_path:
            os.unlink(stale)


def load_native_library(
    source_basename: str, configure: Callable[[ctypes.CDLL], None]
) -> ctypes.CDLL:
    """Load (compiling if needed) a native library; raises on failure.

    ``configure`` sets restype/argtypes on the freshly loaded CDLL; it runs
    once per process per library.
    """
    source = os.path.join(_DIR, source_basename)
    with _LOCK:
        if source_basename in _LIBS:
            return _LIBS[source_basename]
        if source_basename in _FAILED:
            raise RuntimeError(
                f"native library {source_basename} previously failed to "
                f"load: {_FAILED[source_basename]}"
            )
        try:
            path = _lib_path(source)
            if not os.path.exists(path):
                logger.info("compiling native library %s", source_basename)
                _compile(source, path)
            lib = ctypes.CDLL(path)
        except (RuntimeError, OSError) as e:
            _FAILED[source_basename] = str(e)
            logger.warning(
                "native library %s unavailable (%s); its callers fall back "
                "to pure-Python paths", source_basename, e,
            )
            raise
        configure(lib)
        _LIBS[source_basename] = lib
        return lib


def _available(load: Callable[[], ctypes.CDLL]) -> bool:
    try:
        load()
        return True
    except (RuntimeError, OSError):
        return False


def _configure_offheap(lib: ctypes.CDLL) -> None:
    lib.om_build.restype = ctypes.c_int64
    lib.om_build.argtypes = [
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_uint64,
    ]
    lib.om_open.restype = ctypes.c_void_p
    lib.om_open.argtypes = [ctypes.c_char_p]
    lib.om_close.restype = None
    lib.om_close.argtypes = [ctypes.c_void_p]
    lib.om_size.restype = ctypes.c_int64
    lib.om_size.argtypes = [ctypes.c_void_p]
    lib.om_get.restype = ctypes.c_int64
    lib.om_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
    lib.om_key_at.restype = ctypes.c_int64
    lib.om_key_at.argtypes = [
        ctypes.c_void_p,
        ctypes.c_uint64,
        ctypes.c_char_p,
        ctypes.c_uint64,
    ]


def load_offheap_library() -> ctypes.CDLL:
    return load_native_library("offheap_store.cpp", _configure_offheap)


def native_available() -> bool:
    return _available(load_offheap_library)


def _configure_libsvm(lib: ctypes.CDLL) -> None:
    lib.lsvm_parse.restype = ctypes.c_void_p
    lib.lsvm_parse.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int,
        ctypes.c_char_p,
        ctypes.c_uint64,
    ]
    for fn in (lib.lsvm_num_rows, lib.lsvm_nnz, lib.lsvm_max_index):
        fn.restype = ctypes.c_int64
        fn.argtypes = [ctypes.c_void_p]
    lib.lsvm_export.restype = None
    lib.lsvm_export.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_double),
    ]
    lib.lsvm_free.restype = None
    lib.lsvm_free.argtypes = [ctypes.c_void_p]


def load_libsvm_library() -> ctypes.CDLL:
    return load_native_library("libsvm_loader.cpp", _configure_libsvm)


def libsvm_native_available() -> bool:
    return _available(load_libsvm_library)


def _configure_avro(lib: ctypes.CDLL) -> None:
    lib.avdec_open.restype = ctypes.c_void_p
    lib.avdec_open.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_char_p,
        ctypes.c_uint64,
    ]
    lib.avdec_num_records.restype = ctypes.c_int64
    lib.avdec_num_records.argtypes = [ctypes.c_void_p]
    u32p = ctypes.POINTER(ctypes.POINTER(ctypes.c_uint32))
    f64p = ctypes.POINTER(ctypes.POINTER(ctypes.c_double))
    chp = ctypes.POINTER(ctypes.c_char_p)
    u64pp = ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64))
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.avdec_numcol.restype = ctypes.c_int64
    lib.avdec_numcol.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, f64p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
    ]
    lib.avdec_strcol.restype = ctypes.c_int64
    lib.avdec_strcol.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, u32p, chp, u64pp, u64p,
    ]
    lib.avdec_bag.restype = ctypes.c_int64
    lib.avdec_bag.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, u32p, u32p, f64p, chp, u64pp, u64p,
    ]
    lib.avdec_map.restype = ctypes.c_int64
    lib.avdec_map.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, u32p, u32p, u32p,
        chp, u64pp, u64p, chp, u64pp, u64p,
    ]
    lib.avdec_free.restype = None
    lib.avdec_free.argtypes = [ctypes.c_void_p]


def load_avro_library() -> ctypes.CDLL:
    return load_native_library("avro_decoder.cpp", _configure_avro)


def avro_native_available() -> bool:
    return _available(load_avro_library)
