"""Native (C++) runtime components, bound via ctypes.

The reference implements its runtime in C++ with a flat C API consumed by
Python cffi (src/c/flexflow_c.cc). Here the native surface covers the
host-side components that are not XLA's job: the GPT-2 BPE and
SentencePiece tokenizers (reference src/runtime/gpt_tokenizer.cc) and the
C graph builder.

The shared library is built lazily with g++ on first use (sources live in
``native/`` at the repo root) and cached; every binding has a pure-Python
fallback so the framework works even without a toolchain.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_BUILD_DIR = os.path.join(_NATIVE_DIR, "build")
_LIB_PATH = os.path.join(_BUILD_DIR, "libflexflow_tpu_native.so")
# hash of the sources the library at _LIB_PATH was built from; written
# after a successful build, compared before every load
_STAMP_PATH = _LIB_PATH + ".srchash"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _sources():
    src = os.path.join(_NATIVE_DIR, "src")
    return [os.path.join(src, f) for f in
            ("bpe_tokenizer.cpp", "sp_tokenizer.cpp", "graph_builder.cpp")]


def _source_hash() -> str:
    h = hashlib.sha256()
    hdr = os.path.join(_NATIVE_DIR, "include", "flexflow_tpu_c.h")
    for path in _sources() + [hdr]:
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _needs_build() -> bool:
    """True unless the library was built from exactly these sources.

    Content, not mtimes: a checkout or a copy flattens mtimes, and
    ``native/build`` is ignored by git, so a library left on disk by an
    older tree (or built outside ``_build``, with no stamp) must never be
    loaded in place of the current ``native/src``."""
    try:
        with open(_STAMP_PATH) as f:
            stamp = f.read().strip()
    except OSError:
        return True
    return not os.path.exists(_LIB_PATH) or stamp != _source_hash()


def _build() -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    src_hash = _source_hash()
    # linked under this process's own name and renamed into place: another
    # process (a test worker, ``make``) may be loading the same path
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-std=c++17", "-fPIC", "-Wall", "-shared",
           "-I", os.path.join(_NATIVE_DIR, "include"),
           "-o", tmp] + _sources()
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB_PATH)
    except (OSError, subprocess.SubprocessError):
        return False
    with open(_STAMP_PATH, "w") as f:
        f.write(src_hash + "\n")
    return True


def _declare(lib: ctypes.CDLL):
    c = ctypes
    i32p = c.POINTER(c.c_int32)
    lib.ffbpe_create.restype = c.c_void_p
    lib.ffbpe_create.argtypes = [c.c_char_p, c.c_char_p]
    lib.ffbpe_create_from_buffers.restype = c.c_void_p
    lib.ffbpe_create_from_buffers.argtypes = [c.c_char_p, c.c_char_p]
    lib.ffbpe_destroy.argtypes = [c.c_void_p]
    lib.ffbpe_vocab_size.restype = c.c_int
    lib.ffbpe_vocab_size.argtypes = [c.c_void_p]
    lib.ffbpe_encode.restype = c.c_int
    lib.ffbpe_encode.argtypes = [c.c_void_p, c.c_char_p, c.c_int, i32p,
                                 c.c_int]
    lib.ffbpe_decode.restype = c.c_int
    lib.ffbpe_decode.argtypes = [c.c_void_p, i32p, c.c_int, c.c_char_p,
                                 c.c_int]

    ip = c.POINTER(c.c_int)
    lib.ffgb_create.restype = c.c_void_p
    lib.ffgb_create.argtypes = []
    lib.ffgb_destroy.argtypes = [c.c_void_p]
    for fn, extra in (("ffgb_input", [c.c_int, c.c_char_p]),
                      ("ffgb_dense", [c.c_int, c.c_int, c.c_int,
                                      c.c_char_p]),
                      ("ffgb_conv2d", [c.c_int] * 9 + [c.c_int,
                                                       c.c_char_p]),
                      ("ffgb_pool2d", [c.c_int] * 8 + [c.c_char_p]),
                      ("ffgb_unary", [c.c_int, c.c_char_p, c.c_char_p]),
                      ("ffgb_binary", [c.c_int, c.c_int, c.c_char_p,
                                       c.c_char_p]),
                      ("ffgb_concat", [ip, c.c_int, c.c_int, c.c_char_p]),
                      ("ffgb_softmax", [c.c_int, c.c_int, c.c_char_p]),
                      ("ffgb_dropout", [c.c_int, c.c_double, c.c_char_p]),
                      ("ffgb_embedding", [c.c_int, c.c_int, c.c_int,
                                          c.c_char_p]),
                      ("ffgb_reshape", [c.c_int, ip, c.c_int, c.c_char_p]),
                      ("ffgb_layer_norm", [c.c_int, ip, c.c_int, c.c_int,
                                           c.c_double, c.c_char_p]),
                      ("ffgb_batch_norm", [c.c_int, c.c_char_p]),
                      ("ffgb_rms_norm", [c.c_int, c.c_double, c.c_int,
                                         c.c_char_p]),
                      ("ffgb_multihead_attention",
                       [c.c_int] * 5 + [c.c_double, c.c_char_p]),
                      ("ffgb_scalar", [c.c_int, c.c_char_p, c.c_double,
                                       c.c_int, c.c_char_p]),
                      ("ffgb_transpose", [c.c_int, ip, c.c_int, c.c_char_p]),
                      ("ffgb_mean", [c.c_int, ip, c.c_int, c.c_int,
                                     c.c_char_p]),
                      ("ffgb_cast", [c.c_int, c.c_char_p, c.c_char_p]),
                      ("ffgb_output", [ip, c.c_int]),
                      ("ffgb_save", [c.c_char_p]),
                      ("ffgb_serialize", [c.c_char_p, c.c_int])):
        f = getattr(lib, fn)
        f.restype = c.c_int
        f.argtypes = [c.c_void_p] + extra


def load_native() -> Optional[ctypes.CDLL]:
    """Build (if needed) and load the native library; None if unavailable.

    Disable with FF_DISABLE_NATIVE=1 (forces pure-Python fallbacks)."""
    global _lib, _build_failed
    if os.environ.get("FF_DISABLE_NATIVE") == "1":
        return None
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        if _needs_build() and not _build():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            _build_failed = True
            return None
        _declare(lib)
        _lib = lib
        return lib


def native_available() -> bool:
    return load_native() is not None
