"""Host byte codec — ctypes binding of the native C++ codec (native/codec.cc).

API parity with the reference's codec module (/root/reference/src/
compression.py:18-46: g_compress/g_decompress/w_compress/w_decompress wrap
blosc.pack_array/unpack_array): same four names, same role (gradients and
weights on the host wire), different engine — our own shuffle+LZ C++ library
instead of an external c-blosc dependency. Array framing (dtype/shape) is a
small JSON header ahead of the byte stream.

The shared library is built on demand with g++ from native/*.cc and named
after a hash of those sources (native/Makefile writes the same name), so a
binary built from other sources is never loaded: a changed source is a new
file name and a rebuild. Without the sources or a compiler the module falls
back to zlib — and says so once — so the checkpoint/codec feature degrades
rather than fails.
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import logging
import os
import subprocess
import threading
import zlib
from typing import Optional

import numpy as np

logger = logging.getLogger("ps_pytorch_tpu")

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_PKG_DIR, "_native")
_SRC_DIR = os.path.join(os.path.dirname(_PKG_DIR), "native")
_SOURCES = ("codec.cc", "loader.cc")

_lock = threading.Lock()
_lib = None
_lib_tried = False

MAGIC = b"PSAR"  # array framing magic (codec stream has its own 'PSC1')


def _library_path() -> Optional[str]:
    """_native/libpsnative-<hash of the sources>.so, or None without them."""
    digest = hashlib.sha256()
    try:
        for name in _SOURCES:
            with open(os.path.join(_SRC_DIR, name), "rb") as f:
                digest.update(f.read())
    except OSError:
        return None
    return os.path.join(
        _NATIVE_DIR, f"libpsnative-{digest.hexdigest()[:16]}.so"
    )


def _build_library(lib_path: str) -> Optional[str]:
    """Compile the native sources into lib_path (atomically: concurrent
    builders replace it with identical bytes). Returns why it failed."""
    os.makedirs(_NATIVE_DIR, exist_ok=True)
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O3", "-std=c++17", "-fPIC", "-Wall",
        "-shared", "-pthread",
        "-o", tmp, *(os.path.join(_SRC_DIR, s) for s in _SOURCES),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        os.replace(tmp, lib_path)
    except (OSError, subprocess.SubprocessError) as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return f"{type(e).__name__}: {e}"
    return None


def _load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None -> zlib fallback."""
    global _lib, _lib_tried
    with _lock:
        if _lib_tried:
            return _lib
        _lib_tried = True
        lib_path = _library_path()
        why = None
        if lib_path is None:
            why = f"no sources under {_SRC_DIR}"
        elif not os.path.exists(lib_path):
            why = _build_library(lib_path)
        if why is None:
            try:
                lib = ctypes.CDLL(lib_path)
            except OSError as e:
                why = f"OSError: {e}"
        if why is not None:
            logger.warning(
                "native codec unavailable (%s): falling back to zlib", why
            )
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.psc_max_compressed.restype = ctypes.c_size_t
        lib.psc_max_compressed.argtypes = [ctypes.c_size_t]
        lib.psc_compress.restype = ctypes.c_size_t
        lib.psc_compress.argtypes = [
            u8p, ctypes.c_size_t, u8p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
        ]
        lib.psc_raw_size.restype = ctypes.c_size_t
        lib.psc_raw_size.argtypes = [u8p, ctypes.c_size_t]
        lib.psc_decompress.restype = ctypes.c_size_t
        lib.psc_decompress.argtypes = [
            u8p, ctypes.c_size_t, u8p, ctypes.c_size_t, ctypes.c_int,
        ]
        lib.psl_gather.restype = ctypes.c_int
        lib.psl_gather.argtypes = [
            u8p, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, u8p,
            ctypes.c_int,
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def _as_u8p(buf: bytearray):
    return ctypes.cast(
        (ctypes.c_char * len(buf)).from_buffer(buf), ctypes.POINTER(ctypes.c_uint8)
    )


def _as_const_u8p(data: bytes):
    """Zero-copy read-only view of a bytes object for the C side (which
    only reads src) — avoids duplicating checkpoint-sized buffers."""
    return ctypes.cast(ctypes.c_char_p(data or b"\0"), ctypes.POINTER(ctypes.c_uint8))


def compress_bytes(data: bytes, itemsize: int = 1, n_threads: int = 0) -> bytes:
    """Compress raw bytes (native codec, zlib fallback prefixed 'Z')."""
    lib = _load()
    if lib is None:
        return b"Z" + zlib.compress(data, 6)
    n = len(data)
    cap = lib.psc_max_compressed(n)
    dst = ctypes.create_string_buffer(cap)
    got = lib.psc_compress(
        _as_const_u8p(data),
        n,
        ctypes.cast(dst, ctypes.POINTER(ctypes.c_uint8)),
        cap,
        itemsize,
        n_threads,
    )
    if got == 0 and n > 0:
        raise RuntimeError("psc_compress failed")
    # the input (checkpoint-sized) is passed zero-copy above; copying the
    # compressed OUTPUT once here is the cheap side of the trade
    return b"N" + ctypes.string_at(dst, got)


def decompress_bytes(blob: bytes, n_threads: int = 0) -> bytes:
    tag, payload = blob[:1], blob[1:]
    if tag == b"Z":
        return zlib.decompress(payload)
    if tag != b"N":
        raise ValueError("not a psnative codec blob")
    lib = _load()
    if lib is None:
        raise RuntimeError(
            "blob was written by the native codec but the library is unavailable"
        )
    src = _as_const_u8p(payload)
    raw = lib.psc_raw_size(src, len(payload))
    if raw == 0:
        # raw==0 is either a genuinely empty stream or a bad header —
        # disambiguate by validating the header here
        if (
            len(payload) >= 16
            and payload[:4] == b"PSC1"
            and payload[4] == 1
            and int.from_bytes(payload[8:16], "little") == 0
        ):
            return b""
        raise ValueError("malformed psnative stream")
    dst = bytearray(raw)
    got = lib.psc_decompress(src, len(payload), _as_u8p(dst), raw, n_threads)
    if got != raw:
        raise ValueError("corrupt psnative stream")
    return bytes(dst)


def compress_array(arr: np.ndarray, n_threads: int = 0) -> bytes:
    """Array -> framed compressed blob (parity role: blosc.pack_array)."""
    arr = np.asarray(arr)
    shape = list(arr.shape)  # before ascontiguousarray, which promotes 0-d to 1-d
    arr = np.ascontiguousarray(arr)
    header = json.dumps({"dtype": arr.dtype.str, "shape": shape}).encode()
    body = compress_bytes(arr.tobytes(), itemsize=arr.dtype.itemsize, n_threads=n_threads)
    return MAGIC + len(header).to_bytes(4, "little") + header + body


def decompress_array(blob: bytes, n_threads: int = 0) -> np.ndarray:
    if blob[:4] != MAGIC:
        raise ValueError("not a psnative array blob")
    hlen = int.from_bytes(blob[4:8], "little")
    meta = json.loads(blob[8 : 8 + hlen].decode())
    raw = decompress_bytes(blob[8 + hlen :], n_threads=n_threads)
    return np.frombuffer(raw, dtype=np.dtype(meta["dtype"])).reshape(meta["shape"]).copy()


# ----- reference-name aliases (compression.py:18-46) -----------------------
def g_compress(grad: np.ndarray) -> bytes:
    return compress_array(grad)


def g_decompress(msg: bytes) -> np.ndarray:
    return decompress_array(msg)


def w_compress(weight: np.ndarray) -> bytes:
    return compress_array(weight)


def w_decompress(msg: bytes) -> np.ndarray:
    return decompress_array(msg)
