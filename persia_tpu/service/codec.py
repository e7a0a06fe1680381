"""Wire compression for the RPC tier.

Parity target: the reference compresses large RPC bodies with lz4 FAST(3)
(`others/persia-rpc/src/lib.rs:68-145`). The round-1 zlib fallback is far
too slow for the per-batch lookup/gradient path, so the hot frames
effectively travelled uncompressed; ``native/codec.cpp`` provides an
LZ4-block-format codec fast enough to sit on the data plane. zlib remains
as the no-toolchain fallback (the frame flag records which codec was used,
so mixed deployments interoperate).
"""

from __future__ import annotations

import ctypes
import os
import zlib
from typing import Optional

from persia_tpu.logger import get_default_logger

logger = get_default_logger("persia_tpu.codec")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "codec.cpp")
_SO = os.path.join(_REPO_ROOT, "native", "libpersia_codec.so")
_LIB: Optional[ctypes.CDLL] = None
_LOAD_FAILED = False


def build_native(force: bool = False) -> str:
    """Compile the codec core if missing or stale (see
    ``_native_build.build_so``); returns the path to ``CDLL``."""
    from persia_tpu.embedding._native_build import build_so

    return build_so(
        _SRC, _SO,
        ["-O3", "-std=c++17", "-fPIC", "-shared", "-Wall"],
        logger, force=force,
    )


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _LOAD_FAILED
    if _LIB is not None or _LOAD_FAILED:
        return _LIB
    try:
        # CDLL the path build_native RETURNS (sanitizer-variant aware)
        lib = ctypes.CDLL(build_native())
        i64, u8p = ctypes.c_int64, ctypes.POINTER(ctypes.c_uint8)
        lib.lz4_compress_bound.restype = i64
        lib.lz4_compress_bound.argtypes = [i64]
        lib.lz4_compress.restype = i64
        lib.lz4_compress.argtypes = [u8p, i64, u8p, i64]
        lib.lz4_decompress.restype = i64
        lib.lz4_decompress.argtypes = [u8p, i64, u8p, i64]
        _LIB = lib
    except Exception as e:  # noqa: BLE001 — toolchain-less host
        logger.warning("native codec unavailable (%r); falling back to zlib", e)
        _LOAD_FAILED = True
    return _LIB


def lz4_available() -> bool:
    return _load() is not None


def lz4_compress(data: bytes) -> bytes:
    lib = _load()
    if lib is None:
        raise RuntimeError("native lz4 codec unavailable")
    cap = lib.lz4_compress_bound(len(data))
    out = ctypes.create_string_buffer(cap)
    n = lib.lz4_compress(
        ctypes.cast(data, ctypes.POINTER(ctypes.c_uint8)), len(data),
        ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)), cap,
    )
    if n < 0:
        raise RuntimeError("lz4 compression failed")
    return out.raw[:n]


def lz4_decompress(data: bytes, orig_size: int) -> bytes:
    lib = _load()
    if lib is None:
        raise RuntimeError("native lz4 codec unavailable")
    out = ctypes.create_string_buffer(max(orig_size, 1))
    n = lib.lz4_decompress(
        ctypes.cast(data, ctypes.POINTER(ctypes.c_uint8)), len(data),
        ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)), orig_size,
    )
    if n != orig_size:
        raise ValueError(f"lz4 decompression produced {n} bytes, expected {orig_size}")
    return out.raw[:orig_size]


# ------------------------------------------------------- frame-level helpers
# Frame codec ids (the RPC frame's flag bits record the codec in use)
CODEC_NONE = 0
CODEC_ZLIB = 1
CODEC_LZ4 = 2


def compress_frame(payload: bytes, prefer_lz4: bool = True,
                   allow_zlib: bool = True):
    """(codec_id, body) — lz4 when available (body = u32 orig_size | blocks).
    ``allow_zlib=False`` returns CODEC_NONE instead of falling back: zlib on
    a hot frame costs more than it saves (the ~20x-slower codec this module
    exists to replace), so reply paths skip compression when lz4 is out."""
    if prefer_lz4 and lz4_available():
        import struct

        return CODEC_LZ4, struct.pack("<I", len(payload)) + lz4_compress(payload)
    if allow_zlib:
        return CODEC_ZLIB, zlib.compress(payload, level=1)
    return CODEC_NONE, payload


def decompress_frame(codec_id: int, body: bytes) -> bytes:
    if codec_id == CODEC_ZLIB:
        return zlib.decompress(body)
    if codec_id == CODEC_LZ4:
        import struct

        (orig,) = struct.unpack("<I", body[:4])
        return lz4_decompress(body[4:], orig)
    raise ValueError(f"unknown codec id {codec_id}")
