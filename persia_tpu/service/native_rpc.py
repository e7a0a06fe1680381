"""Native (C++) RPC server binding — the parameter-server data plane.

Parity target: the reference's fully compiled remote path (hyper HTTP +
speedy bodies + lz4 over tokio, `others/persia-rpc/src/lib.rs:68-145`,
`persia-embedding-server/src/bin/*.rs`). ``NativeRpcServer`` owns the TCP
listener in C++ (`native/server.cpp`): the hot methods (``ping``,
``lookup_batched``, ``update_batched``) run frame-parse → dispatch → C++
store call → wire-dtype convert → writev reply entirely off the GIL;
every other registered method falls back to the Python handler table, so
the control plane (checkpoints, config, admin) is unchanged.

Drop-in for ``persia_tpu.service.rpc.RpcServer`` when the store is the
native ``NativeEmbeddingStore``; ``ParameterServerService`` picks it
automatically (opt out with ``PERSIA_NATIVE_SERVER=0``).
"""

from __future__ import annotations

import ctypes
import os
import threading
import zlib
from typing import Callable, Dict, Optional

from persia_tpu.logger import get_default_logger

logger = get_default_logger("persia_tpu.native_rpc")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRCS = [
    os.path.join(_REPO_ROOT, "native", "server.cpp"),
    os.path.join(_REPO_ROOT, "native", "codec.cpp"),
]
_SO = os.path.join(_REPO_ROOT, "native", "libpersia_net.so")
_PS_SO = os.path.join(_REPO_ROOT, "native", "libpersia_ps.so")
_PS_SO_PATH = _PS_SO  # resolved (variant-aware) by _load()

_FALLBACK_CB = ctypes.CFUNCTYPE(
    None, ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
    ctypes.c_void_p,
)

_LIB: Optional[ctypes.CDLL] = None
_LOAD_FAILED = False


_FLAGS = ["-O3", "-std=c++17", "-fPIC", "-shared", "-Wall", "-pthread", "-ldl"]


def build_native(force: bool = False) -> str:
    """Compile the server core if missing or stale (see
    ``_native_build.build_so``); returns the path to ``CDLL``."""
    from persia_tpu.embedding._native_build import build_so

    return build_so(_SRCS, _SO, _FLAGS, logger, force=force)


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _LOAD_FAILED
    if _LIB is not None or _LOAD_FAILED:
        return _LIB
    try:
        from persia_tpu.embedding._native_build import build_so
        from persia_tpu.embedding.native_store import build_native as build_ps

        global _PS_SO_PATH
        # the server dlopens libpersia_ps.so for the store calls — under a
        # sanitizer that must be the matching VARIANT ps artifact (mixed
        # sanitized/unsanitized cores in one process would miss reports)
        _PS_SO_PATH = build_ps()
        # CDLL the path build_so RETURNS (sanitizer-variant aware); built
        # here rather than through build_native() so persia-lint's ABI pass
        # can tie the handle to _SO (this file names two libs)
        so_path = build_so(_SRCS, _SO, _FLAGS, logger)
        lib = ctypes.CDLL(so_path)
        lib.net_server_start.restype = ctypes.c_void_p
        lib.net_server_start.argtypes = [
            ctypes.c_int, ctypes.c_void_p, ctypes.c_char_p, _FALLBACK_CB,
            ctypes.c_int64,
        ]
        lib.net_server_port.restype = ctypes.c_int
        lib.net_server_port.argtypes = [ctypes.c_void_p]
        lib.net_server_stop.restype = None
        lib.net_server_stop.argtypes = [ctypes.c_void_p]
        lib.net_reply.restype = None
        lib.net_reply.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int64,
        ]
        _LIB = lib
    except Exception as e:  # noqa: BLE001 — toolchain-less host
        logger.warning("native rpc server unavailable (%r)", e)
        _LOAD_FAILED = True
    return _LIB


def native_server_available() -> bool:
    return _load() is not None


class NativeRpcServer:
    """RpcServer-shaped wrapper over the C++ listener. ``handlers`` serve
    the Python fallback path; the C++ side intercepts the hot methods and
    never consults them for lookup_batched/update_batched/ping."""

    def __init__(self, store, port: int = 0, compress_threshold: int = 1 << 20):
        lib = _load()
        if lib is None:
            raise RuntimeError("native rpc server unavailable")
        if not getattr(store, "_h", None):
            raise TypeError("NativeRpcServer requires a NativeEmbeddingStore")
        self._lib = lib
        from persia_tpu.service.rpc import _capabilities_reply

        self.handlers: Dict[str, Callable[[bytes], bytes]] = {
            "ping": lambda p: b"pong",
            "capabilities": _capabilities_reply,
            "shutdown": lambda p: b"ok",
        }
        self._stopped = threading.Event()

        # the ctypes callback object must outlive the server (C++ holds the
        # raw pointer)
        self._cb = _FALLBACK_CB(self._fallback)
        self._handle = lib.net_server_start(
            port, store._h, _PS_SO_PATH.encode(), self._cb, compress_threshold
        )
        if not self._handle:
            raise RuntimeError("net_server_start failed")
        self.port = lib.net_server_port(self._handle)
        self._thread: Optional[threading.Thread] = None

    # -------------------------------------------------------------- fallback

    def _fallback(self, method_b, payload_p, plen, reply_ctx) -> None:
        try:
            method = method_b.decode()
            payload = ctypes.string_at(payload_p, plen) if plen else b""
            if method.startswith("__zlib__:"):  # legacy zlib-compressed peer
                method = method[len("__zlib__:"):]
                payload = zlib.decompress(payload)
            fn = self.handlers.get(method)
            if fn is None:
                reply, status = f"unknown method {method!r}".encode(), 1
            else:
                try:
                    reply, status = fn(payload) or b"", 0
                except Exception as e:  # noqa: BLE001 — app error crosses the wire
                    logger.exception("handler %s failed", method)
                    from persia_tpu.service.rpc import _is_transportish

                    prefix = b"unavailable: " if _is_transportish(e) else b""
                    reply, status = prefix + repr(e).encode(), 1
            if not isinstance(reply, (bytes, bytearray)):
                # scatter-gather handler replies flatten here (control plane
                # only — the hot methods never reach Python)
                reply = b"".join(bytes(memoryview(b).cast("B")) for b in reply)
            self._lib.net_reply(reply_ctx, status, bytes(reply), len(reply))
            if method == "shutdown":
                self._stopped.set()
        except BaseException as e:  # noqa: BLE001 — never unwind into C++
            logger.exception("fallback dispatch failed")
            msg = repr(e).encode()
            self._lib.net_reply(reply_ctx, 1, msg, len(msg))

    # ------------------------------------------------------------- lifecycle

    def register(self, name: str, fn: Callable[[bytes], bytes]) -> None:
        self.handlers[name] = fn

    def start(self) -> "NativeRpcServer":
        # the C++ accept loop is already running; expose an RpcServer-shaped
        # joinable thread that parks until shutdown
        self._thread = threading.Thread(
            target=self._stopped.wait, daemon=True, name="native-rpc-park"
        )
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self._stopped.wait()

    def stop(self) -> None:
        self._stopped.set()
        h, self._handle = self._handle, None
        if h:
            self._lib.net_server_stop(h)

    def __del__(self):
        try:
            self.stop()
        except Exception:  # noqa: BLE001
            pass
