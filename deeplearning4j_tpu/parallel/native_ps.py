"""ctypes binding for the native parameter-server transport core
(native/param_server.cpp) — the Aeron VoidParameterServer/RoutedTransport
analog (SURVEY.md §2.9, §5.8): a C++ aggregation store + TCP listener so
concurrent pushes of large flattened parameter vectors run without the
Python GIL. Drop-in for :class:`..parallel.param_server.InMemoryParameterServer`
/ ``ParameterServerNode``; falls back to those when no toolchain is
available (same silent-fallback policy as the reference's cuDNN helpers).

Wire protocol (native TCP front-end): 1-byte opcode ('P' push / 'G' get /
'Q' quit) + u64 little-endian length + raw little-endian f32 payload; 'G'
answers with an 'R' frame. :class:`NativeParameterServerClient` below speaks
it from Python.
"""

from __future__ import annotations

import ctypes
import logging
import socket
import struct
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "libdl4jtpu_native.so"
_lib = None


def _make(*targets: str) -> bool:
    """``make -C native [targets]``; a failed build (no toolchain, compile
    error) is logged — the Python path serves — never swallowed."""
    try:
        subprocess.run(["make", "-C", str(_NATIVE_DIR), *targets],
                       check=True, capture_output=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logging.getLogger(__name__).warning(
            "native build failed (make -C %s): %s %s", _NATIVE_DIR, e,
            (getattr(e, "stderr", None) or b"")[-400:])
        return False


def _load_lib():
    global _lib
    if _lib is not None:
        return _lib
    if not _LIB_PATH.exists():
        # The .so is not shipped in the repo (a committed binary can't be
        # reviewed against its sources) — build it on first use and say so.
        logging.getLogger(__name__).info(
            "building native parameter-server library: make -C %s",
            _NATIVE_DIR)
        if not _make():
            return None
    if not _LIB_PATH.exists():
        return None
    lib = ctypes.CDLL(str(_LIB_PATH))
    try:
        lib.ps_create.restype = ctypes.c_void_p
    except AttributeError:
        # stale .so from before param_server.cpp: rebuild once
        if not _make("clean", "all"):
            return None
        lib = ctypes.CDLL(str(_LIB_PATH))
    lib.ps_create.restype = ctypes.c_void_p
    lib.ps_create.argtypes = [ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
                              ctypes.c_double, ctypes.c_int, ctypes.c_int]
    lib.ps_port.restype = ctypes.c_int
    lib.ps_port.argtypes = [ctypes.c_void_p]
    lib.ps_push.argtypes = [ctypes.c_void_p,
                            ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
    lib.ps_pull.argtypes = [ctypes.c_void_p,
                            ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
    lib.ps_pushes.restype = ctypes.c_int64
    lib.ps_pushes.argtypes = [ctypes.c_void_p]
    lib.ps_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def native_available() -> bool:
    return _load_lib() is not None


def _as_f32(vector) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(vector, dtype=np.float32))


class NativeParameterServer:
    """Native aggregation store, optionally serving the TCP protocol.

    Same surface as ``InMemoryParameterServer`` (+ ``host``/``port`` when
    ``serve=True``, like ``ParameterServerNode``)."""

    def __init__(self, initial: np.ndarray, alpha: Optional[float] = None,
                 num_workers: int = 1, serve: bool = False, port: int = 0):
        lib = _load_lib()
        if lib is None:
            raise ImportError("native parameter-server library unavailable "
                              "(no C++ toolchain?) — use "
                              "parallel.param_server instead")
        self._lib = lib
        init = _as_f32(initial)
        self._n = init.size
        a = float(alpha) if alpha is not None else 1.0 / max(1, num_workers)
        self._h = lib.ps_create(
            init.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._n, a, int(port), 1 if serve else 0)
        if not self._h:
            raise OSError("ps_create failed (bind error?)")
        self.host = "127.0.0.1"
        self.port = lib.ps_port(self._h) if serve else 0
        self._closed = False

    @property
    def pushes(self) -> int:
        return int(self._lib.ps_pushes(self._h))

    def push(self, vector: np.ndarray) -> None:
        v = _as_f32(vector)
        if v.size != self._n:
            raise ValueError(f"push size {v.size} != server {self._n}")
        self._lib.ps_push(
            self._h, v.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._n)

    def pull(self) -> np.ndarray:
        out = np.empty(self._n, np.float32)
        self._lib.ps_pull(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._n)
        return out

    # reference naming aliases (ParameterServerClient.pushNDArray/getNDArray)
    push_ndarray = push
    get_ndarray = pull

    def shutdown(self):
        if not self._closed:
            self._closed = True
            self._lib.ps_destroy(self._h)

    close = shutdown

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass


class NativeParameterServerClient:
    """Python client for the native TCP protocol (raw-f32 framing)."""

    def __init__(self, host: str, port: int):
        self._sock = socket.create_connection((host, port))
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._lock = threading.Lock()

    # Socket I/O under the lock is the PROTOCOL (GL010-annotated): one
    # shared connection carries alternating request/response frames, so
    # each round-trip is one critical section by design — same contract
    # as ParameterServerClient.
    def push_ndarray(self, vector: np.ndarray) -> None:
        v = _as_f32(vector)
        payload = v.tobytes()
        with self._lock:
            self._sock.sendall(   # graftlint: disable=GL010
                b"P" + struct.pack("<Q", len(payload)) + payload)

    def get_ndarray(self) -> np.ndarray:
        with self._lock:
            self._sock.sendall(   # graftlint: disable=GL010
                b"G" + struct.pack("<Q", 0))
            hdr = self._recv_exact(9)   # graftlint: disable=GL010
            if hdr[0:1] != b"R":
                raise ConnectionError("bad response frame")
            (ln,) = struct.unpack("<Q", hdr[1:])
            return np.frombuffer(
                self._recv_exact(ln),   # graftlint: disable=GL010
                dtype=np.float32).copy()

    def _recv_exact(self, n: int) -> bytes:
        chunks = []
        while n:
            c = self._sock.recv(min(n, 1 << 20))
            if not c:
                raise ConnectionError("peer closed")
            chunks.append(c)
            n -= len(c)
        return b"".join(chunks)

    def close(self):
        try:
            with self._lock:
                self._sock.sendall(   # graftlint: disable=GL010
                    b"Q" + struct.pack("<Q", 0))
        except OSError:
            pass
        self._sock.close()
