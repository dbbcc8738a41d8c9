"""ctypes binding for the C++ socket shuttle, with a pure-Python fallback.

Builds ``native/shuttle.cpp`` on first use (g++ -O2 -shared -fPIC) into a
file named by a hash of the source, so a binary built from other source (or
copied in from another checkout) is never loaded. When the toolchain or
build is unavailable the Python implementation (threads + stdlib sockets —
IO releases the GIL anyway, but framing runs in Python) keeps everything
working; which of the two planes a process runs is logged once and
published as the ``distar_shuttle_native`` gauge.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import socket
import struct
import subprocess
import threading
import time
from typing import Optional, Tuple

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "native", "shuttle.cpp")
_lib = None
_lib_tried = False
_lib_lock = threading.Lock()


def _build() -> Optional[ctypes.CDLL]:
    with open(_SRC, "rb") as f:
        key = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_DIR, "native", f"libshuttle-{key}.so")
    if not os.path.exists(so):
        # built aside, then renamed: a crashed or concurrent build never
        # leaves a half-written file under the keyed name
        tmp = f"{so}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC, "-lpthread"],
                check=True,
                capture_output=True,
            )
            os.replace(tmp, so)
        except (OSError, subprocess.CalledProcessError):
            return None
    try:
        return ctypes.CDLL(so)
    except OSError:
        return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    with _lib_lock:
        if _lib_tried:
            return _lib
        _lib_tried = True
        lib = _build()
        from ..obs import get_registry

        get_registry().gauge(
            "distar_shuttle_native",
            "1 = the C++ shuttle plane is loaded, 0 = the Python fallback runs",
        ).set(0.0 if lib is None else 1.0)
        logging.getLogger(__name__).info(
            "shuttle plane: %s", "python fallback" if lib is None else "native")
        if lib is None:
            return None
        lib.shuttle_serve.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
        ]
        lib.shuttle_serve.restype = ctypes.c_int
        lib.shuttle_fetch.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.shuttle_fetch.restype = ctypes.c_int
        lib.shuttle_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        lib.shuttlez_bound.argtypes = [ctypes.c_uint64]
        lib.shuttlez_bound.restype = ctypes.c_uint64
        lib.shuttlez_compress.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
        ]
        lib.shuttlez_compress.restype = ctypes.c_int64
        lib.shuttlez_decompress.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
        ]
        lib.shuttlez_decompress.restype = ctypes.c_int64
        lib.shuttlez_crc32.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint32,
        ]
        lib.shuttlez_crc32.restype = ctypes.c_uint32
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


# ----------------------------------------------------------------- checksum
def crc32(data, crc: int = 0) -> int:
    """IEEE CRC-32, bit-identical to ``zlib.crc32`` but faster via the
    native slice-by-8 kernel (this image's zlib is unvectorized, and the
    shm ring transport checksums every payload byte twice — write +
    verify). Accepts bytes or buffer views; degrades to ``zlib.crc32``
    for tiny inputs (call overhead) and .so-less hosts — the value is
    identical either way. Views go through numpy's zero-copy data pointer
    rather than ctypes ``from_buffer``: the latter forms a reference
    cycle (_objects -> memoryview) that pins the underlying mmap until a
    GC pass, which made SharedMemory teardown raise BufferError."""
    import zlib

    lib = _load()
    n = len(data)
    if lib is None or n < 1024:
        return zlib.crc32(data, crc)
    if isinstance(data, bytes):
        # c_char_p conversion borrows the bytes' internal buffer — no copy
        return lib.shuttlez_crc32(data, n, crc)
    try:
        import numpy as np

        arr = np.frombuffer(data, dtype=np.uint8)  # zero-copy, refcounted
        return lib.shuttlez_crc32(
            ctypes.cast(arr.ctypes.data, ctypes.c_char_p), arr.nbytes, crc)
    except (TypeError, ValueError, BufferError, ImportError):
        return zlib.crc32(data, crc)


# ------------------------------------------------------- lz4-block codec
def lz_compress(data: bytes) -> Optional[bytes]:
    """LZ4-block compress via the native codec; None when the native lib is
    unavailable (callers fall back to zlib)."""
    lib = _load()
    if lib is None:
        return None
    cap = lib.shuttlez_bound(len(data))
    out = (ctypes.c_uint8 * cap)()
    n = lib.shuttlez_compress(data, len(data), out, cap)
    if n < 0:
        raise OSError(f"shuttlez_compress failed: {n}")
    return bytes(bytearray(out)[:n])


def lz_decompress(blob: bytes, decompressed_len: int) -> bytes:
    """LZ4-block decompress; uses the native codec when available, else a
    pure-Python decoder (the format is trivially decodable)."""
    lib = _load()
    if lib is not None:
        out = (ctypes.c_uint8 * decompressed_len)()
        n = lib.shuttlez_decompress(blob, len(blob), out, decompressed_len)
        if n < 0:
            raise ValueError(f"shuttlez_decompress failed: {n}")
        if n != decompressed_len:
            raise ValueError(f"decompressed {n} != expected {decompressed_len}")
        return bytes(out)
    return _py_lz_decompress(blob, decompressed_len)


def _py_lz_decompress(blob: bytes, decompressed_len: int) -> bytes:
    """Pure-Python LZ4-block decoder (fallback when g++/the .so is absent).

    Raises ValueError (never IndexError) on truncated/malformed streams so
    callers see the same error contract as the native decoder.
    """
    src = memoryview(blob)
    out = bytearray()
    i, end = 0, len(blob)

    def read_byte(pos: int) -> int:
        if pos >= end:
            raise ValueError("malformed lz stream (truncated)")
        return src[pos]

    while i < end:
        token = src[i]
        i += 1
        lit = token >> 4
        if lit == 15:
            while True:
                b = read_byte(i)
                i += 1
                lit += b
                if b != 255:
                    break
        if i + lit > end:
            raise ValueError("malformed lz stream (truncated literals)")
        out += src[i : i + lit]
        i += lit
        if i >= end:
            break
        if i + 2 > end:
            raise ValueError("malformed lz stream (truncated offset)")
        offset = src[i] | (src[i + 1] << 8)
        i += 2
        if offset == 0 or offset > len(out):
            raise ValueError("malformed lz stream (bad offset)")
        mlen = token & 0x0F
        if mlen == 15:
            while True:
                b = read_byte(i)
                i += 1
                mlen += b
                if b != 255:
                    break
        mlen += 4
        start = len(out) - offset
        if offset >= mlen:
            out += out[start : start + mlen]
        else:
            for k in range(mlen):  # overlapping copy must be sequential
                out.append(out[start + k])
    if len(out) != decompressed_len:
        raise ValueError(f"decompressed {len(out)} != expected {decompressed_len}")
    return bytes(out)


def _metrics():
    from ..obs import get_registry

    return get_registry()


def serve(payload: bytes, accept_count: int = 1, timeout_ms: int = 30_000) -> int:
    """Serve ``payload`` (framed) on an ephemeral port to up to
    ``accept_count`` connections; returns the port."""
    reg = _metrics()
    reg.counter("distar_shuttle_serves_total", "serve windows opened").inc()
    reg.counter("distar_shuttle_tx_bytes_total", "payload bytes offered").inc(len(payload))
    lib = _load()
    if lib is not None:
        buf = (ctypes.c_uint8 * len(payload)).from_buffer_copy(payload)
        port = lib.shuttle_serve(buf, len(payload), accept_count, timeout_ms)
        if port > 0:
            return port
        raise OSError(f"shuttle_serve failed: {port}")
    return _py_serve(payload, accept_count, timeout_ms)


def fetch(host: str, port: int, timeout_ms: int = 30_000) -> bytes:
    """Fetch one framed payload from host:port."""
    reg = _metrics()
    lib = _load()
    try:
        if lib is not None:
            out = ctypes.POINTER(ctypes.c_uint8)()
            out_len = ctypes.c_uint64()
            rc = lib.shuttle_fetch(
                host.encode(), port, timeout_ms, ctypes.byref(out), ctypes.byref(out_len)
            )
            if rc != 0:
                raise OSError(f"shuttle_fetch failed: {rc}")
            try:
                blob = ctypes.string_at(out, out_len.value)
            finally:
                lib.shuttle_free(out)
        else:
            blob = _py_fetch(host, port, timeout_ms)
    except (OSError, ConnectionError):
        reg.counter("distar_shuttle_fetch_errors_total", "failed fetches").inc()
        raise
    reg.counter("distar_shuttle_fetches_total", "payloads fetched").inc()
    reg.counter("distar_shuttle_rx_bytes_total", "payload bytes received").inc(len(blob))
    return blob


# ------------------------------------------------------------ python fallback
def _py_serve(payload: bytes, accept_count: int, timeout_ms: int) -> int:
    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind(("0.0.0.0", 0))
    listener.listen(16)
    listener.settimeout(timeout_ms / 1000.0)
    port = listener.getsockname()[1]
    framed = struct.pack(">Q", len(payload)) + payload
    reg = _metrics()
    reg.gauge("distar_shuttle_active_serves", "serve windows currently open").inc()

    def run():
        served = 0
        try:
            for _ in range(accept_count):
                try:
                    conn, _ = listener.accept()
                except socket.timeout:
                    break
                # accepted sockets do NOT inherit the listener timeout
                # (always-blocking since py3.4): without this, one consumer
                # that connects and never reads parks sendall forever and
                # the serve window never expires
                conn.settimeout(timeout_ms / 1000.0)
                try:
                    with conn:
                        conn.sendall(framed)
                    served += 1
                except OSError:
                    continue  # hung/reset consumer: window stays open for others
        finally:
            listener.close()
            reg.gauge("distar_shuttle_active_serves").dec()
            if served < accept_count:
                # expired serve window: the payload copies nobody fetched
                # are drops, the loss side of broker-depth accounting
                reg.counter(
                    "distar_shuttle_drops_total", "serve-window expiries (unfetched payloads)"
                ).inc(accept_count - served)

    threading.Thread(target=run, daemon=True).start()
    return port


def _py_fetch(host: str, port: int, timeout_ms: int) -> bytes:
    # timeout_ms is a DEADLINE over the whole fetch (connect + every recv),
    # not a per-recv idle timeout: a peer trickling one byte per timeout
    # window used to hold the fetch open indefinitely
    deadline = time.monotonic() + timeout_ms / 1000.0
    with socket.create_connection((host, port), timeout=timeout_ms / 1000.0) as s:

        def recv_exact(n: int) -> bytes:
            chunks = []
            while n > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise socket.timeout(f"fetch deadline ({timeout_ms}ms) exceeded")
                s.settimeout(remaining)
                chunk = s.recv(min(n, 1 << 20))
                if not chunk:
                    raise ConnectionError("short read")
                chunks.append(chunk)
                n -= len(chunk)
            return b"".join(chunks)

        (length,) = struct.unpack(">Q", recv_exact(8))
        return recv_exact(length)
