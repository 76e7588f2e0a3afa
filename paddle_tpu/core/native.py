"""ctypes loader for the native runtime (csrc/runtime.cc).

The shared library is built lazily with g++ on first use and cached next to
the source (pybind11 is not in this image; the C ABI + ctypes is the
binding layer — SURVEY §2.2 "Pybind bindings" altitude). Every consumer
must degrade gracefully when the toolchain is unavailable:
`load_native()` returns None and the pure-Python fallbacks take over.

Native components exposed here:
  TCPStore / TCPStoreServer  — rendezvous KV
      (parity: paddle/fluid/distributed/store/tcp_store.cc :: TCPStore,
      MasterDaemon)
  NativeQueue                — bounded blocking queue; DataLoader prefetch
      (parity: the reference's native buffered-reader machinery)
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading

_LIB = None
_LIB_LOCK = threading.Lock()
_BUILD_FAILED = False


def _src_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "csrc", "runtime.cc")


def _lib_path() -> str:
    """The built library, named by the source's content hash: only a
    build of THIS csrc/runtime.cc is ever loaded — a .so left in the
    checkout by another tree or toolchain is not an input."""
    import hashlib
    with open(_src_path(), "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(os.path.dirname(_src_path()),
                        f"libpaddle_tpu_runtime-{tag}.so")


def load_native():
    """Build (once) and dlopen the runtime; None if unavailable — said
    once on stderr, so a missing toolchain is not a silent downgrade."""
    global _LIB, _BUILD_FAILED
    if _LIB is not None:
        return _LIB
    if _BUILD_FAILED or os.environ.get("PADDLE_TPU_NO_NATIVE") == "1":
        return None
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB
        try:
            src, lib = _src_path(), _lib_path()
            if not os.path.exists(lib):
                # pid-unique scratch: concurrently-launched ranks all see
                # the missing .so and build; a shared ".tmp" makes them
                # clobber each other's half-written output (os.replace of
                # a file another rank is still writing), taking the
                # native runtime down for the whole job
                tmp = f"{lib}.{os.getpid()}.tmp"
                try:
                    subprocess.run(
                        ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                         "-pthread", src, "-o", tmp],
                        check=True, capture_output=True, timeout=120)
                    os.replace(tmp, lib)
                finally:
                    if os.path.exists(tmp):
                        os.remove(tmp)
            L = ctypes.CDLL(lib)
        except Exception as e:
            _BUILD_FAILED = True
            detail = getattr(e, "stderr", None)
            detail = detail.decode(errors="replace").strip()[-400:] \
                if detail else ""
            print(f"paddle_tpu: native runtime unavailable "
                  f"({type(e).__name__}: {e}) {detail}— building "
                  "csrc/runtime.cc needs g++; pure-Python fallbacks "
                  "take over", file=sys.stderr)
            return None
        # signatures
        L.pd_store_master_start.restype = ctypes.c_void_p
        L.pd_store_master_start.argtypes = [ctypes.c_int]
        L.pd_store_master_port.restype = ctypes.c_int
        L.pd_store_master_port.argtypes = [ctypes.c_void_p]
        L.pd_store_master_stop.argtypes = [ctypes.c_void_p]
        L.pd_store_client_connect.restype = ctypes.c_void_p
        L.pd_store_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                              ctypes.c_int]
        L.pd_store_client_close.argtypes = [ctypes.c_void_p]
        L.pd_store_set.restype = ctypes.c_int
        L.pd_store_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_char_p, ctypes.c_int]
        L.pd_store_get.restype = ctypes.c_int
        L.pd_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_char_p, ctypes.c_int]
        L.pd_store_add.restype = ctypes.c_int
        L.pd_store_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_longlong,
                                   ctypes.POINTER(ctypes.c_longlong)]
        L.pd_store_wait.restype = ctypes.c_int
        L.pd_store_wait.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_int]
        L.pd_queue_new.restype = ctypes.c_void_p
        L.pd_queue_new.argtypes = [ctypes.c_int]
        L.pd_queue_close.argtypes = [ctypes.c_void_p]
        L.pd_queue_free.argtypes = [ctypes.c_void_p]
        L.pd_queue_put.restype = ctypes.c_int
        L.pd_queue_put.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                   ctypes.c_int]
        L.pd_queue_get.restype = ctypes.c_void_p
        L.pd_queue_get.argtypes = [ctypes.c_void_p, ctypes.c_int]
        L.pd_queue_size.restype = ctypes.c_int
        L.pd_queue_size.argtypes = [ctypes.c_void_p]
        _LIB = L
        return L


class TCPStoreServer:
    """Master daemon; bind port 0 for an ephemeral port (read .port)."""

    def __init__(self, port: int = 0):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.pd_store_master_start(port)
        if not self._h:
            raise OSError(f"TCPStoreServer: cannot bind port {port}")
        self.port = lib.pd_store_master_port(self._h)

    def stop(self):
        if self._h:
            self._lib.pd_store_master_stop(self._h)
            self._h = None

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


class TCPStore:
    """Client with the reference TCPStore surface: set/get/add/wait."""

    def __init__(self, host: str, port: int, timeout_s: float = 30.0):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.pd_store_client_connect(
            host.encode(), port, int(timeout_s * 1000))
        if not self._h:
            raise ConnectionError(f"TCPStore: cannot reach {host}:{port}")

    def set(self, key: str, value: bytes):
        if self._lib.pd_store_set(self._h, key.encode(), value,
                                  len(value)) != 0:
            raise IOError("store set failed")

    def get(self, key: str, max_len: int = 1 << 20):
        buf = ctypes.create_string_buffer(max_len)
        n = self._lib.pd_store_get(self._h, key.encode(), buf, max_len)
        if n < 0:
            return None
        # value larger than the buffer: the C side reports the full length —
        # retry with an exact-size buffer instead of silently truncating
        # (loop: the value may have grown again between calls)
        for _ in range(4):
            if n <= max_len:
                return buf.raw[:n]
            max_len = n
            buf = ctypes.create_string_buffer(max_len)
            n = self._lib.pd_store_get(self._h, key.encode(), buf, max_len)
            if n < 0:
                return None
        raise IOError(f"store get: value for {key!r} keeps growing")

    def add(self, key: str, delta: int) -> int:
        out = ctypes.c_longlong()
        if self._lib.pd_store_add(self._h, key.encode(), delta,
                                  ctypes.byref(out)) != 0:
            raise IOError("store add failed")
        return out.value

    def wait(self, key: str, timeout_s: float = 30.0):
        if self._lib.pd_store_wait(self._h, key.encode(),
                                   int(timeout_s * 1000)) != 0:
            raise TimeoutError(f"store wait({key}) timed out")

    def close(self):
        if self._h:
            self._lib.pd_store_client_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeQueue:
    """Bounded blocking queue of integer tokens (1-based; 0 is reserved)."""

    def __init__(self, capacity: int):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.pd_queue_new(capacity)

    def put(self, token: int, timeout_s: float = 10.0) -> bool:
        assert token > 0
        return self._lib.pd_queue_put(self._h, ctypes.c_void_p(token),
                                      int(timeout_s * 1000)) == 0

    def get(self, timeout_s: float = 10.0):
        r = self._lib.pd_queue_get(self._h, int(timeout_s * 1000))
        return None if not r else int(r)

    def qsize(self) -> int:
        return self._lib.pd_queue_size(self._h)

    def close(self):
        if self._h:
            self._lib.pd_queue_close(self._h)

    def free(self):
        if self._h:
            self._lib.pd_queue_close(self._h)
            self._lib.pd_queue_free(self._h)
            self._h = None
