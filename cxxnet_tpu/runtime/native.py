"""ctypes bindings to the native runtime (runtime/libcxxnet_runtime.so).

The native library provides a background-threaded BinaryPage stream reader
and libjpeg decoding — the C++ path the reference used for its data pipeline
(``iter_thread_imbin``/``thread_buffer``/``decoder``).  Build with
``make -C runtime``; everything degrades gracefully to the pure-Python
implementations when the .so is absent (``native_available()`` is False).
Set ``CXXNET_NO_NATIVE=1`` to force the Python path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
from typing import Iterator, Optional

import numpy as np

_LIB = None
_TRIED = False


def _lib_path() -> str:
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(here, 'runtime', 'libcxxnet_runtime.so')


def _load():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    if os.environ.get('CXXNET_NO_NATIVE') == '1':
        return None
    path = _lib_path()
    if not os.path.exists(path):
        # built on first use; a failed build is said once, with make's
        # own error, because the Python path that takes over is slower
        makefile_dir = os.path.dirname(path)
        if os.path.exists(os.path.join(makefile_dir, 'Makefile')):
            try:
                r = subprocess.run(['make', '-s', '-C', makefile_dir],
                                   capture_output=True, text=True)
                err = None
                if r.returncode:
                    lines = (r.stderr or r.stdout).strip().splitlines()
                    err = next((ln for ln in lines if 'error' in ln.lower()),
                               f'make rc={r.returncode}')
            except OSError as e:            # no make on this machine
                err = str(e)
            if err is not None:
                print(f'cxxnet_tpu: building {path} failed ({err}); using '
                      f'the pure-Python page reader and JPEG decoder',
                      file=sys.stderr)
    if not os.path.exists(path):
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    lib.cxr_open.restype = ctypes.c_void_p
    lib.cxr_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.cxr_open_order.restype = ctypes.c_void_p
    lib.cxr_open_order.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.c_int]
    lib.cxr_next_page.restype = ctypes.c_int
    lib.cxr_next_page.argtypes = [ctypes.c_void_p]
    lib.cxr_get_obj.restype = ctypes.c_void_p
    lib.cxr_get_obj.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                ctypes.POINTER(ctypes.c_size_t)]
    lib.cxr_close.argtypes = [ctypes.c_void_p]
    lib.cxr_jpeg_decode.restype = ctypes.c_int
    lib.cxr_jpeg_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_void_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
    _LIB = lib
    return lib


def native_available() -> bool:
    return _load() is not None


class NativePageReader:
    """Iterates the blobs of a BinaryPage stream with C++-side prefetch.

    ``order`` (a sequence of page indices) switches the reader thread to
    seek-based random access — the imgbinx shuffled-epoch path — still
    prefetching ``prefetch_pages`` ahead."""

    def __init__(self, path: str, prefetch_pages: int = 2, order=None):
        lib = _load()
        if lib is None:
            raise RuntimeError('native runtime not available')
        self._lib = lib
        if order is not None:
            arr = np.ascontiguousarray(order, dtype=np.int64)
            self._h = lib.cxr_open_order(
                path.encode(),
                arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                len(arr), prefetch_pages)
        else:
            self._h = lib.cxr_open(path.encode(), prefetch_pages)
        if not self._h:
            raise IOError(f'cannot open {path}')

    def iter_pages(self) -> Iterator[list]:
        """Yield each page's blobs as a list (page granularity is the unit
        of distributed sharding and shuffle)."""
        lib = self._lib
        while True:
            n = lib.cxr_next_page(self._h)
            if n == -2:
                raise RuntimeError('imgbin: truncated page (ordered read '
                                   'past end of .bin)')
            if n < 0:
                return
            page = []
            for r in range(n):
                size = ctypes.c_size_t()
                ptr = lib.cxr_get_obj(self._h, r, ctypes.byref(size))
                page.append(ctypes.string_at(ptr, size.value))
            yield page

    def __iter__(self) -> Iterator[bytes]:
        for page in self.iter_pages():
            yield from page

    def close(self) -> None:
        if self._h:
            self._lib.cxr_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # lint: allow(fault-taxonomy): interpreter-teardown finalizer; raising in __del__ aborts shutdown
            pass


def decode_jpeg(blob: bytes) -> Optional[np.ndarray]:
    """Decode a JPEG blob to (h, w, 3) uint8 RGB via libjpeg; None if the
    native lib is unavailable or the blob is not a decodable JPEG."""
    lib = _load()
    if lib is None:
        return None
    w = ctypes.c_int()
    h = ctypes.c_int()
    rc = lib.cxr_jpeg_decode(blob, len(blob), None, 0,
                             ctypes.byref(w), ctypes.byref(h))
    if rc != 0:
        return None
    out = np.empty((h.value, w.value, 3), np.uint8)
    rc = lib.cxr_jpeg_decode(blob, len(blob),
                             out.ctypes.data_as(ctypes.c_void_p), out.nbytes,
                             ctypes.byref(w), ctypes.byref(h))
    if rc != 0:
        return None
    return out
