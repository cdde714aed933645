"""ctypes bindings for the C++ hot-loop helpers (native/sse_scan.cpp).

Loaded lazily; every caller has a pure-Python twin so the framework
runs without the compiled library (build with ``make -C native``). The
library is a build product (git-ignored), so which scanner a process
runs is reported, not assumed: the gateway's ``/health`` carries
``native_scanner: loaded | python``, and ``chip_smoke.py`` builds the
library from the committed sources and requires ``loaded``.
"""

from __future__ import annotations

import ctypes
import os

_LIB = None
_TRIED = False


def _load():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
        "native",
        "libaigw_native.so",
    )
    if not os.path.exists(path):
        return None
    try:
        lib = ctypes.CDLL(path)
        lib.aigw_sse_scan.restype = ctypes.c_int
        lib.aigw_sse_scan.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.aigw_es_scan.restype = ctypes.c_int
        lib.aigw_es_scan.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        _LIB = lib
    except (OSError, AttributeError):
        _LIB = None
    return _LIB


def available() -> bool:
    return _load() is not None


_MAX_EVENTS = 4096
_scan_out = None
_scan_tail = None


def sse_scan(buf: bytes) -> tuple[list[tuple[int, int]], int, bool] | None:
    """Returns ([(event_end, sep_len), ...], tail_offset, truncated) or
    None if the native library is unavailable. ``truncated`` is True when
    the event-count cap was hit and the tail may hold more events."""
    global _scan_out, _scan_tail
    lib = _load()
    if lib is None:
        return None
    if _scan_out is None:  # reuse one output buffer (not thread-shared:
        # each SSEParser runs on the event loop thread)
        _scan_out = (ctypes.c_int32 * (2 * _MAX_EVENTS))()
        _scan_tail = ctypes.c_size_t(0)
    out, tail = _scan_out, _scan_tail
    n = lib.aigw_sse_scan(buf, len(buf), out, _MAX_EVENTS,
                          ctypes.byref(tail))
    return (
        [(out[2 * i], out[2 * i + 1]) for i in range(n)],
        tail.value,
        n >= _MAX_EVENTS,
    )


_MAX_FRAMES = 1024
_es_out = None
_es_tail = None


def es_scan(buf: bytes):
    """AWS event-stream frame scan: returns
    ([(offset, total_len, headers_len), ...], tail, truncated), None when
    the native library is unavailable, or raises ValueError on CRC error —
    mirroring aigw_tpu/translate/eventstream.py semantics."""
    global _es_out, _es_tail
    lib = _load()
    if lib is None:
        return None
    if _es_out is None:
        _es_out = (ctypes.c_int32 * (3 * _MAX_FRAMES))()
        _es_tail = ctypes.c_size_t(0)
    out, tail = _es_out, _es_tail
    n = lib.aigw_es_scan(buf, len(buf), out, _MAX_FRAMES,
                         ctypes.byref(tail))
    if n < 0:
        raise ValueError("event-stream CRC/framing error")
    return (
        [(out[3 * i], out[3 * i + 1], out[3 * i + 2]) for i in range(n)],
        tail.value,
        n >= _MAX_FRAMES,
    )
