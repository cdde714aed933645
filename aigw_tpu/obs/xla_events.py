"""XLA compile tracking — one shared hook instead of per-test hacks.

Two complementary sources, because neither alone answers both questions
operators and tests ask:

1. **Process-wide compile events** via ``jax.monitoring``: JAX records a
   ``/jax/core/compile/backend_compile_duration`` event for every XLA
   backend compile (lowering and jaxpr-trace durations ride sibling
   keys). One module-level listener counts them and sums their wall
   time — the "did anything compile, and how long did it cost" counter
   exported on ``/metrics`` and ``/state``. That event wraps JAX's
   ``compile_or_get_cached``, so a program LOADED from the persistent
   compile cache (utils/boot.py places it) still counts as one: the
   tripwire "zero new programs on the hot path" wants exactly that.
   Which of the two it was is told by the sibling counters
   ``/jax/compilation_cache/cache_hits`` and ``cache_misses``, counted
   here too and exported as ``xla_cache_hits`` / ``xla_cache_misses``.

2. **Per-engine program accounting** via the jit caches of the engine's
   REGISTERED hot-path callables (prefill ladder, decode/verify scans,
   row-update scatters, CoW page copy). ``_cache_size()`` per function is
   the shape-key-level view: which program family grew, and by how many
   compiled shapes. This is what the compile tripwire tests assert on —
   it is immune to other engines compiling concurrently in the same
   process (the monitoring counter is not).

jax.monitoring listeners are process-global and cannot be individually
removed, so installation happens once per process and trackers read
deltas against a baseline taken at construction/checkpoint time.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

#: jax.monitoring duration keys counted as "an XLA compile happened"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: persistent-cache outcome of one compile request (plain events)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_installed = False
_compile_count = 0
_compile_ms = 0.0
_last_compile_at = 0.0
_cache_hits = 0
_cache_misses = 0


def _on_duration(event: str, duration_secs: float, **_kw: Any) -> None:
    global _compile_count, _compile_ms, _last_compile_at
    if event != _COMPILE_EVENT:
        return
    with _lock:
        _compile_count += 1
        _compile_ms += duration_secs * 1e3
        _last_compile_at = time.time()


def _on_event(event: str, **_kw: Any) -> None:
    global _cache_hits, _cache_misses
    if event == _CACHE_HIT_EVENT:
        with _lock:
            _cache_hits += 1
    elif event == _CACHE_MISS_EVENT:
        with _lock:
            _cache_misses += 1


def install() -> bool:
    """Register the process-wide compile listener (idempotent). Returns
    False when jax.monitoring is unavailable — the per-engine program
    accounting still works without it."""
    global _installed
    with _lock:
        if _installed:
            return True
    try:
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:  # noqa: BLE001 — telemetry must never break serving
        return False
    with _lock:
        _installed = True
    return True


def compile_count() -> int:
    """XLA backend compiles observed process-wide since install()."""
    with _lock:
        return _compile_count


def compile_ms() -> float:
    with _lock:
        return _compile_ms


def cache_counts() -> tuple[int, int]:
    """Persistent compile cache (hits, misses) process-wide since
    install(): of the compile events counted above, ``hits`` were loads,
    not builds. Process-wide (not a tracker delta) because weight
    initialisation compiles before any engine exists."""
    with _lock:
        return _cache_hits, _cache_misses


class CompileTracker:
    """Per-engine compile accounting over registered jitted callables,
    plus a delta view of the process-wide monitoring counter."""

    def __init__(self) -> None:
        self.monitoring = install()
        self._fns: dict[str, Callable] = {}
        self._base_count = compile_count()
        self._base_ms = compile_ms()

    # -- registration -----------------------------------------------------
    def register(self, name: str, fn: Callable) -> Callable:
        """Track ``fn`` (a jax.jit product) under ``name``; returns it so
        registration composes at the creation site."""
        self._fns[name] = fn
        return fn

    # -- per-engine program view (the tripwire surface) -------------------
    @staticmethod
    def _size(fn: Callable) -> int:
        get = getattr(fn, "_cache_size", None)
        if get is None:
            return 0
        try:
            return int(get())
        except Exception:  # noqa: BLE001 — private API; fail soft
            return 0

    def programs(self) -> dict[str, int]:
        """Registered program family → compiled-shape count."""
        return {name: self._size(fn) for name, fn in self._fns.items()}

    def program_count(self) -> int:
        return sum(self.programs().values())

    # -- process-wide event view ------------------------------------------
    def compiles(self) -> int:
        """Compile events observed since this tracker was constructed."""
        return compile_count() - self._base_count

    def compiles_total_ms(self) -> float:
        return compile_ms() - self._base_ms

    # -- checkpoint/delta (warmup tripwires) ------------------------------
    def checkpoint(self) -> tuple[int, int]:
        return (self.program_count(), compile_count())

    def compiles_since(self, cp: tuple[int, int]) -> int:
        """New compiled programs across this engine's registered
        callables since ``cp`` — the precise zero-compile-after-warmup
        assertion (other engines in the process don't pollute it)."""
        return self.program_count() - cp[0]

    def snapshot(self) -> dict[str, Any]:
        return {
            "monitoring": self.monitoring,
            "xla_compiles": self.compiles(),
            "xla_compile_ms": round(self.compiles_total_ms(), 3),
            "programs": self.programs(),
            "program_count": self.program_count(),
        }
