"""XLA compile tracking — one shared hook instead of per-test hacks.

Two complementary sources, because neither alone answers both questions
operators and tests ask:

1. **Process-wide program loads** via ``jax.monitoring``. JAX sends
   three duration events for every program it builds, each with the
   program's ``fun_name``, one after the other on the thread that asked:
   ``jaxpr_trace_duration`` (Python tracing; ``fun_name`` is the bare
   function), ``jaxpr_to_mlir_module_duration`` (lowering) and
   ``backend_compile_duration`` (``fun_name`` ``jit(<function>)``). The
   third wraps ``compile_or_get_cached``, so a program LOADED from the
   persistent compile cache (utils/boot.py places it) counts as one too
   — the tripwire "zero new programs on the hot path" wants exactly
   that — and the cache's own events fire inside it on the same thread:
   ``cache_hits`` with ``cache_retrieval_time_sec`` (the read and
   ``deserialize_and_load``) and ``compile_time_saved_sec``, or
   ``cache_misses``. One module-level listener folds them into the
   :class:`LoadLedger`: a record per program name, a bounded log of
   load events, and process-wide totals per stage for ``/state`` and
   ``/metrics``. Once the server has called :func:`mark_ready` every
   load is *late*: a request waited for it.

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

import collections
import threading
from typing import Any, Callable

from aigw_tpu.utils.boot import BOOT

#: jax.monitoring duration keys: the three stages of one program
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: ... the third of which counts as "an XLA compile happened"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: inside a backend span that the persistent cache served
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
#: persistent-cache outcome of one compile request (plain events)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: load events kept for ``/debug/programs`` (newest)
LOG_CAPACITY = 256

#: a program's record; ``retrieval_ms`` lies inside ``backend_ms``
_RECORD = ("requests", "trace_ms", "lower_ms", "backend_ms",
           "retrieval_ms", "saved_ms", "hits", "misses")
#: the ledger's process-wide totals, under their ``/state`` names
#: (``xla_compiles`` / ``xla_compile_ms`` are the engine's deltas of the
#: first two, as before)
_TOTALS = ("compiles", "compile_ms", "xla_cache_hits", "xla_cache_misses",
           "xla_trace_ms", "xla_lower_ms", "xla_retrieval_ms",
           "xla_late_loads", "xla_late_ms", "xla_late_trace_ms",
           "xla_late_lower_ms", "xla_late_retrieval_ms")


class LoadLedger:
    """What every program of this process cost to get, by stage.

    Fed by the listener on whichever thread builds the program; the
    stages of one program arrive in order on one thread, so what is
    pending lives in a thread-local and the lock is taken once a
    program, at its backend event. A trace event fires for every jitted
    function met while tracing another (thousands a program, of a few
    dozen names): each is one thread-local store under its name, and a
    program's ``trace`` is the duration of the last one under ITS name
    before its lowering, which holds the others. (A program built
    eagerly INSIDE another's trace is counted in both.)"""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._pending = threading.local()
        self.programs: dict[str, dict[str, float]] = {}
        self.log: collections.deque[dict] = collections.deque(
            maxlen=LOG_CAPACITY)
        self.totals: dict[str, float] = dict.fromkeys(_TOTALS, 0)
        self.ready = False
        # thread ident -> called with a late load's log entry on that
        # thread, before the entry is filed (it may add to it)
        self.late_hooks: dict[int, Callable[[dict], None]] = {}

    def on_event(self, event: str, **_kw: Any) -> None:
        if event in (_CACHE_HIT_EVENT, _CACHE_MISS_EVENT):
            hit = self._pending.hit = event == _CACHE_HIT_EVENT
            with self._lock:
                self.totals[
                    "xla_cache_hits" if hit else "xla_cache_misses"] += 1

    def on_duration(self, event: str, duration_secs: float,
                    fun_name: str = "", **_kw: Any) -> None:
        p = self._pending
        ms = duration_secs * 1e3
        if event == _TRACE_EVENT:
            p.__dict__.setdefault("traced", {})[fun_name] = ms
        elif event == _LOWER_EVENT:
            # the trace of THIS program: ``jit(f)`` lowers what the last
            # trace of ``f`` made (none if its jaxpr was cached)
            traced = p.__dict__.pop("traced", {})
            bare = fun_name[fun_name.find("(") + 1:-1]
            p.staged = (traced.get(bare, 0.0), ms)
        elif event == _RETRIEVAL_EVENT:
            p.retrieval_ms = ms
        elif event == _SAVED_EVENT:
            p.saved_ms = ms
        elif event == _COMPILE_EVENT:
            self._loaded(fun_name, ms)

    def _loaded(self, fn: str, backend_ms: float) -> None:
        p = self._pending
        trace_ms, lower_ms = p.__dict__.pop("staged", (0.0, 0.0))
        hit = p.__dict__.pop("hit", None)  # None: the cache was not asked
        late = self.ready
        entry = {
            "t_ms": round(BOOT.since_start_ms(), 3), "fn": fn,
            "trace_ms": round(trace_ms, 3), "lower_ms": round(lower_ms, 3),
            "backend_ms": round(backend_ms, 3),
            "retrieval_ms": round(p.__dict__.pop("retrieval_ms", 0.0), 3),
            "hit": hit, "late": late, "phase": "",
        }
        saved_ms = p.__dict__.pop("saved_ms", 0.0)
        if late:
            hook = self.late_hooks.get(threading.get_ident())
            if hook is not None:
                try:
                    hook(entry)
                except Exception:  # noqa: BLE001 — telemetry must never
                    pass           # break the thread that compiles
        with self._lock:
            rec = self.programs.get(fn)
            if rec is None:
                rec = self.programs[fn] = dict.fromkeys(_RECORD, 0)
            rec["requests"] += 1
            rec["saved_ms"] += saved_ms
            rec["backend_ms"] += entry["backend_ms"]
            if hit is not None:
                rec["hits" if hit else "misses"] += 1
            t = self.totals
            t["compiles"] += 1
            t["compile_ms"] += entry["backend_ms"]
            for stage in ("trace", "lower", "retrieval"):
                ms = entry[stage + "_ms"]
                rec[stage + "_ms"] += ms
                t[f"xla_{stage}_ms"] += ms
                if late:
                    t[f"xla_late_{stage}_ms"] += ms
            if late:
                t["xla_late_loads"] += 1
                t["xla_late_ms"] += (entry["trace_ms"] + entry["lower_ms"]
                                     + entry["backend_ms"])
            self.log.append(entry)

    def snapshot(self) -> dict[str, Any]:
        """The table, the log (oldest first) and the totals, for
        ``/debug/programs``."""
        with self._lock:
            return {
                "programs": {
                    fn: {k: round(v, 3) for k, v in rec.items()}
                    for fn, rec in sorted(self.programs.items())},
                "log": [dict(e) for e in self.log],
                "totals": {k: round(v, 3) for k, v in self.totals.items()},
                "log_capacity": LOG_CAPACITY,
                "ready": self.ready,
            }

    def total(self, *keys: str) -> tuple:
        with self._lock:
            return tuple(self.totals[k] for k in keys)


#: the process's ledger: jax.monitoring has one set of listeners
LEDGER = LoadLedger()
_installed = False


def install() -> bool:
    """Register the process-wide listener (idempotent). Returns False
    when jax.monitoring is unavailable — the per-engine program
    accounting still works without it."""
    global _installed
    with LEDGER._lock:
        if _installed:
            return True
    try:
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(LEDGER.on_duration)
        monitoring.register_event_listener(LEDGER.on_event)
    except Exception:  # noqa: BLE001 — telemetry must never break serving
        return False
    with LEDGER._lock:
        _installed = True
    return True


def mark_ready() -> None:
    """The server answers from now on: every later load is late."""
    LEDGER.ready = True


def compile_count() -> int:
    """XLA backend compiles observed process-wide since install()."""
    return LEDGER.total("compiles")[0]


class CompileTracker:
    """Per-engine compile accounting over registered jitted callables,
    plus a delta view of the process-wide monitoring counter."""

    def __init__(self) -> None:
        self.monitoring = install()
        self._fns: dict[str, Callable] = {}
        self._base_count, self._base_ms = LEDGER.total(
            "compiles", "compile_ms")

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
    def totals(self) -> dict[str, float]:
        """The ``xla_*`` counters of ``EngineStats``, at one read of the
        ledger: compile events and their backend milliseconds since
        this tracker was constructed, and the process-wide rest (the
        cache's hits and misses among them: weight initialisation
        compiles before any engine exists)."""
        values = dict(zip(_TOTALS, LEDGER.total(*_TOTALS)))
        out = {"xla_compiles": values.pop("compiles") - self._base_count,
               "xla_compile_ms": values.pop("compile_ms") - self._base_ms}
        out.update(values)
        return {k: round(v, 3) for k, v in out.items()}

    # -- checkpoint/delta (warmup tripwires) ------------------------------
    def checkpoint(self) -> tuple[int, int]:
        return (self.program_count(), compile_count())

    def compiles_since(self, cp: tuple[int, int]) -> int:
        """New compiled programs across this engine's registered
        callables since ``cp`` — the precise zero-compile-after-warmup
        assertion (other engines in the process don't pollute it)."""
        return self.program_count() - cp[0]

    def snapshot(self) -> dict[str, Any]:
        totals = self.totals()
        return {
            "monitoring": self.monitoring,
            "xla_compiles": totals["xla_compiles"],
            "xla_compile_ms": totals["xla_compile_ms"],
            "programs": self.programs(),
            "program_count": self.program_count(),
        }
