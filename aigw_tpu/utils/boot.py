"""Process boot for everything that opens an accelerator.

One rule, used by ``aigw_tpu tpuserve``, the launcher's replica child
(``aigw_tpu.tpuserve.child``) and the kernel parity child: **the platform is
the one somebody named** (``--platform``, else ``JAX_PLATFORMS``). When
nobody named one, a TPU is required and boot fails naming what JAX found
instead — JAX's own default would quietly hand back the CPU, and a
server, a benchmark or an interpreted Pallas kernel would then run
somewhere nobody asked for.

The same function places the persistent compile cache: wherever
``JAX_COMPILATION_CACHE_DIR`` says when it is set (nothing is set in
code then), else ``<checkout>/.jax_cache``. The path is part of the
cache key's world — a directory that moves never hits — so it is never
derived from a temporary name, a pid or a time.

One process per chip: a process that has called this holds the chip.
Parents that start children needing it (``chip_smoke.py``, the gateway's
``LocalProcessLauncher``) must stay off JAX themselves.

The module also keeps the process's **boot timeline** (:data:`BOOT`):
where the seconds between the process's start and a replica's first
``/health`` ``ok`` went, as contiguous phases of self time.
"""

from __future__ import annotations

import os
import time

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the fixed fallback cache location (git-ignored)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


#: the boot timeline's phases, in the order a replica passes them
BOOT_PHASES = ("import", "backend", "weights", "weights_layout", "engine",
               "warmup", "listen")


def _process_age_ns() -> int:
    """Nanoseconds since the OS started this process (``/proc/self/stat``
    field 22 against ``/proc/uptime``); 0 where there is no ``/proc``,
    which is why the entry points import this module first."""
    try:
        with open("/proc/self/stat") as f:
            # the command name (field 2) may hold spaces and brackets
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        age_s = uptime_s - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0
    return max(0, int(age_s * 1e9))


class BootLedger:
    """Where the boot's time went: nanoseconds per phase of
    ``BOOT_PHASES`` from the process's start to ``ready()``, as
    ``obs/flight.py``'s ``LoopLedger`` keeps them for the engine loop.
    ``enter(phase)`` closes the running phase at one clock read and
    opens the next, so the phases are contiguous and each holds SELF
    time: code that enters a phase inside another hands the name
    ``enter`` returned back to it when done. After ``ready()`` the
    ledger stands still (a second server in one process adds nothing).
    Marked by the thread that boots; read by any."""

    def __init__(self) -> None:
        self.t0 = time.perf_counter_ns() - _process_age_ns()
        self.t = self.t0
        self.cur = BOOT_PHASES[0]
        self.ns = dict.fromkeys(BOOT_PHASES, 0)
        self.is_ready = False

    def enter(self, phase: str) -> str:
        """Switch to ``phase``; returns the phase that was running."""
        prev = self.cur
        if not self.is_ready:
            now = time.perf_counter_ns()
            self.ns[prev] += now - self.t
            self.t = now
            self.cur = phase
        return prev

    def ready(self) -> None:
        """The replica would answer ``/health`` from here on."""
        self.enter(self.cur)
        self.is_ready = True

    def since_start_ms(self) -> float:
        return (time.perf_counter_ns() - self.t0) / 1e6

    def flat(self) -> dict[str, float]:
        """The timeline as flat /state keys: ``boot_<phase>_ms`` and
        their sum ``boot_ready_ms`` (what is accounted so far, until
        ``ready()``)."""
        out = {f"boot_{p}_ms": round(self.ns[p] / 1e6, 3)
               for p in BOOT_PHASES}
        out["boot_ready_ms"] = round(sum(self.ns.values()) / 1e6, 3)
        return out


#: this process's boot timeline (there is one boot a process)
BOOT = BootLedger()


class BootError(RuntimeError):
    """No platform was named and JAX found no TPU."""


def boot_jax(platform: str = "") -> str:
    """Select the platform, place the compile cache, initialise the
    backend. Returns the platform JAX runs on. Raises :class:`BootError`
    when no platform was named and the default backend is not a TPU.
    Its own time is the boot timeline's ``backend`` phase; what follows
    is ``engine`` until somebody enters another."""
    BOOT.enter("backend")
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    named = platform or os.environ.get("JAX_PLATFORMS", "")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # keep the small programs too (row-update scatters, page movers):
    # a warm boot should find every program of the cold one
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    found = jax.default_backend()
    if not named and found != "tpu":
        raise BootError(
            f"no TPU: JAX found platform {found!r} and nobody named one. "
            "Serving on a CPU must be asked for explicitly "
            "(--platform cpu or JAX_PLATFORMS=cpu).")
    BOOT.enter("engine")
    return found


def compile_cache_dir() -> str:
    """The directory the persistent compile cache lives in."""
    import jax

    return str(jax.config.jax_compilation_cache_dir or "")
