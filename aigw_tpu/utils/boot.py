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
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the fixed fallback cache location (git-ignored)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


class BootError(RuntimeError):
    """No platform was named and JAX found no TPU."""


def boot_jax(platform: str = "") -> str:
    """Select the platform, place the compile cache, initialise the
    backend. Returns the platform JAX runs on. Raises :class:`BootError`
    when no platform was named and the default backend is not a TPU."""
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
    return found


def compile_cache_dir() -> str:
    """The directory the persistent compile cache lives in."""
    import jax

    return str(jax.config.jax_compilation_cache_dir or "")
