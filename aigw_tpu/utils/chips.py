"""One process per chip: confining a child process to ONE chip of a
multi-chip TPU host.

A chip belongs to one process at a time. A host with four chips can run
four one-chip ``tpuserve`` replicas only if each child is confined to
its own chip by its ENVIRONMENT, set by whoever launches it before the
child imports jax (``chip_smoke.py``, the gateway's
``LocalProcessLauncher``) — a child cannot confine itself once libtpu
has loaded. This module imports nothing heavy so a launcher that must
stay off JAX can use it.

Found on a v5litepod-4 host (libtpu 0.0.34, PR 21): these three
variables are necessary and sufficient. ``TPU_VISIBLE_CHIPS`` alone
fails ("Internal error when accessing libtpu multi-process lockfile");
ports / ``ALLOW_MULTIPLE_LIBTPU_LOAD`` add nothing. Every confined
process sees its chip as device id 0 at coords (0, 0, 0), so a
replica's identity is the chip index it was given — tpuserve's /state
echoes it as ``visible_chips``.
"""

from __future__ import annotations


def chip_env(index: int) -> dict[str, str]:
    """Environment that confines a new process to chip ``index``."""
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
