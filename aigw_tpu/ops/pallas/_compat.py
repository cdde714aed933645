"""Backend detection for Pallas kernels."""

from __future__ import annotations

import jax


def is_tpu_backend() -> bool:
    """Whether kernels compile for a TPU (else they run interpreted, on
    the CPU platform a test or ``--platform cpu`` named — utils/boot.py
    refuses to boot on a platform nobody named). A backend that fails
    to initialise raises here: answering "not a TPU" would flip every
    ``interpret=not is_tpu_backend()`` to interpret mode on real
    hardware."""
    return jax.default_backend() == "tpu"
