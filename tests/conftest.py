"""Test bootstrap: force JAX onto a virtual 8-device CPU platform.

Multi-chip hardware is not available in CI; shardings are validated on a
virtual CPU mesh (``--xla_force_host_platform_device_count=8``), the same
way the driver's ``dryrun_multichip`` does.

The CPU is NAMED here, twice: ``jax.config.update`` for this process
and ``JAX_PLATFORMS`` for every child a test starts — nothing in the
serving path falls back to a CPU nobody asked for (aigw_tpu/utils/boot.py
refuses to boot without a TPU unless a platform is named).
"""

import os

# Engine-thread sanitizer (ISSUE 15, aigw_tpu/analysis/registry.py):
# every @engine_thread_only method asserts it runs on the owning engine
# thread whenever that thread is live. On for the WHOLE suite — the f32
# rigs prove the checks don't perturb byte-identity or the zero-hot-
# compile tripwires, and the chaos/churn tests get thread-discipline
# violations as loud failures instead of corrupted streams. Must be set
# before aigw_tpu imports (the flag is read once at import).
os.environ.setdefault("AIGW_TSAN", "1")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
