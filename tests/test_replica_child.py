"""The product's own replica child (``python -m aigw_tpu.tpuserve.child``):
what ``LocalProcessLauncher`` and ``tools/chaos.py`` start.

A spec becomes a ``ModelSpec`` and an ``EngineConfig`` for every
registered family through the registry's one public lookup; a key the
child does not know is refused by name; a real CPU replica reports its
port, serves, and drains to exit 0; and the launcher's default child
needs nothing beside the package (it starts from a working directory
outside the checkout).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import subprocess
import sys
import urllib.request

import pytest

from aigw_tpu.gateway.controller import LocalProcessLauncher
from aigw_tpu.models.registry import (
    family_config_class,
    get_model_spec,
)
from aigw_tpu.tpuserve import child
from aigw_tpu.tpuserve.engine import EngineConfig

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
sys.path.insert(0, os.path.join(_REPO, "tools"))

import chaos  # noqa: E402  (tools/chaos.py)

_TINY = {
    "vocab_size": 512, "dim": 64, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "ffn_dim": 128, "max_seq_len": 256,
    "rope_theta": 10000.0,
}


def _spec(model: str, **over) -> dict:
    spec = {"model": model, "cfg": dict(_TINY), "batch": 2, "page": 16,
            "k": 2, "engine": {"min_prefill_bucket": 16, "num_pages": 48}}
    spec.update(over)
    return spec


def _get(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


@pytest.mark.parametrize(
    "registered", ["tiny-random", "tiny-moe", "tiny-qwen3-next"])
def test_spec_builds_model_and_engine_config(registered):
    """One document shape for every family: the spec names the family,
    the registry names its configuration dataclass."""
    have = get_model_spec(registered)
    cfg = json.loads(json.dumps(dataclasses.asdict(have.config)))
    model_spec, engine_cfg = child.build({
        "model": "doc-" + registered, "family": have.family, "cfg": cfg,
        "batch": 3, "page": 32, "k": 4,
        "engine": {"num_pages": 64, "max_batch_size": 5}})
    assert type(model_spec.config) is family_config_class(have.family)
    assert model_spec.config == have.config
    assert (model_spec.name, model_spec.family) == (
        "doc-" + registered, have.family)
    assert isinstance(engine_cfg, EngineConfig)
    assert engine_cfg.max_seq_len == have.config.max_seq_len
    assert (engine_cfg.page_size, engine_cfg.decode_steps_per_tick,
            engine_cfg.num_pages) == (32, 4, 64)
    # ``engine`` holds the last word on a field the short keys also set
    assert engine_cfg.max_batch_size == 5


def test_unknown_family_names_the_registered_ones():
    with pytest.raises(KeyError, match="no-such.*llama"):
        family_config_class("no-such")


@pytest.mark.parametrize("spec, named", [
    (_spec("x", sampler="greedy"), "sampler"),
    (_spec("x", engine={"prefill_rungs": 2}), "prefill_rungs"),
])
def test_unknown_key_exits_nonzero_naming_it(spec, named):
    """A spec comes from outside the program (a config file's
    ``launcher.spec``): a key the child does not know is an error, not
    a default."""
    p = subprocess.run(
        [sys.executable, "-m", "aigw_tpu.tpuserve.child",
         json.dumps(spec)],
        cwd=_REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert repr(named) in p.stderr, p.stderr[-400:]
    assert "SERVE_PORT=" not in p.stdout


def test_slow_start_stall_comes_before_the_spec_is_read(monkeypatch):
    """``AIGW_CHAOS_SLOW_START_S`` (tools/chaos.py): the child stalls
    that long before it does anything a launcher could observe."""
    slept: list[float] = []
    monkeypatch.setattr("aigw_tpu.utils.boot.boot_jax", lambda: "cpu")
    monkeypatch.setattr(child.time, "sleep", slept.append)
    monkeypatch.setenv("AIGW_CHAOS_SLOW_START_S", "2.5")
    assert child.main(["{not json"]) != 0
    assert slept == [2.5]


def test_cpu_replica_serves_and_drains_to_exit_zero():
    """Through ``tools/chaos.py``, which starts the same module."""
    rep = chaos.spawn_replica(_spec("tiny-child-a"), boot_timeout_s=600)
    try:
        assert _get(rep.url + "/health") == {
            "status": "ok", "model": "tiny-child-a"}
        assert _get(rep.url + "/state")["platform"] == "cpu"
        assert rep.term(timeout=90) == 0
    finally:
        if rep.alive():
            rep.kill9()


def test_default_launcher_needs_nothing_beside_the_package(
        tmp_path, monkeypatch):
    """``LocalProcessLauncher`` with no ``child``: the replica starts
    from a working directory outside the checkout, given only what an
    installed package gives (the package on the import path)."""
    monkeypatch.chdir(tmp_path)
    launcher = LocalProcessLauncher(
        _spec("tiny-child-b"), boot_timeout_s=600, term_grace_s=90,
        env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": _REPO})
    assert launcher.child_argv == [
        sys.executable, "-m", "aigw_tpu.tpuserve.child"]

    async def main() -> int | None:
        try:
            addr = await launcher.launch()
            health = await asyncio.to_thread(
                _get, f"http://{addr}/health")
            assert health["model"] == "tiny-child-b"
            await launcher.terminate(addr)
            return launcher.returncode(addr)
        finally:
            await launcher.close()

    assert asyncio.run(main()) == 0
