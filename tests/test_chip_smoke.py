"""chip_smoke.py without a chip: the orchestrator stays off jax, a
failed phase fails the run, and (slow) the whole thing on a named CPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: run the orchestrator with stubbed phases in a FRESH interpreter (this
#: one already imported jax) and report whether jax got imported
STUBBED = """
import sys
import chip_smoke

def serve(args, out_dir):
    if args.model == "boom":
        raise chip_smoke.PhaseError("stubbed failure")
    return {"device": {"platform": "tpu", "kind": "stub", "count": 1}}

chip_smoke.PHASES["build"] = lambda args, out_dir: {}
chip_smoke.PHASES["serve"] = serve
rc = chip_smoke.main(["--phases", "build,serve", "--model", sys.argv[2],
                      "--out", sys.argv[1]])
assert "jax" not in sys.modules, "the orchestrator imported jax"
sys.exit(rc)
"""


def _run_stubbed(tmp_path, model: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", STUBBED, str(tmp_path), model],
        cwd=REPO, capture_output=True, text=True, timeout=60)


def test_orchestrator_never_imports_jax_and_ends_with_one_json(tmp_path):
    proc = _run_stubbed(tmp_path, "qwen2-7b")
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.strip().splitlines()
    # the driver's contract: exactly these keys, nothing beside them
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "stub", "count": 1}}
    summary = json.loads(lines[-2])
    assert summary["served"] == "qwen2-7b" and summary["layers"] == 28
    assert summary["phases"] == {"build": "ok", "serve": "ok"}


def test_a_run_that_saw_no_device_prints_no_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-c", STUBBED.replace(
            '"build,serve"', '"build"'), str(tmp_path), "qwen2-7b"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    assert '"ok": true' not in proc.stdout


def test_a_failed_phase_fails_the_run_and_prints_no_result(tmp_path):
    proc = _run_stubbed(tmp_path, "boom")
    assert proc.returncode == 1
    assert "stubbed failure" in proc.stdout + proc.stderr
    assert '"ok": true' not in proc.stdout
    report = json.load(open(tmp_path / "report.json"))
    assert report["ok"] is False
    assert report["phases"]["serve"]["verdict"] == "FAILED"


@pytest.mark.slow
def test_full_run_on_a_named_cpu(tmp_path):
    """The real children on the CPU, tiny model: every phase the CPU
    can run (Mosaic kernels compile for a TPU only)."""
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--platform", "cpu",
         "--model", "tiny-random", "--no-quantize",
         "--phases", "build,serve,ragged", "--out", str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
