"""What ``cellbench/run.py`` does when it cannot measure: no TPU, no
program, no such cell — always a non-zero exit and no result line; and
the parent never imports jax."""

import json
import os
import subprocess
import sys

import pytest

import cellbench_sandbox as sb


def run(cwd, *argv, env=None):
    return subprocess.run(
        [sys.executable, "cellbench/run.py", *argv], cwd=cwd,
        env=env or dict(os.environ), capture_output=True, text=True,
        timeout=240)


ARGS = ("--seed", str(2 ** 31 + 1), "--seconds", "2", "--trace", "0")


def test_parent_never_imports_jax():
    code = ("import sys; import cellbench.run, cellbench.client, "
            "cellbench.stack, cellbench.traffic, cellbench.stats, "
            "cellbench.roofline, cellbench.readers.roofline_decode; "
            "assert 'jax' not in sys.modules, 'jax imported'; "
            "assert 'numpy' not in sys.modules, 'numpy imported'")
    out = subprocess.run([sys.executable, "-c", code], cwd=sb.REPO,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr


def test_without_a_tpu_no_result():
    """The committed cell, here, where JAX finds only the CPU: the
    replica refuses to boot (nobody named a platform to it) and the
    harness exits non-zero having printed nothing on stdout."""
    out = run(sb.REPO, "--workload", "qwen2-7b.chat-steady", *ARGS)
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_alone_in_a_directory_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program to
    serve with."""
    import shutil

    shutil.copy(os.path.join(sb.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(sb.REPO, "cellbench"),
                    tmp_path / "cellbench")
    out = run(str(tmp_path), "--workload", "qwen2-7b.decode-closed", *ARGS)
    assert out.returncode not in (0, None)
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("argv", [
    ("--workload", "no-such.cell", *ARGS),
    ("--workload", "qwen2-7b.chat-steady", "--seed", "1", "--seconds", "2",
     "--trace", "2"),
    ("--workload", "qwen2-7b.chat-steady", "--seconds", "2", "--trace", "0"),
])
def test_bad_arguments_no_result(argv):
    out = run(sb.REPO, *argv)
    assert out.returncode not in (0, None) and out.stdout.strip() == ""


def test_takes_no_notice_of_bench_run():
    with open(os.path.join(sb.REPO, "cellbench", "run.py")) as f:
        src = f.read()
    for path, _, files in os.walk(os.path.join(sb.REPO, "cellbench")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(path, name)) as f:
                    assert "BENCH_RUN" not in f.read(), name
    assert "add_argument" in src and src.count("add_argument(") == 4


def test_manifest_command_is_this_script():
    with open(os.path.join(sb.REPO, "BENCHMARK.json")) as f:
        assert json.load(f)["command"] == ["python3", "cellbench/run.py"]
