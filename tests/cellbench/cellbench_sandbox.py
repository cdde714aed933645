"""A throw-away checkout for the end-to-end dry runs: the committed
``BENCHMARK.json`` and ``cellbench/`` copied into a temporary directory
(beside links to the program), to which a test ADDS files — a
configuration, a mix, a metric, a reader, a cell — and edits none that
exist. That a cell added this way runs is the proof that the harness
takes a later PR's cells as data."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_KEYS = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
}


#: dataclass field -> published key, as a configuration file lists them
SHARED_FIELDS = {
    "vocab_size": "vocab_size", "dim": "hidden_size",
    "n_layers": "num_hidden_layers", "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads", "ffn_dim": "intermediate_size",
    "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
    "max_seq_len": "max_position_embeddings",
}


def tiny_config(name: str, family: str, replicas: int = 1,
                extra_flags: tuple = ()) -> dict:
    """``tiny-random`` / ``tiny-moe`` shapes as a configuration file
    that serves on the CPU (the platform is named in its own flags)."""
    doc = dict(TINY_KEYS)
    fields, param_bytes = {}, 279168.0
    keys = dict(SHARED_FIELDS, tie_embeddings="tie_word_embeddings")
    if family == "mixtral":
        doc.pop("tie_word_embeddings")
        doc.update(num_local_experts=4, num_experts_per_tok=2)
        fields, param_bytes = {"capacity_factor": 2.0}, 575104.0
        keys = dict(SHARED_FIELDS, n_experts="num_local_experts",
                    experts_per_token="num_experts_per_tok")
    doc["cellbench"] = {
        "name": name, "source": "tests", "family": family,
        "chat_template": "llama3", "reduced": [], "assumed": {},
        "fields": keys, "model_fields": fields,
        "serve_flags": ["--platform", "cpu", "--max-batch-size", "4",
                        "--max-seq-len", "512", "--page-size", "128",
                        "--prefill-bucket-rungs", "1", *extra_flags],
        "module_groups": "xla_default", "replicas": replicas,
        "chips": replicas,
        "expect": {"platform": "cpu", "param_bytes_total": param_bytes},
    }
    return doc


def make_checkout(dst: str) -> str:
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "cellbench"),
                    os.path.join(dst, "cellbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "aigw_tpu"), os.path.join(dst, "aigw_tpu"))
    # its own copy of the scanner's sources: the harness builds them
    # where it runs, and a build must not race the repo's own tests
    shutil.copytree(os.path.join(REPO, "native"), os.path.join(dst, "native"),
                    ignore=shutil.ignore_patterns("*.o", "*.so", "aigw-core"))
    return dst


def add_file(checkout: str, rel: str, content) -> None:
    """Add a NEW file; refuses to touch one that exists."""
    path = os.path.join(checkout, rel)
    assert not os.path.exists(path), f"{rel} exists: a cell may only add"
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(content if isinstance(content, str)
                else json.dumps(content, indent=1))


def add_entries(checkout: str, **entries) -> None:
    """Append entries to the manifest's lists (what a later benchmark
    PR does to ``BENCHMARK.json``)."""
    path = os.path.join(checkout, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    for key, items in entries.items():
        manifest[key].extend(items)
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1)


def run_cell(checkout: str, workload: str, seed: int, seconds: float,
             trace: int, timeout: float = 300.0):
    """(exit code, parsed last line or None, stdout lines, stderr)."""
    env = dict(os.environ)
    # the suite's eight virtual CPU devices are its own affair: a
    # replica of the dry run owns one device, as on the chip
    env.pop("XLA_FLAGS", None)
    # one compile cache for every throw-away checkout, inside the repo
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        REPO, ".jax_cache", "cellbench_tests")
    out = subprocess.run(
        [sys.executable, "cellbench/run.py", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=checkout, env=env, capture_output=True, text=True,
        timeout=timeout)
    lines = out.stdout.strip().splitlines()
    last = None
    if out.returncode == 0 and lines:
        last = json.loads(lines[-1])
    return out.returncode, last, lines, out.stderr
