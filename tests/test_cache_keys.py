"""The programs the engine dispatches for the llama and mixtral
families hash, for JAX's persistent compile cache, to what they hashed
before the per-slot state pool, the cache description and the
``slot_ids`` argument existed: a replica of those families finds its
compiled programs in the cache it filled before this change.

The hybrid family's ``prefill`` and ``prefill_suffix`` programs are
held beside them since its decode step began to loop over the experts
its live rows hit: a chunk bypasses that loop (it runs the dense pass
it ran), so its programs keep their keys; the family's decode programs
changed with it and have no golden.

The goldens were taken from the parent commit (6150a62; the hybrid
family's from 861624e) by
``python tests/engine_keys_child.py <checkout of the parent>`` under the
JAX named below. Another JAX lowers to other text and the comparison
then says nothing, so it is skipped BY NAME of that condition; the
second test still holds the two lean/full decode programs apart."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

GOLDEN_JAX = "0.9.0"
GOLDEN = {
    "tiny-random.prefill":
        "63446f95883beb2185b1d06c53d2aa2c07228f1b47a82e11905a671823a9c562",
    "tiny-random.prefill_suffix":
        "19a6afad6854c5c8fe11cf03417798b96cb3ae53895151e9896692bb9ae268fd",
    "tiny-random.decode.lean=True":
        "0888f9a44abe9cabd3b9bf4c036c2535ee6e091993040b3d8a56aded22cdb55b",
    "tiny-random.decode.lean=False":
        "e4c7c8ff5665c5f260e9e001b2d34b827330e8f5503382208aa3be3fd42ef0c9",
    "tiny-moe.prefill":
        "13e1709b8ca64f0371b94219a8bc4ce92f1172315ce362a18416bb65c4537263",
    "tiny-moe.prefill_suffix":
        "284de1ca630b9e535842d620d29b02cb1cb4df4930bd3c954c77a28386bc5754",
    "tiny-moe.decode.lean=True":
        "3800c551d3a6d2c48cbc44e7e3020b541a022ed19eccbce9edbbdc10bb395cc0",
    "tiny-moe.decode.lean=False":
        "a5adef1a048e166953f76a693652d3af2e66ae12a75220d715cbcb09c88cd0d3",
    "tiny-qwen3-next.prefill":
        "27722e95630f8db99a3ed566f6e2f930108d9fb1bbeda62b2c787d70c34d52ae",
    "tiny-qwen3-next.prefill_suffix":
        "7af8902797042320b451ce03ecfd320c9a7c81aa2c6a81ea56965151b399f382",
}


@pytest.fixture(scope="module")
def keys():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "engine_keys_child.py")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, timeout=600)
    assert out.returncode == 0
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", sorted(GOLDEN))
def test_engine_program_keeps_its_compile_cache_key(keys, program):
    if keys["jax"] != GOLDEN_JAX:
        pytest.skip(f"goldens are of jax {GOLDEN_JAX}, this is "
                    f"{keys['jax']}: retake them from the parent commit")
    assert keys[program] == GOLDEN[program]


def test_every_program_is_a_program_of_its_own(keys):
    got = [v for k, v in keys.items() if k != "jax"]
    assert len(got) == len(GOLDEN) and len(set(got)) == len(got)
