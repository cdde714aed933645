"""Names inside the device programs: every decode and prefill program
of the five model families carries each ``jax.named_scope`` of its blocks,
and the scopes are operation metadata only — the hash JAX's persistent
compile cache takes of a program is the same with and without them, so
no serving program recompiles for having been named.

The programs are lowered by ``tests/scope_keys_child.py`` in two child
processes (the scopes are decorators, applied at import; the second
child turns ``jax.named_scope`` into a no-op before it imports the
program). On the CPU, no skip condition."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

DENSE = {"embed", "layer/attn", "layer/mlp", "lm_head", "sample"}
MOE = {"embed", "layer/attn", "layer/moe_route", "layer/moe_experts",
       "lm_head", "sample"}
#: the hybrid family: its DeltaNet layers' projections, convolution and
#: rule (chunked in a prefill, the recurrence in a decode step), its
#: gated attention (``layer/attn`` is the softmax core it shares with
#: the other families, nested inside; a trace attributes it to the outer
#: scope), and the expert layer's three parts
HYBRID = {"embed", "layer/gdn_proj", "layer/gdn_conv", "layer/attn_gated",
          "layer/attn", "layer/moe_route", "layer/moe_experts",
          "layer/moe_shared", "lm_head", "sample"}
#: the latent-attention family: the absorbed query and the page row's
#: projections, attention over cached rows (a chunk's blocks; a decode
#: step's walk), the value half and the output projection; the leading
#: dense layer's ``layer/mlp`` and the expert layer's three parts
LATENT = {"embed", "layer/mla_q", "layer/mla_kv", "layer/mla_out",
          "layer/mlp", "layer/moe_route", "layer/moe_experts",
          "layer/moe_shared", "lm_head", "sample"}
#: the window-and-global family: the fused projection and the rotary
#: of both kinds of layer, a window layer's attention (a chunk's band
#: over ring and chunk; a step's loop over the live rows' rings) and
#: its ring write, a global layer's page read (a chunk's blocks, a
#: step's walk), the output projection; the leading dense layer's
#: ``layer/mlp`` and the expert layer's two parts (no shared expert)
WINDOW = {"embed", "layer/qkv", "layer/rope", "layer/attn_window",
          "layer/ring_write", "layer/attn_out", "layer/mlp",
          "layer/moe_route", "layer/moe_experts", "lm_head", "sample"}
#: program -> the scopes its lowered text must carry
WANT = {
    "mimo_v2.decode": WINDOW | {"layer/kv_walk"},
    # (a whole prompt has no page window behind it to read)
    "mimo_v2.prefill": WINDOW | {"layer/attn_global"},
    "mimo_v2.prefill_suffix": WINDOW | {"layer/kv_gather",
                                        "layer/attn_global"},
    "axk1.decode": LATENT | {"layer/kv_walk"},
    "axk1.prefill": LATENT | {"layer/mla_attn"},
    "axk1.prefill_suffix": LATENT | {"layer/mla_attn"},
    "llama.decode": DENSE | {"layer/kv_walk"},
    "llama.prefill": DENSE,
    "llama.prefill_suffix": DENSE | {"layer/kv_gather"},
    "mixtral.decode": MOE | {"layer/kv_walk"},
    "mixtral.prefill": MOE,
    "mixtral.prefill_suffix": MOE | {"layer/kv_gather"},
    "qwen3_next.decode": (HYBRID - {"layer/attn"}) | {
        "layer/gdn_recurrent", "layer/kv_walk"},
    "qwen3_next.prefill": HYBRID | {"layer/gdn_chunk"},
    "qwen3_next.prefill_suffix": HYBRID | {"layer/gdn_chunk",
                                           "layer/kv_gather"},
}


def _child(*flags: str) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device is enough
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "scope_keys_child.py"), *flags],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)


@pytest.fixture(scope="module")
def lowered():
    procs = {"scoped": _child(), "bare": _child("--no-scopes")}
    out = {}
    for name, proc in procs.items():
        stdout, _ = proc.communicate(timeout=600)
        assert proc.returncode == 0, name
        out[name] = json.loads(stdout.strip().splitlines()[-1])
    return out


@pytest.mark.parametrize("program,scope", [
    (p, s) for p in sorted(WANT) for s in sorted(WANT[p])])
def test_program_carries_scope(lowered, program, scope):
    assert scope in lowered["scoped"][program]["scopes"]


@pytest.mark.parametrize("program", sorted(WANT))
def test_program_carries_no_other_scope(lowered, program):
    assert set(lowered["scoped"][program]["scopes"]) == WANT[program]
    assert lowered["bare"][program]["scopes"] == []  # the child's switch


@pytest.mark.parametrize("program", sorted(WANT))
def test_guarded_sort_keeps_the_sample_scope(lowered, program):
    """The sampler's sort sits inside a conditional (ISSUE 26); what is
    in its branches is still named ``sample``, so ``tools/trace_gaps.py``
    keeps attributing it."""
    got = lowered["scoped"][program]
    assert got["sorts"] == 1
    assert any("jit(sort)" in st for st in got["in_cond"])
    assert [st for st in got["in_cond"]
            if not re.search(r"(?:^|/)sample/cond/branch_", st)] == []
    assert lowered["bare"][program]["sorts"] == 1


@pytest.mark.parametrize("program", sorted(WANT))
def test_expert_layer_loops_in_a_decode_step_alone(lowered, program):
    """A single-chip decode step of an MoE family runs one trip of a
    loop for each expert its live rows hit (ISSUE 41: mixtral's, as the
    hybrid family's), and that loop is the step's ONE expert path: no
    conditional stands in the expert layer with a dense pass in its
    other branch. A sequence program hits every expert and has no such
    loop."""
    got = lowered["scoped"][program]
    assert ("layer/moe_experts" in got["loops"]) == (
        program in ("mixtral.decode", "qwen3_next.decode", "axk1.decode",
                    "mimo_v2.decode"))
    assert [st for st in got["in_cond"] if "layer/moe" in st] == []


@pytest.mark.parametrize("program", sorted(WANT))
def test_compile_cache_key_ignores_the_scopes(lowered, program):
    assert lowered["scoped"][program]["key"] == \
        lowered["bare"][program]["key"]


def test_scopes_live_only_in_the_programs_and_the_ledger():
    """``grep -rn "TraceAnnotation\\|named_scope" aigw_tpu`` finds them
    only where they were put: the model blocks, the sampler, and the
    one factory of the loop ledger."""
    found = {}
    root = os.path.join(os.path.dirname(HERE), "aigw_tpu")
    for path, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(path, name)) as f:
                    text = f.read()
                rel = os.path.relpath(os.path.join(path, name), root)
                for word in ("named_scope(", "TraceAnnotation("):
                    if word in text:
                        found.setdefault(word, set()).add(rel)
    assert found == {
        "named_scope(": {"models/axk1.py", "models/llama.py",
                         "models/mimo_v2.py", "models/mixtral.py",
                         "models/olmo_hybrid.py", "models/qwen3_next.py",
                         "ops/paged_walk.py",
                         "tpuserve/sampling.py"},
        "TraceAnnotation(": {"obs/flight.py"},
    }
