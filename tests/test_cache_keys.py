"""The programs the engine dispatches hash, for JAX's persistent compile
cache, to what they hashed before: a replica finds its compiled
programs in the cache it filled before a change that was not meant to
touch them.

The whole-prompt programs (``prefill``) of the llama and mixtral
families keep the keys they had at 6150a62 — through the per-slot
state pool, the cache description, the ``slot_ids`` argument, the
hybrid family's expert loop (a chunk bypasses it), ISSUE 31's page walk
(a decode step's) and ISSUE 46's page read (a chunk's: a whole prompt
has no window behind it). That they stand here byte for byte is the
proof that those programs did not change.

The decode goldens of the three families were re-taken from the tree
of ISSUE 31, whose decode step walks the page pool: every decode
program's key moved once with it (one cold compile a deployment), and
the hybrid family's decode programs, which had no golden, have one now.
ISSUE 35 moved those two alone (the DeltaNet state update over the
live rows, two more columns on the decode tape): the llama and mixtral
decode programs and the hybrid family's prefill, chunk and tail
programs stand as they stood at ec98829. ISSUE 41 moved mixtral's two
decode programs alone (the loop over the experts live rows hit, two
more columns on the decode tape); its prefill and chunk programs keep
their keys through the move of ``moe_mlp``'s router and fence into
helpers the decode step shares, which is the proof that the move
changed nothing a sequence computes. ISSUE 43 moved the hybrid family's
``prefill`` and ``prefill_suffix`` alone (``_gdn_chunk`` inverts its
triangular matrix by block merges instead of a solve): its two decode
programs, which run no chunk, and every llama and mixtral program
stand as they stood at 5971f59. ISSUE 46 moved the three families'
``prefill_suffix`` alone (the chunk, tail and prefix-hit program reads
its page window a whole page at a time, ``kvq.window_kv``, where it
indexed a layer's rows by token; one cold compile a deployment): every
``prefill`` and every decode program stands as it stood at bc6b3be —
the decode walk now takes its one-list-of-pages view from the helper
the chunk's read shares (``paged_walk.page_list``), and lowers to the
same text. ISSUE 49 moved the decode programs of the three families
that walk a K/V pool (llama, mixtral, the hybrid family: one flat list
of live (row, page) pairs in place of row blocks; one cold compile a
deployment) and nothing else: every ``prefill`` and ``prefill_suffix``
stands as it stood at b404cf1, and so do the decode programs of the
latent and the window-and-global family (``tiny-axk1``,
``tiny-mimo-v2``), whose walk over a latent pool keeps its block plan —
their goldens were taken from b404cf1 BEFORE the change, which is the
proof that those two did not move. ISSUE 50 moved ``tiny-axk1``'s two
decode programs alone (the child lays the latent family's weights out
through ``ModelFns.serving_params``, as the server does at load, and
the programs read the serving leaves; one cold compile a deployment of
that family): every other program here stands as it stood at be12620,
the proof that no other family's parameter tree or program changed.

Taken by ``python tests/engine_keys_child.py [<checkout>]`` under the
JAX named below. Another JAX lowers to other text and the comparison
then says nothing, so it is skipped BY NAME of that condition; the
second test still holds the lean/full decode programs apart."""

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
        "0d7a67337dba23ed3bb53768e6872510f68f013fa3061cc7adfd912572836926",
    "tiny-random.decode.lean=True":
        "425f29843f1e463db589236172431ea8342b2034501fcb42964efa47050dd32c",
    "tiny-random.decode.lean=False":
        "02e3e90a9aec5321fad41285dc934c7cb6b5e6422776353abe41719f0f39fa9b",
    "tiny-moe.prefill":
        "13e1709b8ca64f0371b94219a8bc4ce92f1172315ce362a18416bb65c4537263",
    "tiny-moe.prefill_suffix":
        "c7dd23cbbf6fa891a2e095b62408a1ba0717a9db5e724ab2f2cb135f8ff8af64",
    "tiny-moe.decode.lean=True":
        "5617157d44cd5e23bb5ec8e37c3cc4fc9bc0c321d3e8efdef8162480392108d3",
    "tiny-moe.decode.lean=False":
        "c3b96f3959dbd1566ae5399d0efa17036fa4db77da77d9cfdb27187e36764231",
    "tiny-qwen3-next.prefill":
        "333eea910eeff3d9582dfd042ef8086b35d25fa058befefcd7a50d313274ea2f",
    "tiny-qwen3-next.prefill_suffix":
        "aa051e79688b5172ddf12d694c5d4c46fafcac38cf605c5e1fcfd8b5dfc0a7fe",
    "tiny-qwen3-next.decode.lean=True":
        "fd6d25c2a5b5c14588ca6ae4f6dbcb16d7a3ae47a779d0859d960b76e2f993b4",
    "tiny-qwen3-next.decode.lean=False":
        "141b31fa01a17e49ac059fc1ff28a7302d341375c23f7274fa71461bcc8c157b",
    "tiny-axk1.decode.lean=True":
        "91d2363d72eecab35bbf4eaee266e1a10daefd700b62cdea16cd1788724e5f9c",
    "tiny-axk1.decode.lean=False":
        "f86fbb4be43d899964e4cdec210e963a28ec36b07ca8421a9ece32155843f622",
    "tiny-mimo-v2.decode.lean=True":
        "69f19761e4a1b30b797db4a30db49b6e8c55d3a6eb896d8be39f5623cf608618",
    "tiny-mimo-v2.decode.lean=False":
        "802dde71965e3f26f1aece10496b7623005aed0601cc697ecf1cbf06c80a7b8a",
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
