"""A chunk's, a tail's and a verify program's window read
(``kvq.window_kv`` over ``ops/paged_walk.window_pages``, ISSUE 46):
whole pages out of the pool as one list of pages give, bit for bit,
what indexing a layer's rows by token gave; the three families' chunk
programs compute what they computed over it; and in their lowered text
every gather under ``layer/kv_gather`` takes a slice of ``page_size``
tokens, so the token form cannot come back unseen.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aigw_tpu.models import kvq, llama, mixtral, qwen3_next
from aigw_tpu.ops import paged_walk

PAGE = 8


def _token_rows(kv, layer, page_table, page_size):
    """The read as it stood before ISSUE 46 (``kvq.gather_kv`` over the
    callers' ``gslot``): a layer's K and V rows indexed by token."""
    B, P = page_table.shape
    gslot = (page_table[:, :, None] * page_size + jnp.arange(
        page_size, dtype=jnp.int32)).reshape(B, P * page_size)
    if not kvq.is_quantized(kv):
        return kv[layer, 0][gslot], kv[layer, 1][gslot]
    k = kvq.dequantize_rows(kv["q"][layer, 0][gslot],
                            kv["scale"][layer, 0][gslot])
    v = kvq.dequantize_rows(kv["q"][layer, 1][gslot],
                            kv["scale"][layer, 1][gslot])
    return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)


def _pool(rng, n_pages, dtype, layers=3, hkv=2, d=16):
    rows = jnp.asarray(rng.normal(size=(
        layers, 2, n_pages * PAGE, hkv, d)).astype(np.float32))
    if dtype in kvq.QUANT_DTYPES:
        q, scale = kvq.quantize_rows(rows, dtype)
        return {"q": q, "scale": scale}
    return rows.astype(dtype)


def _table(rng, B, P, n_pages):
    """Page ids as an engine's tables hold them: scattered through the
    pool, one page shared by two rows and held twice by one (a prefix
    hit, a copy-on-write not yet taken), and the DUMP page (the pool's
    last: where padding rows' entries point) behind the live ones."""
    pt = rng.permutation(n_pages - 1)[:B * P].reshape(B, P)
    pt[0, 1] = pt[0, 0]
    pt[-1, 0] = pt[0, 0]
    pt[:, P - 1] = n_pages - 1
    return jnp.asarray(pt, jnp.int32)


@pytest.mark.parametrize("P", [2, 8])
@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("dtype", ["bfloat16", "int8", "int4"])
def test_page_read_is_the_token_read_bit_for_bit(dtype, B, P):
    rng = np.random.default_rng(46 + B * 10 + P)
    n_pages = B * P + 2
    kv = _pool(rng, n_pages, dtype)
    pt = _table(rng, B, P, n_pages)
    for layer in (0, 2):
        want = _token_rows(kv, layer, pt, PAGE)
        got = jax.jit(kvq.window_kv, static_argnums=(1, 3))(
            kv, layer, pt, PAGE)
        for w, g in zip(want, got):
            assert g.shape == (B, P * PAGE, 2, 16) and g.dtype == w.dtype
            assert np.array_equal(np.asarray(g, np.float32),
                                  np.asarray(w, np.float32))


def test_page_list_is_the_pool_in_place_and_the_walks_view():
    """One view for the chunk's read and the decode walk's: entry
    ``(layer*2 + which)*n_pages + i`` is page ``i`` of K or V of the
    layer, for the data and for a quantised pool's scales."""
    kv = _pool(np.random.default_rng(0), 5, "int8")
    for leaf in (kv["q"], kv["scale"]):
        pages, n_pages = paged_walk.page_list(leaf, PAGE)
        assert n_pages == 5 and pages.shape == (3 * 2 * 5, PAGE,
                                                *leaf.shape[3:])
        assert np.array_equal(
            np.asarray(pages[(2 * 2 + 1) * 5 + 3]),
            np.asarray(leaf[2, 1, 3 * PAGE:4 * PAGE]))


FAMILIES = {
    "tiny-llama": (llama, llama.LlamaConfig(
        vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=256, attn_bias=True)),
    "tiny-mixtral": (mixtral, mixtral.MixtralConfig(
        vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=256, n_experts=4, experts_per_token=2)),
    "tiny-qwen3-next": (qwen3_next, qwen3_next.TINY),
}


def _chunk_inputs(mod, cfg, B, S, P, page):
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    n_rows = (B * P + 1) * page
    if mod is qwen3_next:
        cache = cfg.cache_spec().make(n_rows, B, "bfloat16")
    else:
        cache = jnp.zeros((cfg.n_layers, 2, n_rows, cfg.n_kv_heads,
                           cfg.dim // cfg.n_heads), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(B * P).reshape(B, P), jnp.int32)
    tokens = jnp.asarray(rng.integers(1, 500, size=(B, 2 * S)), jnp.int32)
    return params, cache, pt, tokens


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_chunk_program_gives_the_logits_the_token_read_gave(
        family, monkeypatch):
    """Two chunks of a prompt through ``prefill_suffix`` (the second
    attends over the first's pages): the logits and the pool with the
    page read are those with the token read, to the bit."""
    mod, cfg = FAMILIES[family]
    B, S, P, page = 2, 16, 4, 16
    params, cache, pt, tokens = _chunk_inputs(mod, cfg, B, S, P, page)

    def two_chunks():
        # a fresh trace each call: the read is looked up at trace time
        step = jax.jit(lambda c, t, pre, n: mod.prefill_suffix(
            params, cfg, t, pre, n, c, pt, page))
        zero = jnp.zeros((B,), jnp.int32)
        lens = jnp.asarray([S, S - 3], jnp.int32)  # a ragged first chunk
        _, c = step(cache, tokens[:, :S], zero, lens)
        return step(c, tokens[:, S:], lens, lens + jnp.asarray([S, 5]))

    got_logits, got_cache = two_chunks()
    monkeypatch.setattr(kvq, "window_kv", _token_rows)
    want_logits, want_cache = two_chunks()
    assert np.array_equal(np.asarray(got_logits, np.float32),
                          np.asarray(want_logits, np.float32))
    for g, w in zip(jax.tree_util.tree_leaves(got_cache),
                    jax.tree_util.tree_leaves(want_cache)):
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(w, np.float32))


# -- the lowered text -------------------------------------------------------
_GATHER = re.compile(
    r'"stablehlo\.gather".*slice_sizes = array<i64: ([\d, ]+)>'
    r'.*\((tensor<[^>]+>), tensor<[^>]+>\) -> .* loc\((#loc\d+)\)')
_CALL = re.compile(r"= call @(\w+)\(.* loc\((#loc\d+)\)")
_FUNC = re.compile(r"func\.func (?:public|private) @(\w+)\(")
_LOC = re.compile(r'^(#loc\d+) = loc\("([^"]*)"')


def window_gathers(text: str) -> list[tuple[tuple[int, ...], str]]:
    """(slice sizes, operand type) of every gather of a lowered program
    (``as_text(debug_info=True)``) that runs under ``layer/kv_gather``:
    in the scope's own name stack, or inside a function called there
    (``jnp.take`` is a call to a private ``_take``)."""
    names = dict(m.groups() for m in map(_LOC.match, text.split("\n")) if m)
    funcs: dict[str, dict] = {}
    cur = None
    for line in text.split("\n"):
        m = _FUNC.search(line)
        if m:
            cur = funcs.setdefault(m.group(1), {"gathers": [], "calls": []})
            continue
        if cur is None:
            continue
        g, c = _GATHER.search(line), _CALL.search(line)
        if g:
            sizes = tuple(int(x) for x in g.group(1).split(","))
            cur["gathers"].append((sizes, g.group(2), g.group(3)))
        elif c:
            cur["calls"].append(c.groups())
    out: list = []

    def walk(fn: str, inside: bool) -> None:
        for sizes, operand, loc in funcs[fn]["gathers"]:
            if inside or "layer/kv_gather" in names.get(loc, ""):
                out.append((sizes, operand))
        for callee, loc in funcs[fn]["calls"]:
            walk(callee, inside or "layer/kv_gather" in names.get(loc, ""))

    walk("main", False)
    return out


def _lowered_chunk(family):
    """(lowered text of the family's ``prefill_suffix``, its page pool's
    shape): two rows of 16 tokens over four pages of 16."""
    mod, cfg = FAMILIES[family]
    B, S, P, page = 2, 16, 4, 16
    params, cache, pt, _ = jax.eval_shape(
        lambda: _chunk_inputs(mod, cfg, B, S, P, page))
    vec = jax.ShapeDtypeStruct((B,), jnp.int32)
    text = jax.jit(lambda p, c, t, pre, n, pt: mod.prefill_suffix(
        p, cfg, t, pre, n, c, pt, page)).lower(
            params, cache, jax.ShapeDtypeStruct((B, S), jnp.int32), vec,
            vec, pt).as_text(debug_info=True)
    return text, getattr(cache, "kv", cache).shape, page


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_window_gather_takes_whole_pages(family):
    text, (kv_layers, _, n_rows, hkv, hd), page = _lowered_chunk(family)
    got = window_gathers(text)
    assert len(got) == 2 * kv_layers  # K and V of every layer with pages
    for sizes, operand in got:
        # one entry of the one list of pages: a page of page_size tokens
        assert sizes == (1, page, hkv, hd)
        assert operand.startswith(
            f"tensor<{kv_layers * 2 * n_rows // page}x{page}x{hkv}x{hd}x")


def test_window_gathers_sees_a_token_gather(monkeypatch):
    """The check's own check: with the token form back in place of the
    page read, it finds gathers of ONE token under the scope."""
    monkeypatch.setattr(kvq, "window_kv", _token_rows)
    text, _, page = _lowered_chunk("tiny-llama")
    got = window_gathers(text)
    assert got and all(sizes[0] == 1 and sizes[1] != page
                       for sizes, _ in got)
