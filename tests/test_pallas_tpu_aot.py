"""Every Pallas kernel must COMPILE for TPU v5 lite — without a chip.

libtpu can describe a TPU topology on a chipless box, so each
``pl.pallas_call`` in ``aigw_tpu/ops/pallas/`` is lowered and compiled
ahead of time by the real Mosaic compiler (``interpret=False``) at the
two served attention geometries. Interpret-mode parity tests cannot see
a Mosaic refusal (block shapes, in-kernel reshapes, unaligned slices):
a kernel once passed them for eight PRs and had never lowered.
Agreement with the XLA twins on real hardware is ``chip_smoke.py``'s
kernels phase; this file only keeps the lowering from regressing.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from aigw_tpu.ops.pallas import qmatmul
from aigw_tpu.ops.pallas.paged_attention import ragged_prefill_attention

#: (n_heads, n_kv_heads, dim, ffn_dim, vocab_size)
GEOMETRIES = {
    "qwen2-7b": (28, 4, 3584, 18944, 152064),
    "llama-3-8b": (32, 8, 4096, 14336, 128256),
}
D, PAGE, B, P = 128, 128, 8, 16
N_SLOTS = (B * P + 1) * PAGE  # the engine's pool: pages + its extra page


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one device of a described (not attached) v5e:2x2."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu on this box
        pytest.skip(f"libtpu cannot describe a v5e topology: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes, **kw_shapes):
    def sds(spec):
        shape, dtype = spec
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    jax.jit(fn).lower(
        *(sds(s) for s in shapes),
        **{k: sds(s) for k, s in kw_shapes.items()}).compile()


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_attention_kernels_compile(v5e, geometry):
    H, Hkv = GEOMETRIES[geometry][:2]
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = ((N_SLOTS, Hkv, D), bf16)
    pt = ((B, P), i32)
    _compile(
        functools.partial(ragged_prefill_attention, page_size=PAGE),
        v5e, ((256, H, D), bf16), pool, pool, pt, ((B + 1,), i32),
        ((B,), i32))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_w8a16_matmul_compiles_at_every_weight_shape(v5e, geometry):
    H, Hkv, dim, ffn, vocab = GEOMETRIES[geometry]
    shapes = {(dim, H * D), (dim, Hkv * D), (H * D, dim),
              (dim, ffn), (ffn, dim), (dim, vocab)}
    for k, n in sorted(shapes):
        assert qmatmul.supported(B, k, n), (k, n)
        _compile(qmatmul._w8a16_matmul, v5e, ((B, k), jnp.bfloat16),
                 ((k, n), jnp.int8), ((1, n), jnp.float32))


HYBRID_SLOTS = 32


def _hybrid_cell(v5e):
    """``qwen3-next-80b-a3b-1chip`` as shapes on the described chip: the
    model module, its configuration (published widths, 12 layers, 128
    of 512 experts held), the parameters and the cache of 32 slots."""
    from aigw_tpu.models import qwen3_next as qn

    cfg = qn.Qwen3NextConfig(num_hidden_layers=12, num_experts=128,
                             router_experts=512, vocab_size=37984)

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    p = sds(jax.eval_shape(
        lambda: qn.init_params(jax.random.PRNGKey(0), cfg)))
    cache = sds(jax.eval_shape(lambda: cfg.cache_spec().make(
        (HYBRID_SLOTS * 32 + 1) * PAGE, HYBRID_SLOTS, "bfloat16")))
    return qn, cfg, p, cache


@pytest.mark.parametrize("pages", [16, 32])
def test_hybrid_decode_window_copies_no_pool_and_no_expert_matrix(v5e, pages):
    """No Pallas here, but the same kind of fact only the chip's
    compiler knows. The Qwen3-Next decode window (a scan of
    ``decode_step``) at the published widths, 12 layers, 128 experts
    held, 32 slots: the loop over the experts the live rows hit slices
    each expert's blocks out of the flat matrices where they lie, and
    the DeltaNet state pool is updated in place from layer to layer.
    Both have failed silently: with the expert loops between the
    layers the compiler could no longer order the pool's readers and
    copied all 604 MB of it twice a step (3.4 ms of a 23 ms step on the
    chip) until ``decode_step`` handed the pool to each layer with its
    input. A copy of a pool or of an expert matrix is an instruction
    of that shape in the compiled program. Since ISSUE 31 neither is
    a gathered ``[32, P*128, 2, 256]`` window: the cell's two page
    buckets (16 and 32) are the two programs compiled here. Since
    ISSUE 35 no array of a whole layer's state is in it either: the
    DeltaNet update reads the live rows' ``[1, 32, 128, 128]`` slices
    out of the pool where they lie and stores them back in place."""
    qn, cfg, p, cache = _hybrid_cell(v5e)
    slots, page = HYBRID_SLOTS, PAGE
    i32 = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e)

    def window(p, cache, tokens, positions, page_table, active):
        def body(carry, _):
            cache, tokens, positions = carry
            logits, cache, moe = qn.decode_step(
                p, cfg, tokens, positions, cache, page_table, page, active,
                moe_stats=True)
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            return (cache, tokens, positions + 1), (tokens, moe)

        return jax.lax.scan(body, (cache, tokens, positions), None,
                            length=2)

    text = jax.jit(window, donate_argnums=(1,)).lower(
        p, cache, i32, i32,
        jax.ShapeDtypeStruct((slots, pages), jnp.int32, sharding=v5e),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e)
    ).compile().as_text()
    _no_window_and_no_pool_copy(
        text, (slots, pages * page, cfg.num_key_value_heads, cfg.head_dim),
        (*jax.tree_util.tree_leaves(cache),
         p["l0.experts_gate"], p["l0.experts_down"]))
    layer_state = cache.slots["gdn_state"].shape[1:]
    assert layer_state == (32, 32, 128, 128)
    assert f"f32[{','.join(map(str, layer_state))}]" not in text


def test_hybrid_chunk_program_inverts_by_products_alone(v5e):
    """The Qwen3-Next chunk program (``prefill_suffix``, one row of 256
    tokens over a 16-page window, the cell's widths and 12 layers): the
    chunked DeltaNet form inverts its 64-row unit triangular matrix by
    merging blocks with plain products (``_unit_lower_inverse``), so
    under ``layer/gdn_chunk`` the compiled program holds one loop a
    DeltaNet layer — the scan over the blocks, which carries the state
    — and no triangular solve in any dress. Until ISSUE 43
    ``lax.linalg.triangular_solve`` stood there, which this compiler
    makes one custom call (``InvertDiagBlocksLowerTriangular``, not the
    ``while`` of 64 row steps other backends expand it into): 0.33 of
    that scope's 0.49 ms a layer-call on the chip."""
    import re

    qn, cfg, p, cache = _hybrid_cell(v5e)
    page, pages, S = PAGE, 16, 256
    i32 = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=v5e)

    def chunk(p, cache, tokens, prefix_lens, seq_lens, page_table, slot_ids):
        return qn.prefill_suffix(p, cfg, tokens, prefix_lens, seq_lens,
                                 cache, page_table, page, slot_ids=slot_ids)

    text = jax.jit(chunk, donate_argnums=(1,)).lower(
        p, cache, jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=v5e),
        i32, i32, jax.ShapeDtypeStruct((1, pages), jnp.int32, sharding=v5e),
        i32).compile().as_text()
    for solve in ("triangular-solve", "triangular_solve", "InvertDiagBlocks"):
        assert solve not in text
    loops = [m.group(1) for m in re.finditer(
        r' while\(.*op_name="([^"]*layer/gdn_chunk[^"]*)"', text)]
    n_linear = sum(kind != "full" for kind in cfg.layer_kinds)
    assert n_linear == 9 and len(loops) == n_linear, loops
    assert all(name.endswith("layer/gdn_chunk/while") for name in loops), loops


def test_mixtral_decode_window_holds_no_expert_slab(v5e):
    """The mixtral decode window at ``mixtral-8x7b-1chip``'s geometry
    (published widths, 8 layers, W8A16, 16 slots, pages of 128): the
    loop over the experts the live rows hit slices one expert's int8
    slab out of the stacked ``[8, in, out]`` weight where it lies, and
    the convert-and-scale sits on the operand of the product that reads
    it. A slab sliced, copied or dequantised into HBM first would be an
    array of a slab's shape among the program's temporaries: they stay
    under one expert's int8 bytes (3 x 4096 x 14336 = 176 MB; 161 MB
    with the loop and 159 MB without it, nearly all of it the
    ``lm_head`` re-laid for its Mosaic call), and no copy has the shape
    of a slab or of a stacked weight. The loop is the expert layer's
    one path (ISSUE 41): a ``while`` a layer carries its name, and the
    program holds no conditional, so no dense pass is compiled, loaded
    or kept warm beside it."""
    import re

    from aigw_tpu.models import mixtral
    from aigw_tpu.models.quant import quantize_tensor

    cfg = mixtral.MixtralConfig(n_layers=8)
    slots, page, pages = 16, 128, 16

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    p = sds(jax.eval_shape(lambda: mixtral.init_params(
        jax.random.PRNGKey(0), cfg,
        finish=lambda n, w: quantize_tensor(n, w, "int8"))))
    kv = jax.ShapeDtypeStruct(
        (cfg.n_layers, 2, (slots * pages + 1) * page, cfg.n_kv_heads,
         cfg.head_dim), jnp.bfloat16, sharding=v5e)
    i32 = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e)

    def window(p, kv, tokens, positions, page_table, active):
        def body(carry, _):
            kv, tokens, positions = carry
            logits, kv, moe = mixtral.decode_step(
                p, cfg, tokens, positions, kv, page_table, page, active,
                moe_stats=True)
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            return (kv, tokens, positions + 1), (tokens, moe)

        return jax.lax.scan(body, (kv, tokens, positions), None, length=2)

    compiled = jax.jit(window, donate_argnums=(1,)).lower(
        p, kv, i32, i32,
        jax.ShapeDtypeStruct((slots, pages), jnp.int32, sharding=v5e),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e)
    ).compile()
    D, F, E = cfg.dim, cfg.ffn_dim, cfg.n_experts
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * D * F
    text = compiled.as_text()
    assert " conditional(" not in text
    assert len(re.findall(
        r' while\(.*op_name="[^"]*layer/moe_experts/[^"]*while"', text)
    ) == cfg.n_layers
    copied = set(re.findall(r"= \w+\[([\d,]*)\]\S* copy\(", text))
    slabs = {",".join(map(str, lead + dims))
             for dims in ((D, F), (F, D)) for lead in ((), (1,), (E,))}
    assert copied and not copied & slabs, sorted(copied & slabs)


def _no_window_and_no_pool_copy(text, window, held):
    """The compiled decode window holds no array of a gathered
    ``[B, P*page, Hkv, D]`` window (the page walk reads whole pages of
    live rows and materialises nothing padded) and copies none of
    ``held`` (a pool handed to the walk's loops stays where it is)."""
    import re

    def name(shape, dtype=r"\w+"):
        return rf"{dtype}\[{','.join(map(str, shape))}\]"

    assert not re.findall(name(window), text)
    copied = set(re.findall(r"= (\w+\[[\d,]*\])\S* copy\(", text))
    pools = {f"{a.dtype.name.replace('float', 'f')}"
             f"[{','.join(map(str, a.shape))}]" for a in held}
    assert copied and not copied & pools, sorted(copied & pools)


@pytest.mark.parametrize("pages", [4, 8, 16])
def test_dense_decode_window_gathers_no_window_and_copies_no_pool(v5e, pages):
    """The llama skeleton's decode window at ``qwen2-7b-1chip``'s
    geometry (16 slots, pages of 128 tokens, 28/4 heads of 128; four of
    its layers, bfloat16 weights: the property is a layer's): the walk
    reads the pool where it lies. A layer sliced out of the pool for the
    walk's loops was a copy of it (67 MB a layer) in ISSUE 31's first
    version, and a page re-laid to ``[page, Hkv*D]`` another. Buckets 4
    and 8 are the ones the two qwen2 cells decode at; since ISSUE 49
    the walk is one loop over the flat list of live (row, page) pairs,
    and the program holds no temporary the size of a layer of the pool
    (a trip is 4 MiB of pages and 229 KB of carried statistics)."""
    import dataclasses

    from aigw_tpu.models import llama
    from aigw_tpu.models.registry import get_model_spec

    cfg = dataclasses.replace(get_model_spec("qwen2-7b").config, n_layers=4)
    slots, page = 16, 128

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    p = sds(jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)))
    kv = jax.ShapeDtypeStruct(
        (cfg.n_layers, 2, (slots * 16 + 1) * page, cfg.n_kv_heads,
         cfg.head_dim), jnp.bfloat16, sharding=v5e)
    i32 = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e)

    def window(p, kv, tokens, positions, page_table, active):
        def body(carry, _):
            kv, tokens, positions = carry
            logits, kv = llama.decode_step(
                p, cfg, tokens, positions, kv, page_table, page, active)
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            return (kv, tokens, positions + 1), tokens

        return jax.lax.scan(body, (kv, tokens, positions), None, length=2)

    compiled = jax.jit(window, donate_argnums=(1,)).lower(
        p, kv, i32, i32,
        jax.ShapeDtypeStruct((slots, pages), jnp.int32, sharding=v5e),
        jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=v5e)
    ).compile()
    _no_window_and_no_pool_copy(
        compiled.as_text(),
        (slots, pages * page, cfg.n_kv_heads, cfg.head_dim), (kv,))
    layer_bytes = 2 * kv.shape[2] * cfg.n_kv_heads * cfg.head_dim * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("rows,queries", [(1, 256), (4, 16), (32, 5)],
                         ids=["chunk", "group", "verify"])
def test_window_read_leaves_the_pool_where_it_lies(v5e, rows, queries):
    """A chunk's, a tail's and a verify program's window read
    (``llama._gather_kv`` over ``kvq.window_kv``, ISSUE 46) with the
    attention behind it, at the hybrid cell's pool (3 layers of 2 KV
    heads of 256, 1025 pages of 128 tokens) and its 32-page bucket:
    whole pages are gathered out of the pool viewed as one list of
    pages, and nothing of the pool's size is copied on the way. Two
    forms did copy, and only the chip's compiler shows it: indexing
    ``kv[layer, w]`` by token copied that layer's K and V out of the
    pool before it gathered (a ``[131200, 2, 256]`` slice, 2.7 ms a
    chunk call on the chip), and the page gather with its output left
    free re-laid the WHOLE pool to the order the attention wants as
    soon as the program had two rows (7.6 ms a call at four rows; 164
    ms a verify-shaped step at qwen2's depth). The pages read are
    pinned to the order they lie in, so what is re-laid is the pages
    read."""
    import re

    from aigw_tpu.models import llama

    L, n_pages, hkv, hd, heads, pages = 3, 1025, 2, 256, 16, 32
    bf16, i32 = jnp.bfloat16, jnp.int32

    def read_and_attend(kv, q, page_table, positions):
        out = 0.0
        for layer in range(L):
            k, v = llama._gather_kv(kv, layer, page_table, PAGE)
            mask = jnp.arange(pages * PAGE, dtype=i32)[None, None, :] \
                <= positions[:, :, None]
            out = out + llama._attention(q, k, v, mask)
        return out

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    pool = (L, 2, n_pages * PAGE, hkv, hd)
    compiled = jax.jit(read_and_attend).lower(
        sds(pool, bf16), sds((rows, queries, heads, hd), bf16),
        sds((rows, pages), i32), sds((rows, queries), i32)).compile()
    text = compiled.as_text()
    big = {",".join(map(str, s)) for s in (
        pool, pool[2:], (L * 2 * n_pages, PAGE, hkv, hd),
        (L * 2 * n_pages * PAGE, hkv, hd))}
    made = set(re.findall(
        r"= \(?\w+\[([\d,]*)\]\S* (?:copy|fusion|slice|reshape)\(", text))
    assert not made & big, sorted(made & big)
    # a page is gathered whole: [rows*pages or rows, pages, 128, 2, 256]
    assert re.search(rf"bf16\[(?:{rows * pages}|{rows},{pages}),"
                     rf"{PAGE},{hkv},{hd}\]\S* fusion\(", text)
    window = rows * pages * PAGE * hkv * hd * 2  # one of K, V: bytes
    logits = rows * heads * queries * pages * PAGE * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * window + 3 * logits


#: operations that only move what they are given
_MOVES = {"parameter", "bitcast", "copy", "transpose", "reshape", "slice",
          "concatenate", "constant", "tuple", "get-tuple-element",
          "dynamic-slice", "pad"}
#: what a weight may pass through and still be a weight and nothing else
#: (the compiler's own streaming of a matrix into fast memory, a quarter
#: at a time, among them)
_CARRIES = {"bitcast", "copy", "transpose", "reshape", "slice", "fusion",
            "concatenate", "tuple", "get-tuple-element", "custom-call",
            "slice-start", "slice-done", "copy-start", "copy-done"}


def _weight_moves(text, floor=1 << 20):
    """``[(name, shape, bytes)]`` of the operations of an optimised HLO
    module that RE-LAY A WEIGHT OUT: a ``copy``, a ``transpose`` or a
    fusion that only moves data, of ``floor`` bytes or more, standing
    on its own in the program (the entry computation, or a loop's body
    for what the loop carries unchanged) with nothing but parameters of
    the argument ``p`` behind its operands. What sits INSIDE a
    product's fusion is how that product reads its operand, not an
    operation of its own, and is not looked at."""
    import math
    import re

    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = comps.setdefault(head.group(2), {})
            entry = head.group(2) if head.group(1) else entry
            continue
        ins = cur is not None and re.match(
            r"\s*(ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*?)\)(.*)$",
            line)
        if not ins:
            cur = None if line.startswith("}") else cur
            continue
        root, name, shape, op, args, rest = ins.groups()

        def attr(pattern, rest=rest):
            found = re.search(pattern, rest)
            return found.group(1) if found else ""

        cur[name] = {
            "name": name, "shape": shape, "op": op, "root": bool(root),
            "args": re.findall(r"%([\w.\-]+)", args),
            "calls": attr(r"calls=%([\w.\-]+)"),
            "body": attr(r"body=%([\w.\-]+)"),
            "index": attr(r"index=(\d+)"),
            "of": attr(r'op_name="([^"]*)"')}

    def nbytes(shape):
        dims = re.match(r"(\w+)\[([\d,]*)\]", shape)
        if not dims:
            return 0
        width = {"bf16": 2, "f16": 2, "s8": 1, "u8": 1, "pred": 1}
        return width.get(dims.group(1), 4) * math.prod(
            int(d) for d in dims.group(2).split(",") if d)

    found = []

    def scan(comp, weights):
        weights = set(weights)
        for ins in comp.values():  # the text defines before it uses
            if ins["op"] == "parameter":
                if comp is comps[entry] and ins["of"].startswith("p["):
                    weights.add(ins["name"])
                continue
            alone = ins["args"] and all(a in weights for a in ins["args"])
            if ins["op"] == "while" and ins["body"]:
                carried(comp, ins, weights)
            if not alone or ins["op"] not in _CARRIES or (
                    ins["op"] == "fusion" and not all(
                        i["op"] in _MOVES
                        for i in comps[ins["calls"]].values())):
                continue
            weights.add(ins["name"])
            if ins["op"] in ("copy", "transpose", "fusion") \
                    and nbytes(ins["shape"]) >= floor:
                found.append((ins["name"], ins["shape"],
                              nbytes(ins["shape"])))

    def carried(comp, loop, weights):
        """A loop's body, with the weights its carry hands through."""
        init, body = comp.get(loop["args"][0]), comps[loop["body"]]
        out = next(i for i in body.values() if i["root"])
        if not init or init["op"] != "tuple" or out["op"] != "tuple":
            return
        arg = next(i["name"] for i in body.values()
                   if i["op"] == "parameter")
        scan(body, {
            g["name"] for g in body.values()
            if g["op"] == "get-tuple-element" and g["args"] == [arg]
            and init["args"][int(g["index"])] in weights
            and out["args"][int(g["index"])] == g["name"]})

    scan(comps[entry], ())
    return found


@pytest.mark.parametrize("program,tree", [
    ("decode_step", "serving"), ("prefill_suffix", "serving"),
    ("decode_window", "serving"), ("decode_step", "published")])
def test_no_latent_program_relays_a_weight(v5e, program, tree):
    """The latent family's decode step, its 256-token chunk and its
    two-step decode window at ``a.x-k1-1chip``'s geometry (published
    widths, 1 dense + 5 expert layers, 12 of 192 experts, 16 slots of
    64 pages of 128: the shapes of tests/cellbench/
    test_cellbench_axk1.py ``test_fits_one_v5e_chip``), parameters from
    ``axk1.serving_params`` by ``eval_shape``: no ``copy``, no
    ``transpose`` and no slice-only fusion of 1 MB or more whose
    operands are parameters alone (ISSUE 50).

    The parent (be12620), whose programs read ``wq_b`` and ``wkv_b`` as
    published, held TWELVE such copies in each of the three programs,
    two a layer, 327 MB a call: ``bf16[1536,12288]{1,0} -> {0,1}`` (37.7
    MB) and ``bf16[512,16384]{1,0} -> {0,1}`` (16.8 MB) — the compiler
    wants the query head-major and ``W_kvb`` contracted with the head as
    a batch dimension, and got both by transposing the WEIGHT, every
    call; in the window all twelve stand in the entry computation,
    hoisted out of the loop. On the chip (PERF.md section 5, PR 50):
    0.83 ms of every window and 0.41 ms of every chunk call. The twelve
    ``bf16[512,64,128]`` head-major slices of ``W_kvb``'s halves (100
    MB) that the same programs show sit INSIDE the products' fusions:
    how a product reads its operand, no operation of their own — the
    trace has no event for them — so this test does not count them.
    The ``published`` case is the control: the same step on
    ``init_params``' own tree, which the programs still accept, must
    show the parent's twelve, or the detector sees nothing."""
    from aigw_tpu.models import axk1

    cfg = axk1.AXK1Config(num_hidden_layers=6, num_experts=12,
                          router_experts=192, vocab_size=20480)
    slots, page, pages = 16, PAGE, 64

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    published = jax.eval_shape(
        lambda: axk1.init_params(jax.random.PRNGKey(0), cfg))
    p = sds(published if tree == "published" else jax.eval_shape(
        lambda q: axk1.serving_params(q, cfg), published))
    cache = sds(jax.eval_shape(lambda: cfg.cache_spec().make(
        (slots * pages + 1) * page, slots, "bfloat16")))

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def step(p, cache, tokens, positions, page_table, active):
        return axk1.decode_step(p, cfg, tokens, positions, cache,
                                page_table, page, active, moe_stats=True)

    def window(p, cache, tokens, positions, page_table, active):
        def body(carry, _):
            cache, tokens, positions = carry
            logits, cache, moe = step(p, cache, tokens, positions,
                                      page_table, active)
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            return (cache, tokens, positions + 1), (tokens, moe)

        return jax.lax.scan(body, (cache, tokens, positions), None,
                            length=2)

    def chunk(p, cache, tokens, prefix_lens, seq_lens, page_table):
        return axk1.prefill_suffix(p, cfg, tokens, prefix_lens, seq_lens,
                                   cache, page_table, page, moe_stats=True)

    rows = (arg((slots,)), arg((slots,)), arg((slots, pages)),
            arg((slots,), jnp.bool_))
    fn, args = {
        "decode_step": (step, rows), "decode_window": (window, rows),
        "prefill_suffix": (chunk, (arg((1, 256)), arg((1,)), arg((1,)),
                                   arg((1, pages))))}[program]
    text = jax.jit(fn, donate_argnums=(1,)).lower(
        p, cache, *args).compile().as_text()
    moves = _weight_moves(text)
    if tree == "published":
        assert len(moves) == 2 * cfg.num_hidden_layers, moves
        assert sum(m[2] for m in moves) == 6 * 2 * (
            1536 * 64 * 192 + 512 * 64 * 256)  # 327 MB
        return
    assert not moves, moves
    # and it is the serving leaves the program reads
    assert "p[\\'l0.wq_nope\\']" in text and "l0.wq_b" not in text
