"""Operations and bytes the ALGORITHM needs for the latent-attention
family (``model_type: axk1``: multi-head latent attention over ONE
cached row a token a layer, a leading dense layer, expert layers of
which this chip holds a share behind a group-limited sigmoid router),
from a configuration file's keys. Beside ``roofline.py`` and
``roofline_hybrid.py`` and for their reasons: kept with the benchmark so
that no later PR can move a roofline share by recounting, and counting
the LEAST the work has to do — each matrix at most once a call, only
the held experts that tokens were routed to, every cached row a real
query has to see and no other, in whichever of the two forms of latent
attention is the cheaper, no padding of a page bucket.

Per kernel (the named scopes of models/axk1.py), each function gives
(floating-point operations, bytes) of ONE layer: ``mla_q``, ``mla_kv``,
``mla_attn`` (a chunk's attention, and a decode step's ``kv_walk``),
``mla_out``, ``mlp``, ``moe_route``, ``moe_experts``, ``moe_shared``.
``tokens`` are the rows a call runs (a chunk's padded tokens, a step's
live rows). The two metrics of the manifest add them up over the layers:
``decode_seconds`` and ``prefill_seconds``, each the larger of its FLOP
and its byte bound. ``trace_reduce.py`` gives no time per scope, so a
kernel's own share is taken from a builder's trace (PERF.md, sections 5
and 7).
"""

from __future__ import annotations

BF16 = 2


def dims(doc: dict) -> dict:
    """The shapes, by short names. ``E`` experts are held here of a
    router ``R`` wide (``cellbench.model_fields.router_experts``)."""
    mf = doc.get("cellbench", {}).get("model_fields", {})
    L = doc["num_hidden_layers"]
    dense = mf.get("first_dense_layers", doc.get("first_k_dense_replace", 0))
    return {
        "D": doc["hidden_size"], "V": doc["vocab_size"], "L": L,
        "n_dense": dense, "n_moe": L - dense,
        "I": doc["intermediate_size"], "H": doc["num_attention_heads"],
        "rq": doc["q_lora_rank"], "r": doc["kv_lora_rank"],
        "dn": doc["qk_nope_head_dim"], "dr": doc["qk_rope_head_dim"],
        "dv": doc["v_head_dim"], "E": doc["n_routed_experts"],
        "R": mf.get("router_experts") or doc["n_routed_experts"],
        "k": doc["num_experts_per_tok"], "F": doc["moe_intermediate_size"],
        "Fs": doc["n_shared_experts"] * doc["moe_intermediate_size"],
    }


# -- parameters (elements) --------------------------------------------------
def q_params(m: dict) -> int:
    """wq_a and wq_b."""
    return m["D"] * m["rq"] + m["rq"] * m["H"] * (m["dn"] + m["dr"])


def kv_params(m: dict) -> int:
    """wkv_a: the latent and the shared rotated key."""
    return m["D"] * (m["r"] + m["dr"])


def kvb_params(m: dict) -> int:
    """wkv_b: each head's key and value out of the latent."""
    return m["r"] * m["H"] * (m["dn"] + m["dv"])


def out_params(m: dict) -> int:
    return m["H"] * m["dv"] * m["D"]


def expert_params(m: dict) -> int:
    return 3 * m["D"] * m["F"]


def shared_params(m: dict) -> int:
    return 3 * m["D"] * m["Fs"]


def dense_params(m: dict) -> int:
    return 3 * m["D"] * m["I"]


def attn_layer_params(m: dict) -> int:
    """What every layer holds beside its feed-forward block: attention
    and the norms (two of the hidden row, the compressed query's, the
    latent's)."""
    return (q_params(m) + kv_params(m) + kvb_params(m) + out_params(m)
            + 2 * m["D"] + m["rq"] + m["r"])


def param_count(m: dict) -> int:
    """Every parameter the replica holds: what
    ``expect.param_bytes_total`` is two bytes each of."""
    moe = m["D"] * m["R"] + m["E"] * expert_params(m) + shared_params(m)
    return (m["L"] * attn_layer_params(m) + m["n_dense"] * dense_params(m)
            + m["n_moe"] * moe + 2 * m["V"] * m["D"] + m["D"])


def row_width(m: dict) -> int:
    """Values a token leaves in a layer: latent | rotated key."""
    return m["r"] + m["dr"]


def cache_bytes_per_token(m: dict) -> int:
    """One bfloat16 row a token a layer."""
    return m["L"] * row_width(m) * BF16


def experts_touched(m: dict, local_assignments: float) -> float:
    """Held experts that get at least one of ``local_assignments``
    assignments spread evenly: E (1 - (1 - 1/E)^n)."""
    return m["E"] * (1.0 - (1.0 - 1.0 / m["E"]) ** local_assignments)


# -- one layer's kernels: (FLOPs, bytes) ------------------------------------
def mla_q(m: dict, tokens: float) -> tuple[float, float]:
    return 2.0 * tokens * q_params(m), BF16 * q_params(m)


def mla_kv(m: dict, tokens: float) -> tuple[float, float]:
    """The projection, and the rows it appends to the cache."""
    return (2.0 * tokens * kv_params(m),
            BF16 * (kv_params(m) + tokens * row_width(m)))


def mla_attn(m: dict, pairs: float, rows_read: float,
             calls: float) -> tuple[float, float]:
    """Attention over ``pairs`` (query, key) pairs, ``calls`` calls' in
    all, that read ``rows_read`` cached rows between them, in the
    cheaper form. Absorbed: a pair costs a product over latent + rotated
    key and one over the latent, a head. Expanded: a pair costs key and
    value products at head width, and every row a call reads is
    decompressed through ``W_kvb`` once. (Folding ``W_kvb`` into the
    query and applying its value half to the output is ``mla_out``'s.)"""
    if pairs <= 0:
        return 0.0, 0.0
    absorbed = 2.0 * pairs * m["H"] * (2 * m["r"] + m["dr"])
    expanded = (2.0 * pairs * m["H"] * (m["dn"] + m["dr"] + m["dv"])
                + 2.0 * rows_read * kvb_params(m))
    return min(absorbed, expanded), BF16 * rows_read * row_width(m)


def mla_out(m: dict, tokens: float) -> tuple[float, float]:
    """``W_kvb``'s two halves on each token's heads (the key half onto
    the query, the value half onto the attended latent) and the output
    projection; ``W_kvb`` is read once for both."""
    return (2.0 * tokens * (out_params(m) + kvb_params(m)),
            BF16 * (out_params(m) + kvb_params(m)))


def mlp(m: dict, tokens: float) -> tuple[float, float]:
    return 2.0 * tokens * dense_params(m), BF16 * dense_params(m)


def moe_route(m: dict, tokens: float) -> tuple[float, float]:
    return 2.0 * tokens * m["D"] * m["R"], BF16 * m["D"] * m["R"]


def moe_experts(m: dict, tokens: float,
                touched: float | None = None) -> tuple[float, float]:
    """The held experts' part: each token places k·E/R assignments here
    on average, each a 3-matrix expert; the weights of the experts
    touched stream once."""
    local = tokens * m["k"] * m["E"] / m["R"]
    if touched is None:
        touched = experts_touched(m, local)
    return 2.0 * local * expert_params(m), BF16 * touched * expert_params(m)


def moe_shared(m: dict, tokens: float) -> tuple[float, float]:
    return 2.0 * tokens * shared_params(m), BF16 * shared_params(m)


# -- the two programs -------------------------------------------------------
def _calls(m: dict, calls: float, tokens: float,
           touched: float | None) -> tuple[float, float]:
    """``calls`` calls of ``tokens`` rows each through everything that
    does not depend on the context: every layer's matrices, the
    feed-forward blocks, the head at one position a row of a decode
    step or at the last of a chunk (``tokens`` of it at the most)."""
    per = [mla_q(m, tokens), mla_kv(m, tokens), mla_out(m, tokens)]
    flops = m["L"] * sum(f for f, _ in per)
    nbytes = m["L"] * sum(b for _, b in per)
    for n, parts in ((m["n_dense"], [mlp(m, tokens)]),
                     (m["n_moe"], [moe_route(m, tokens),
                                   moe_experts(m, tokens, touched),
                                   moe_shared(m, tokens)])):
        flops += n * sum(f for f, _ in parts)
        nbytes += n * sum(b for _, b in parts)
    return (calls * (flops + 2.0 * m["D"] * m["V"]),
            calls * (nbytes + BF16 * m["D"] * m["V"]))


def _bound(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def decode_seconds(doc: dict, steps: float, rows: float, pages: float,
                   hits: float, page_size: int, peaks: dict) -> float:
    """The least time ``steps`` decode steps could take that ran
    ``rows`` live rows in all, whose contexts held ``pages`` pages of
    ``page_size`` tokens a layer in all (the engine's
    ``decode_kv_pages_live``: pages are read whole) and whose expert
    layers' loops made ``hits`` trips in all (one held expert's matrices
    a trip). The byte bound reads every live page once a layer; the
    FLOP bound counts the absorbed products over the tokens those pages
    hold AT THE LEAST — a row's last page may hold one token."""
    if steps <= 0:
        return 0.0
    m = dims(doc)
    flops, nbytes = _calls(m, steps, rows / steps, 0.0)
    nbytes += hits * BF16 * expert_params(m)
    tokens = max(pages - rows, 0.0) * page_size + rows
    a_f, _ = mla_attn(m, tokens, tokens, rows)
    flops += m["L"] * a_f
    nbytes += m["L"] * BF16 * pages * page_size * row_width(m)
    return _bound(flops, nbytes, peaks)


def prefill_seconds(doc: dict, calls: float, padded: float, real: float,
                    attended: float, peaks: dict) -> float:
    """The least time ``calls`` chunk or tail calls could take that ran
    ``padded`` token slots in all (the program runs the padding),
    ``real`` of them real queries that attended to ``attended`` (query,
    key) pairs SUMMED OVER THE LAYERS (the engine's
    ``prefill_keys_attended``). A call reads each cached row of its
    context once: at the least its mean query's, ``pairs / real``."""
    if calls <= 0 or real <= 0:
        return 0.0
    m = dims(doc)
    flops, nbytes = _calls(m, calls, padded / calls, None)
    pairs = attended / m["L"]
    a_f, a_b = mla_attn(m, pairs, calls * pairs / real, calls)
    flops += m["L"] * a_f
    nbytes += m["L"] * a_b
    return _bound(flops, nbytes, peaks)
