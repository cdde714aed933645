"""Operations and bytes the ALGORITHM needs for the window-and-global
family (``model_type: mimo_v2``: softmax attention of two kinds in one
stack — global layers over pages that grow with the context, sliding-
window layers over the last ``sliding_window`` keys of a slot's ring —
keys wider than values, a head count a kind, a leading dense layer,
expert layers of which this chip holds a share, no shared expert), from
a configuration file's keys. Beside ``roofline.py``,
``roofline_hybrid.py`` and ``roofline_latent.py`` and for their reasons:
kept with the benchmark so that no later PR can move a roofline share by
recounting, and counting the LEAST the work has to do — each matrix at
most once a call, only the held experts that tokens were routed to,
every cached key a real query has to see and no other (a window layer's
query: ``sliding_window`` of them at the most), no padding of a page
bucket.

Per kernel (the named scopes of models/mimo_v2.py), each function gives
(floating-point operations, bytes) of ONE layer: ``qkv`` (with
``rope``, which multiplies nothing by a matrix), ``attn_global`` (a
chunk's attention over its page window, and a decode step's
``kv_walk``), ``attn_window`` (with ``ring_write``), ``attn_out``,
``mlp``, ``moe_route``, ``moe_experts``. ``tokens`` are the rows a call
runs (a chunk's padded tokens, a step's live rows). The two metrics of
the manifest add them up over the layers: ``decode_seconds`` and
``prefill_seconds``, each the larger of its FLOP and its byte bound.
``trace_reduce.py`` gives no time per scope, so a kernel's own share is
taken from a builder's trace (PERF.md, sections 5 and 7).
"""

from __future__ import annotations

BF16 = 2


def dims(doc: dict) -> dict:
    """The shapes, by short names. ``E`` experts are held here of a
    router ``R`` wide (``cellbench.model_fields.router_experts``);
    ``Lg`` global and ``Lw`` window layers (``hybrid_layer_pattern``: 0
    and 1), ``Hg`` and ``Hw`` their key heads."""
    mf = doc.get("cellbench", {}).get("model_fields", {})
    L = doc["num_hidden_layers"]
    pattern = doc["hybrid_layer_pattern"]
    dense = mf.get("first_dense_layers",
                   sum(1 for f in doc.get("moe_layer_freq", []) if not f))
    return {
        "D": doc["hidden_size"], "V": doc["vocab_size"], "L": L,
        "Lw": sum(1 for k in pattern if k), "Lg": sum(
            1 for k in pattern if not k),
        "n_dense": dense, "n_moe": L - dense,
        "I": doc["intermediate_size"], "H": doc["num_attention_heads"],
        "Hg": doc["num_key_value_heads"],
        "Hw": doc["swa_num_key_value_heads"],
        "dk": doc["head_dim"], "dv": doc["v_head_dim"],
        "W": doc["sliding_window"], "E": doc["n_routed_experts"],
        "R": mf.get("router_experts") or doc["n_routed_experts"],
        "k": doc["num_experts_per_tok"], "F": doc["moe_intermediate_size"],
    }


# -- parameters (elements) --------------------------------------------------
def row_width(m: dict, kind: str) -> int:
    """Values a token leaves in a layer of ``kind`` ("g" or "w"): each
    key head's value and key."""
    return m["H" + kind] * (m["dv"] + m["dk"])


def qkv_params(m: dict, kind: str) -> int:
    """The fused projection: the query heads, and the layer kind's key
    and value heads."""
    return m["D"] * (m["H"] * m["dk"] + row_width(m, kind))


def out_params(m: dict) -> int:
    return m["H"] * m["dv"] * m["D"]


def expert_params(m: dict) -> int:
    return 3 * m["D"] * m["F"]


def dense_params(m: dict) -> int:
    return 3 * m["D"] * m["I"]


def param_count(m: dict) -> int:
    """Every parameter the replica holds: the matrices, the norms (two a
    layer and the last), a sink a head a window layer, a selection bias
    an expert of the router's width an expert layer."""
    attn = (m["Lg"] * qkv_params(m, "g") + m["Lw"] * qkv_params(m, "w")
            + m["L"] * out_params(m) + m["Lw"] * m["H"])
    moe = m["D"] * m["R"] + m["R"] + m["E"] * expert_params(m)
    return (attn + m["n_dense"] * dense_params(m) + m["n_moe"] * moe
            + (2 * m["L"] + 1) * m["D"] + 2 * m["V"] * m["D"])


def param_bytes(m: dict) -> int:
    """bfloat16, but for the float32 sinks and selection biases."""
    return BF16 * param_count(m) + 2 * (m["Lw"] * m["H"]
                                        + m["n_moe"] * m["R"])


def cache_bytes_per_token(m: dict) -> int:
    """What a token adds to the PAGES: one bfloat16 row a global layer.
    A window layer's ring does not grow."""
    return m["Lg"] * row_width(m, "g") * BF16


def ring_bytes_per_slot(m: dict) -> int:
    """A slot's rings: ``W`` rows a window layer, whatever the
    context."""
    return m["Lw"] * m["W"] * row_width(m, "w") * BF16


def experts_touched(m: dict, local_assignments: float) -> float:
    """Held experts that get at least one of ``local_assignments``
    assignments spread evenly: E (1 - (1 - 1/E)^n)."""
    return m["E"] * (1.0 - (1.0 - 1.0 / m["E"]) ** local_assignments)


# -- one layer's kernels: (FLOPs, bytes) ------------------------------------
def qkv(m: dict, tokens: float, kind: str) -> tuple[float, float]:
    """The projection, and the rows it leaves in the pages or the
    ring."""
    return (2.0 * tokens * qkv_params(m, kind),
            BF16 * (qkv_params(m, kind) + tokens * row_width(m, kind)))


def attend(m: dict, pairs: float, rows_read: float,
           kind: str) -> tuple[float, float]:
    """Attention over ``pairs`` (query, key) pairs that read
    ``rows_read`` cached rows of a layer of ``kind`` between them: a
    pair costs a score product ``dk`` wide and a value product ``dv``
    wide, a query head."""
    return (2.0 * pairs * m["H"] * (m["dk"] + m["dv"]),
            BF16 * rows_read * row_width(m, kind))


def attn_out(m: dict, tokens: float) -> tuple[float, float]:
    return 2.0 * tokens * out_params(m), BF16 * out_params(m)


def mlp(m: dict, tokens: float) -> tuple[float, float]:
    return 2.0 * tokens * dense_params(m), BF16 * dense_params(m)


def moe_route(m: dict, tokens: float) -> tuple[float, float]:
    return 2.0 * tokens * m["D"] * m["R"], BF16 * m["D"] * m["R"]


def moe_experts(m: dict, tokens: float,
                touched: float | None = None) -> tuple[float, float]:
    """The held experts' part: each token places k·E/R assignments here
    on average, each a 3-matrix expert; the weights of the experts
    touched stream once. There is no shared expert."""
    local = tokens * m["k"] * m["E"] / m["R"]
    if touched is None:
        touched = experts_touched(m, local)
    return 2.0 * local * expert_params(m), BF16 * touched * expert_params(m)


# -- the two programs -------------------------------------------------------
def _calls(m: dict, calls: float, tokens: float,
           touched: float | None) -> tuple[float, float]:
    """``calls`` calls of ``tokens`` rows each through everything that
    does not depend on the context: every layer's matrices, the
    feed-forward blocks, the head at one position a row of a decode
    step or at the last of a chunk (``tokens`` of it at the most)."""
    flops = nbytes = 0.0
    for n, parts in (
            (m["Lg"], [qkv(m, tokens, "g"), attn_out(m, tokens)]),
            (m["Lw"], [qkv(m, tokens, "w"), attn_out(m, tokens)]),
            (m["n_dense"], [mlp(m, tokens)]),
            (m["n_moe"], [moe_route(m, tokens),
                          moe_experts(m, tokens, touched)])):
        flops += n * sum(f for f, _ in parts)
        nbytes += n * sum(b for _, b in parts)
    return (calls * (flops + 2.0 * m["D"] * m["V"]),
            calls * (nbytes + BF16 * m["D"] * m["V"]))


def _bound(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def decode_seconds(doc: dict, steps: float, rows: float, pages: float,
                   hits: float, window_keys: float, page_size: int,
                   peaks: dict) -> float:
    """The least time ``steps`` decode steps could take that ran
    ``rows`` live rows in all, whose contexts held ``pages`` pages of
    ``page_size`` tokens a GLOBAL layer in all (the engine's
    ``decode_kv_pages_live``: pages are read whole), whose window
    layers' softmaxes saw ``window_keys`` keys SUMMED OVER THOSE LAYERS
    (``swa_keys_attended``: at most ``sliding_window`` a row a layer)
    and whose expert layers' loops made ``hits`` trips in all (one held
    expert's matrices a trip). The byte bound reads every live page
    once a global layer and every ring row a window layer's query sees
    once; the FLOP bound counts the global layers' products over the
    tokens those pages hold AT THE LEAST — a row's last page may hold
    one token — and the window layers' over the keys they saw."""
    if steps <= 0:
        return 0.0
    m = dims(doc)
    flops, nbytes = _calls(m, steps, rows / steps, 0.0)
    nbytes += hits * BF16 * expert_params(m)
    tokens = max(pages - rows, 0.0) * page_size + rows
    g_f, _ = attend(m, tokens, tokens, "g")
    w_f, w_b = attend(m, window_keys, window_keys, "w")
    flops += m["Lg"] * g_f + w_f
    nbytes += m["Lg"] * BF16 * pages * page_size * row_width(m, "g") + w_b
    return _bound(flops, nbytes, peaks)


def prefill_seconds(doc: dict, calls: float, padded: float, real: float,
                    attended: float, peaks: dict) -> float:
    """The least time ``calls`` chunk or tail calls could take that ran
    ``padded`` token slots in all (the program runs the padding),
    ``real`` of them real queries that attended, in the GLOBAL layers,
    to ``attended`` (query, key) pairs summed over those layers (the
    engine's ``prefill_keys_attended``). A call reads each cached row
    of its context once a global layer: at the least its mean query's,
    ``pairs / real``. In a window layer a real query sees
    ``sliding_window`` keys but for the first of a prompt, which see
    fewer: every call is counted as if it were a prompt's first, and a
    call reads the ring's ``sliding_window - 1`` earlier rows."""
    if calls <= 0 or real <= 0:
        return 0.0
    m = dims(doc)
    flops, nbytes = _calls(m, calls, padded / calls, None)
    pairs = attended / max(m["Lg"], 1)
    g_f, g_b = attend(m, pairs, calls * pairs / real, "g")
    w_pairs = max(real * m["W"] - calls * m["W"] * (m["W"] - 1) / 2.0, 0.0)
    w_f, w_b = attend(m, w_pairs, calls * (m["W"] - 1), "w")
    flops += m["Lg"] * g_f + m["Lw"] * w_f
    nbytes += m["Lg"] * g_b + m["Lw"] * w_b
    return _bound(flops, nbytes, peaks)
