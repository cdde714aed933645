"""Operations and bytes the ALGORITHM needs for the hybrid family
(``model_type: qwen3_next``: Gated DeltaNet layers, gated attention
every ``full_attention_interval``-th, an expert layer of which this
chip holds a share), from a configuration file's keys. Beside
``roofline.py`` and for its reasons: kept with the benchmark so that no
later PR can move a roofline share by recounting, and counting the
LEAST the work has to do — weights at most once a call and only those
of experts that tokens were routed to, a live slot's state once in and
once out, the contexts' own keys and values, no padding, no dense pass
over experts nobody chose.

Per kernel (the named scopes of models/qwen3_next.py), for a call over
``tokens`` tokens, each function gives (floating-point operations,
bytes): ``gdn_proj``, ``gdn_conv``, ``gdn_chunk``, ``gdn_recurrent``,
``attn_gated``, ``moe_route``, ``moe_experts``, ``moe_shared``. The two
metrics of the manifest add them up over the layers: a decode step
(``decode_step_bytes``: bandwidth-bound) and a prefill call
(``prefill_call_seconds``: the larger of its FLOP and its byte bound).
``trace_reduce.py`` gives no time per scope yet, so a kernel's own
share is taken from a builder's trace (PERF.md, sections 6 and 7).
"""

from __future__ import annotations

BF16, F32 = 2, 4


def dims(doc: dict) -> dict:
    """The shapes, by short names. ``E`` experts are held here of a
    router ``R`` wide (``cellbench.model_fields.router_experts``)."""
    mf = doc.get("cellbench", {}).get("model_fields", {})
    L, every = doc["num_hidden_layers"], doc["full_attention_interval"]
    n_full = sum(1 for i in range(L) if (i + 1) % every == 0)
    Hk, Hv = doc["linear_num_key_heads"], doc["linear_num_value_heads"]
    dk, dv = doc["linear_key_head_dim"], doc["linear_value_head_dim"]
    return {
        "D": doc["hidden_size"], "V": doc["vocab_size"], "L": L,
        "n_full": n_full, "n_lin": L - n_full,
        "H": doc["num_attention_heads"], "Hkv": doc["num_key_value_heads"],
        "hd": doc["head_dim"],
        "Hk": Hk, "Hv": Hv, "dk": dk, "dv": dv,
        "kd": Hk * dk, "vd": Hv * dv, "conv": 2 * Hk * dk + Hv * dv,
        "K": doc["linear_conv_kernel_dim"],
        "E": doc["num_experts"], "R": mf.get("router_experts")
        or doc["num_experts"], "k": doc["num_experts_per_tok"],
        "F": doc["moe_intermediate_size"],
        "Fs": doc["shared_expert_intermediate_size"],
    }


# -- parameters (elements) --------------------------------------------------
def gdn_proj_params(m: dict) -> int:
    """in_proj_qkvz, in_proj_ba and out_proj of one DeltaNet layer."""
    return m["D"] * (2 * m["kd"] + 2 * m["vd"]) + m["D"] * 2 * m["Hv"] \
        + m["vd"] * m["D"]


def attn_params(m: dict) -> int:
    """q (query and gate), k, v and o of one full-attention layer."""
    return m["D"] * m["H"] * 2 * m["hd"] + 2 * m["D"] * m["Hkv"] * m["hd"] \
        + m["H"] * m["hd"] * m["D"]


def expert_params(m: dict) -> int:
    return 3 * m["D"] * m["F"]


def shared_params(m: dict) -> int:
    return 3 * m["D"] * m["Fs"] + m["D"]


def state_bytes_per_slot(m: dict) -> int:
    """One slot's float32 state and bfloat16 convolution tail, all
    DeltaNet layers."""
    return m["n_lin"] * (m["Hv"] * m["dk"] * m["dv"] * F32
                         + (m["K"] - 1) * m["conv"] * BF16)


def kv_bytes_per_token(m: dict) -> int:
    return m["n_full"] * 2 * m["Hkv"] * m["hd"] * BF16


def param_count(m: dict) -> int:
    """Every parameter the replica holds (the norms' few thousand
    included): what ``expect.param_bytes_total`` is two bytes each of."""
    per_layer = (m["D"] * m["R"] + m["E"] * expert_params(m)
                 + shared_params(m) + 2 * m["D"])
    lin = gdn_proj_params(m) + m["K"] * m["conv"] + 2 * m["Hv"] + m["dv"]
    full = attn_params(m) + 2 * m["hd"]
    return (m["L"] * per_layer + m["n_lin"] * lin + m["n_full"] * full
            + 2 * m["V"] * m["D"] + m["D"])


def experts_touched(m: dict, local_assignments: float) -> float:
    """Held experts that get at least one of ``local_assignments``
    assignments spread evenly: E (1 - (1 - 1/E)^n)."""
    return m["E"] * (1.0 - (1.0 - 1.0 / m["E"]) ** local_assignments)


# -- one layer's kernels over `tokens` tokens: (FLOPs, bytes) ---------------
def gdn_proj(m: dict, tokens: int) -> tuple[float, float]:
    return 2.0 * tokens * gdn_proj_params(m), BF16 * gdn_proj_params(m)


def gdn_conv(m: dict, tokens: int) -> tuple[float, float]:
    return (2.0 * tokens * m["K"] * m["conv"],
            BF16 * (m["K"] * m["conv"] + 2 * (m["K"] - 1) * m["conv"]
                    + 2 * tokens * m["conv"]))


def gdn_recurrent(m: dict, slots: float) -> tuple[float, float]:
    """One token of ``slots`` live sequences: decay, Sᵀk, the rank-one
    write and Sᵀq are four passes over the state; it is read once and
    written once."""
    state = m["Hv"] * m["dk"] * m["dv"]
    return 7.0 * slots * state, 2.0 * slots * state * F32


def gdn_chunk(m: dict, tokens: int, block: int = 64) -> tuple[float, float]:
    """The chunked (WY) form over ``tokens`` tokens of one sequence, in
    blocks of ``block``: per block and head, kkᵀ and qkᵀ (2·C²·dk each),
    the triangular solve (C³/3), T·v and T·k, w·S, q·S, the local
    product and the state's update (2·C·dk·dv each, the local one
    2·C²·dv). The state comes in once and goes out once."""
    C = block
    n = -(-tokens // C)
    per = (4.0 * C * C * m["dk"] + C ** 3 / 3.0 + 2.0 * C * C * m["dv"]
           + 2.0 * C * C * m["dk"] + 2.0 * C * C * m["dv"]
           + 6.0 * C * m["dk"] * m["dv"])
    return (n * m["Hv"] * per,
            2.0 * m["Hv"] * m["dk"] * m["dv"] * F32
            + BF16 * tokens * (m["conv"] + m["vd"]))


def attn_gated(m: dict, tokens: int, context: float) -> tuple[float, float]:
    """Projections and gate of ``tokens`` tokens that attend over
    ``context`` keys each (on average), and those keys' and values'
    bytes."""
    scores = 4.0 * tokens * context * m["H"] * m["hd"]
    return (2.0 * tokens * attn_params(m) + scores,
            BF16 * attn_params(m)
            + 2.0 * context * m["Hkv"] * m["hd"] * BF16)


def moe_route(m: dict, tokens: int) -> tuple[float, float]:
    return 2.0 * tokens * m["D"] * m["R"], F32 * m["D"] * m["R"]


def moe_experts(m: dict, tokens: float,
                touched: float | None = None) -> tuple[float, float]:
    """The held experts' part: each token places k·E/R assignments here
    on average, each a 3-matrix expert; the weights of the experts
    touched stream once."""
    local = tokens * m["k"] * m["E"] / m["R"]
    if touched is None:
        touched = experts_touched(m, local)
    return 2.0 * local * expert_params(m), BF16 * touched * expert_params(m)


def moe_shared(m: dict, tokens: int) -> tuple[float, float]:
    return 2.0 * tokens * shared_params(m), BF16 * shared_params(m)


# -- the two programs -------------------------------------------------------
def decode_step_bytes(doc: dict, live_slots: float, kv_bytes_live: float,
                      experts_hit_per_layer: float | None = None) -> float:
    """Bytes one decode step has to move: every layer's own matrices
    once, the weights of the held experts that this step's tokens were
    routed to (``experts_hit_per_layer``, measured; else the even
    spread), the live slots' state read and written with their
    convolution tails, the live contexts' keys and values, and the
    output head."""
    m = dims(doc)
    total = BF16 * m["D"] * m["V"] + kv_bytes_live
    total += 2.0 * live_slots * state_bytes_per_slot(m)
    for kind, n in (("lin", m["n_lin"]), ("full", m["n_full"])):
        own = gdn_proj_params(m) + m["K"] * m["conv"] if kind == "lin" \
            else attn_params(m)
        total += n * BF16 * own
    per_layer = (moe_route(m, 1)[1] + moe_shared(m, 1)[1]
                 + moe_experts(m, live_slots, experts_hit_per_layer)[1])
    return total + m["L"] * per_layer


def prefill_call(doc: dict, tokens: int, context: float
                 ) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill call over ``tokens`` tokens of one
    sequence whose tokens attend over ``context`` keys on average."""
    m = dims(doc)
    flops = 2.0 * m["D"] * m["V"]  # the head, at the last position
    nbytes = BF16 * m["D"] * m["V"]
    lin = [gdn_proj(m, tokens), gdn_conv(m, tokens), gdn_chunk(m, tokens)]
    full = [attn_gated(m, tokens, context)]
    moe = [moe_route(m, tokens), moe_experts(m, tokens),
           moe_shared(m, tokens)]
    for n, parts in ((m["n_lin"], lin), (m["n_full"], full),
                     (m["L"], moe)):
        flops += n * sum(f for f, _ in parts)
        nbytes += n * sum(b for _, b in parts)
    return flops, nbytes


def prefill_call_seconds(doc: dict, tokens: int, peaks: dict,
                         context: float = 0.0) -> float:
    """The least time the chip could take over one prefill call: the
    larger of its FLOP and its byte bound. ``context`` 0 counts no
    attention over earlier tokens (a lower bound, as it should be)."""
    flops, nbytes = prefill_call(doc, tokens, max(context, tokens / 2.0))
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
