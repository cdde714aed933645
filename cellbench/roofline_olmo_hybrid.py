"""Operations and bytes the ALGORITHM needs for the Olmo-Hybrid family
(``model_type: olmo_hybrid``: Gated DeltaNet layers with separate
q / k / v / gate projections and key and value head widths that differ,
plain full attention with as many key heads as query heads, a dense
SwiGLU in every layer), from a configuration file's keys. Beside
``roofline.py`` and for its reasons: kept with the benchmark so that no
later PR can move a roofline share by recounting, and counting the
LEAST the work has to do — weights at most once a call, a live slot's
state once in and once out, the contexts' own keys and values, LOGICAL
bytes (a float32 state row ``[30, 96, 192]`` counts 2,211,840 bytes,
whatever its tiled layout pads it to), a snapshot copy once read and
once written.

Per kernel (the named scopes of models/olmo_hybrid.py), for a call over
``tokens`` tokens, each function gives (floating-point operations,
bytes): ``gdn_proj``, ``gdn_conv``, ``gdn_chunk``, ``gdn_recurrent``,
``attn_full``, ``mlp``, and ``snapshot_copy`` for the engine's two copy
programs. The two metrics of the manifest add them up over the layers:
a decode step (``decode_step_bytes``: bandwidth-bound) and a prefill
call (``prefill_call_seconds``: the larger of its FLOP and its byte
bound, at PADDED tokens: the program runs the padding, and counting
real tokens against a time that includes it could read over 100).
"""

from __future__ import annotations

BF16, F32 = 2, 4


def dims(doc: dict) -> dict:
    """The shapes, by short names."""
    kinds = doc["layer_types"]
    L = doc["num_hidden_layers"]
    assert len(kinds) == L, (len(kinds), L)
    n_full = sum(1 for k in kinds if k == "full_attention")
    H, dk, dv = (doc["linear_num_value_heads"], doc["linear_key_head_dim"],
                 doc["linear_value_head_dim"])
    return {
        "D": doc["hidden_size"], "V": doc["vocab_size"], "L": L,
        "F": doc["intermediate_size"], "n_full": n_full, "n_lin": L - n_full,
        "Ha": doc["num_attention_heads"], "Hkv": doc["num_key_value_heads"],
        "hd": doc["hidden_size"] // doc["num_attention_heads"],
        "H": H, "dk": dk, "dv": dv, "kd": H * dk, "vd": H * dv,
        "conv": 2 * H * dk + H * dv, "K": doc["linear_conv_kernel_dim"],
    }


# -- parameters (elements) --------------------------------------------------
def gdn_proj_params(m: dict) -> int:
    """q, k, v, gate, b | a and the output projection of one DeltaNet
    layer."""
    return (m["D"] * (2 * m["kd"] + 2 * m["vd"]) + m["D"] * 2 * m["H"]
            + m["vd"] * m["D"])


def attn_params(m: dict) -> int:
    """q, k, v and o of one full-attention layer."""
    return (m["D"] * m["Ha"] * m["hd"] + 2 * m["D"] * m["Hkv"] * m["hd"]
            + m["Ha"] * m["hd"] * m["D"])


def mlp_params(m: dict) -> int:
    return 3 * m["D"] * m["F"]


def state_bytes_per_slot(m: dict) -> int:
    """One slot's float32 state and bfloat16 convolution tail, all
    DeltaNet layers: what a snapshot holds too."""
    return m["n_lin"] * (m["H"] * m["dk"] * m["dv"] * F32
                         + (m["K"] - 1) * m["conv"] * BF16)


def kv_bytes_per_token(m: dict) -> int:
    return m["n_full"] * 2 * m["Hkv"] * m["hd"] * BF16


def linear_layer_params(m: dict) -> int:
    """A DeltaNet layer whole: its projections, the convolution,
    ``A_log``, ``dt_bias``, the head norm, the two block norms and the
    MLP."""
    return (gdn_proj_params(m) + m["K"] * m["conv"] + 2 * m["H"] + m["dv"]
            + 2 * m["D"] + mlp_params(m))


def full_layer_params(m: dict) -> int:
    """A full-attention layer whole: projections, the two norms over
    the whole query and key projections, the two block norms, the
    MLP."""
    return (attn_params(m) + m["Ha"] * m["hd"] + m["Hkv"] * m["hd"]
            + 2 * m["D"] + mlp_params(m))


def param_count(m: dict) -> int:
    """Every parameter the replica holds: what
    ``expect.param_bytes_total`` is two bytes each of."""
    return (m["n_lin"] * linear_layer_params(m)
            + m["n_full"] * full_layer_params(m)
            + 2 * m["V"] * m["D"] + m["D"])


# -- one layer's kernels over `tokens` tokens: (FLOPs, bytes) ---------------
def gdn_proj(m: dict, tokens: float) -> tuple[float, float]:
    return 2.0 * tokens * gdn_proj_params(m), BF16 * gdn_proj_params(m)


def gdn_conv(m: dict, tokens: float) -> tuple[float, float]:
    return (2.0 * tokens * m["K"] * m["conv"],
            BF16 * (m["K"] * m["conv"] + 2 * (m["K"] - 1) * m["conv"]
                    + 2 * tokens * m["conv"]))


def gdn_recurrent(m: dict, slots: float) -> tuple[float, float]:
    """One token of ``slots`` live sequences: decay, Sᵀk, the rank-one
    write and Sᵀq are four passes over the state; it is read once and
    written once."""
    state = m["H"] * m["dk"] * m["dv"]
    return 7.0 * slots * state, 2.0 * slots * state * F32


def gdn_chunk(m: dict, tokens: float, block: int = 64
              ) -> tuple[float, float]:
    """The chunked (WY) form over ``tokens`` tokens of one sequence, in
    blocks of ``block``: per block and head, kkᵀ and qkᵀ (2·C²·dk each),
    the triangular inverse (C³/3), T·v (2·C²·dv) and T·k (2·C²·dk), the
    local product (2·C²·dv), and w·S, q·S and the state's update
    (2·C·dk·dv each). The state comes in once and goes out once."""
    C = block
    n = -(-int(tokens) // C)
    per = (4.0 * C * C * m["dk"] + C ** 3 / 3.0 + 2.0 * C * C * m["dv"]
           + 2.0 * C * C * m["dk"] + 2.0 * C * C * m["dv"]
           + 6.0 * C * m["dk"] * m["dv"])
    return (n * m["H"] * per,
            2.0 * m["H"] * m["dk"] * m["dv"] * F32
            + BF16 * tokens * (m["conv"] + m["vd"]))


def attn_full(m: dict, tokens: float, context: float
              ) -> tuple[float, float]:
    """Projections of ``tokens`` tokens that attend over ``context``
    keys each (on average), and those keys' and values' bytes."""
    scores = 4.0 * tokens * context * m["Ha"] * m["hd"]
    return (2.0 * tokens * attn_params(m) + scores,
            BF16 * attn_params(m)
            + 2.0 * context * m["Hkv"] * m["hd"] * BF16)


def mlp(m: dict, tokens: float) -> tuple[float, float]:
    return 2.0 * tokens * mlp_params(m), BF16 * mlp_params(m)


def snapshot_copy(m: dict, copies: float) -> tuple[float, float]:
    """``copies`` snapshot saves or restores: a slot's state read once
    and written once, no arithmetic."""
    return 0.0, 2.0 * copies * state_bytes_per_slot(m)


# -- the two programs -------------------------------------------------------
def decode_step_bytes(doc: dict, live_slots: float, kv_bytes_live: float
                      ) -> float:
    """Bytes one decode step has to move: every layer's matrices once,
    the live slots' state read and written with their convolution
    tails, the live contexts' keys and values, and the output head."""
    m = dims(doc)
    total = BF16 * m["D"] * m["V"] + kv_bytes_live
    total += 2.0 * live_slots * state_bytes_per_slot(m)
    total += m["n_lin"] * BF16 * (gdn_proj_params(m) + m["K"] * m["conv"])
    total += m["n_full"] * BF16 * attn_params(m)
    return total + m["L"] * mlp(m, 1)[1]


def prefill_call(doc: dict, tokens: float, context: float
                 ) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill call over ``tokens`` tokens of one
    sequence whose tokens attend over ``context`` keys on average."""
    m = dims(doc)
    flops = 2.0 * m["D"] * m["V"]  # the head, at the last position
    nbytes = float(BF16 * m["D"] * m["V"])
    lin = [gdn_proj(m, tokens), gdn_conv(m, tokens), gdn_chunk(m, tokens)]
    full = [attn_full(m, tokens, context)]
    for n, parts in ((m["n_lin"], lin), (m["n_full"], full),
                     (m["L"], [mlp(m, tokens)])):
        flops += n * sum(f for f, _ in parts)
        nbytes += n * sum(b for _, b in parts)
    return flops, nbytes


def prefill_call_seconds(doc: dict, tokens: float, peaks: dict,
                         context: float = 0.0) -> float:
    """The least time the chip could take over one prefill call: the
    larger of its FLOP and its byte bound. ``context`` 0 counts no
    attention over earlier tokens (a lower bound, as it should be)."""
    flops, nbytes = prefill_call(doc, tokens, max(context, tokens / 2.0))
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def snapshot_copy_seconds(doc: dict, copies: float, peaks: dict) -> float:
    return snapshot_copy(dims(doc), copies)[1] / peaks["hbm_bytes_per_s"]
