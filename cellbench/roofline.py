"""Operations and bytes the ALGORITHM needs, from a configuration
file's shapes, and the peaks they are held against. Kept with the
benchmark so that no later PR can move a roofline share by recounting.

A share can be wrong in two ways that make it too high: counting work
the algorithm does not need (padding, capacity slots, a gather window
wider than the contexts) or leaving device time out. So the counts here
are the LEAST the step has to do — weights read once per step, the
contexts' own keys and values — and the time is everything the device
spent inside the group's programs. (Decode only: prefill's FLOPs had a
count here too, and no sound time to hold it against; PERF.md, section 7.)
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

#: bytes per weight element as served
WEIGHT_BYTES = {"": 2.0, "int8": 1.0, "int4": 0.5}


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``. A kind that is not in
    the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"cellbench/peaks.json (has: {sorted(table)})")
    return table[device_kind]


def _dims(doc: dict) -> dict:
    hd = doc.get("head_dim") or doc["hidden_size"] // doc["num_attention_heads"]
    return {
        "d": doc["hidden_size"], "L": doc["num_hidden_layers"],
        "q": doc["num_attention_heads"] * hd,
        "kv": doc["num_key_value_heads"] * hd,
        "f": doc["intermediate_size"], "v": doc["vocab_size"],
        "E": doc.get("num_local_experts", 1),
    }


def attn_matrix_params(doc: dict) -> int:
    """Elements of one layer's q, k, v and o projections."""
    m = _dims(doc)
    return m["d"] * (m["q"] + 2 * m["kv"]) + m["q"] * m["d"]


def mlp_matrix_params(doc: dict) -> int:
    """Elements of ONE feed-forward block (one expert of an MoE layer):
    gate, up and down."""
    m = _dims(doc)
    return 3 * m["d"] * m["f"]


def router_params(doc: dict) -> int:
    m = _dims(doc)
    return m["d"] * m["E"] if m["E"] > 1 else 0


def kv_bytes_per_token(doc: dict, kv_elem_bytes: float = 2.0) -> float:
    m = _dims(doc)
    return 2 * m["kv"] * m["L"] * kv_elem_bytes


def decode_weight_bytes(doc: dict, quantize: str) -> float:
    """Weight bytes one decode step has to read: every layer's
    attention and feed-forward matrices (ALL experts: a batch of a few
    tokens with top-k routing touches every expert) and the output head
    — not the embedding table, of which a step gathers one row a
    sequence."""
    m = _dims(doc)
    per_layer = (attn_matrix_params(doc) + m["E"] * mlp_matrix_params(doc)
                 + router_params(doc))
    return WEIGHT_BYTES[quantize] * (m["L"] * per_layer + m["d"] * m["v"])


def decode_step_bytes(doc: dict, quantize: str, kv_bytes_resident: float
                      ) -> float:
    """Bytes one decode step has to move: the weights once and the keys
    and values of the contexts it attends over (the pool bytes the live
    sequences hold, which is what /state reports)."""
    return decode_weight_bytes(doc, quantize) + kv_bytes_resident


def share_pct(least_seconds: float, measured_seconds: float) -> float | None:
    """Roofline share in percent: the least time the chip could take
    over the time it took. No clamp: a share over 100 means the counts
    or the time are wrong, and must show."""
    if measured_seconds <= 0:
        return None
    return 100.0 * least_seconds / measured_seconds
