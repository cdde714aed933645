"""On-device batched sampling.

Temperature / top-k / top-p composed in one jit-able function over the
whole decode batch — sampling never leaves the device; only the sampled
token ids (a [B] int32) cross to the host per step, keeping the
host↔device traffic per decode step to a few hundred bytes.

Per-slot sampling parameters are carried as arrays so one compiled program
serves any mix of greedy/temperature requests in the same batch.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    seed: int = 0
    frequency_penalty: float = 0.0
    presence_penalty: float = 0.0
    # token id → additive logit bias (OpenAI logit_bias)
    logit_bias: tuple[tuple[int, float], ...] = ()

    @staticmethod
    def from_request(body: dict) -> "SamplingParams":
        """JSON null (SDKs serialize unset optionals as null) falls back
        to the OpenAI defaults; explicit 0 temperature means greedy."""

        def pick(key: str, default: float) -> float:
            v = body.get(key)
            return default if v is None else float(v)

        bias = body.get("logit_bias") or {}
        return SamplingParams(
            temperature=pick("temperature", 1.0),
            top_p=pick("top_p", 1.0),
            top_k=int(pick("top_k", 0)),
            seed=int(pick("seed", 0)),
            frequency_penalty=pick("frequency_penalty", 0.0),
            presence_penalty=pick("presence_penalty", 0.0),
            logit_bias=tuple(
                (int(k), float(v)) for k, v in bias.items()
            ),
        )


def apply_penalties(
    logits: jax.Array,  # [B, V] float32
    counts: jax.Array,  # [B, V] — occurrences of each token so far
    freq_penalty: jax.Array,  # [B]
    pres_penalty: jax.Array,  # [B]
    bias: jax.Array | None = None,  # [B, V] additive logit bias
) -> jax.Array:
    """OpenAI-semantics penalties: logit -= freq·count + pres·(count>0),
    plus per-request logit_bias."""
    countf = counts.astype(jnp.float32)
    out = (
        logits
        - freq_penalty[:, None] * countf
        - pres_penalty[:, None] * (countf > 0)
    )
    if bias is not None:
        out = out + bias
    return out


def spec_accept(
    drafts: jax.Array,  # [B, D] int32 proposed tokens (-1 = no proposal)
    sampled: jax.Array,  # [B, D+1] int32 model samples per position
    active: jax.Array,  # [B] bool slot occupied + below its limit
    budget: jax.Array,  # [B] int32 tokens the slot may still emit
) -> tuple[jax.Array, jax.Array]:
    """Vectorized acceptance masks for speculative verification.

    Longest-matching-prefix rule per slot: ``n_acc`` drafts whose
    cumulative match with the model's own samples is unbroken are
    accepted, and the model's sample at the position after them rides
    along — so every step emits ``n_acc + 1`` model-exact tokens,
    clipped to the slot's remaining ``budget`` (the page-safety fence).
    Returns (n_emit [B] int32, emit_mask [B, D+1] bool): emit_mask[b, d]
    marks sampled[b, d] as model-exact output; everything past it is
    conditioned on a rejected draft and must be discarded."""
    D = drafts.shape[1]
    match = (drafts == sampled[:, :D]).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    n_emit = jnp.where(
        active, jnp.minimum(n_acc + 1, jnp.maximum(budget, 0)), 0
    )
    d_idx = jnp.arange(D + 1, dtype=jnp.int32)[None, :]
    return n_emit, d_idx < n_emit[:, None]


@jax.named_scope("sample")
def sample(
    logits: jax.Array,  # [B, V] float32
    keys: jax.Array,  # [B, 2] uint32 (jax PRNG keys, one per slot)
    temperature: jax.Array,  # [B] float32; 0 = greedy
    top_p: jax.Array,  # [B] float32
    top_k: jax.Array,  # [B] int32; 0 = off
    live: jax.Array | None = None,  # [B] bool; None = every row
) -> jax.Array:
    """Returns sampled token ids [B] int32.

    The vocabulary-wide sort behind the top-k / top-p thresholds runs
    only when a ``live`` row truncates: the predicate is the row-wise
    complement of the two tests the masks below are ORed with, so a
    batch that skips the sort has both masks all true and samples the
    same tokens bit for bit. It reads only ``top_k``, ``top_p`` and
    ``live`` — never ``logits`` or ``keys`` — so a ``vmap`` over those
    two (the speculative verify body) leaves it one conditional. A row
    that is not ``live`` (an empty slot, a row at its limit) never
    forces the sort; its sample is junk the caller discards."""
    B, V = logits.shape
    truncates = (top_k > 0) | ~(top_p >= 1.0)
    if live is not None:
        truncates = truncates & live

    def thresholds(logits):
        # top-k: the k-th highest logit (k==0 → masked off below)
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]  # descending
        k_idx = jnp.clip(top_k - 1, 0, V - 1)
        kth = jnp.take_along_axis(sorted_logits, k_idx[:, None], axis=-1)
        # top-p (nucleus) over the sorted distribution. OpenAI/vLLM
        # semantics: temperature scaling precedes the nucleus cutoff, so
        # membership is computed on the *scaled* distribution (sort order
        # is invariant under the positive scale, so one sort serves both
        # thresholds).
        inv_t = 1.0 / jnp.maximum(temperature[:, None], 1e-6)
        probs_sorted = jax.nn.softmax(sorted_logits * inv_t, axis=-1)
        cum = jnp.cumsum(probs_sorted, axis=-1)
        # keep tokens whose cumulative mass *before* them is < top_p
        cutoff_mass = cum - probs_sorted
        keep_sorted = cutoff_mass < top_p[:, None]
        # threshold logit: smallest kept logit in sorted order
        last_kept = jnp.sum(keep_sorted.astype(jnp.int32), axis=-1) - 1
        thresh = jnp.take_along_axis(
            sorted_logits, jnp.clip(last_kept, 0, V - 1)[:, None], axis=-1
        )
        return kth, thresh

    def no_thresholds(logits):
        none = jnp.full((B, 1), -jnp.inf, logits.dtype)
        return none, none

    kth, thresh = jax.lax.cond(
        jnp.any(truncates), thresholds, no_thresholds, logits)
    keep_k = (top_k[:, None] <= 0) | (logits >= kth)
    keep_p = (top_p[:, None] >= 1.0) | (logits >= thresh)

    masked = jnp.where(keep_k & keep_p, logits, -jnp.inf)
    scaled = masked / jnp.maximum(temperature[:, None], 1e-6)
    # per-slot categorical with per-slot keys
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)
