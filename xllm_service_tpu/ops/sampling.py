"""Token sampling: greedy, temperature, top-k, top-p, penalties — batched
and jit-safe.

Per-sequence sampling parameters arrive as dense arrays (one scalar per batch
slot) so a single compiled program serves every request mix; there is no
per-request recompilation. ``temperature == 0`` selects greedy via
``jnp.where``, not Python control flow.

OpenAI contract coverage (reference proto carries these end to end,
xllm/chat.proto:1-192 — the rebuild must not silently drop them):
- per-request ``seed``: each row derives its own PRNG key inside the
  compiled step — ``fold_in(PRNGKey(seed), position)`` — so a seeded
  request's token stream is deterministic regardless of batch composition;
- ``presence_penalty`` / ``frequency_penalty``: applied against a [B, V]
  output-token count tensor that lives on device (engine carries it only
  while some active slot uses penalties);
- ``logprobs`` / ``top_logprobs``: chosen-token logprob always computed;
  top-k alternatives computed in-step when the engine enables them.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


class SamplingTensors(NamedTuple):
    """Per-slot sampling state, shape [B] each."""

    temperature: jnp.ndarray            # float32; 0.0 → greedy
    top_p: jnp.ndarray                  # float32 in (0, 1]
    top_k: jnp.ndarray                  # int32; 0 → disabled
    # Defaults (None) mean "feature off for the whole batch" — direct
    # construction stays terse; ``unpack`` always fills them in.
    seed: Optional[jnp.ndarray] = None        # int32; -1 → unseeded
    presence: Optional[jnp.ndarray] = None    # float32; 0.0 → off
    frequency: Optional[jnp.ndarray] = None   # float32; 0.0 → off

    # Packed-transfer form: six per-slot vectors ride host->device as TWO
    # arrays (float [B,4], int [B,2]) instead of six — each separate
    # upload pays the backend's fixed dispatch cost, so the hot engine
    # paths ship the packed pair and
    # reconstruct the tuple *inside* the jitted step via ``unpack``.
    @staticmethod
    def pack_batch(params_list):
        import numpy as np
        f32 = np.empty((len(params_list), 4), np.float32)
        i32 = np.empty((len(params_list), 2), np.int32)
        for i, p in enumerate(params_list):
            f32[i, 0] = p.temperature
            f32[i, 1] = p.top_p
            f32[i, 2] = p.presence_penalty
            f32[i, 3] = p.frequency_penalty
            i32[i, 0] = p.top_k
            i32[i, 1] = -1 if p.seed is None else int(p.seed)
        return f32, i32

    @classmethod
    def unpack(cls, f32: jnp.ndarray, i32: jnp.ndarray) -> "SamplingTensors":
        return cls(temperature=f32[:, 0], top_p=f32[:, 1],
                   presence=f32[:, 2], frequency=f32[:, 3],
                   top_k=i32[:, 0], seed=i32[:, 1])

def greedy(logits: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def apply_penalties(logits: jnp.ndarray, counts: jnp.ndarray,
                    tensors: SamplingTensors) -> jnp.ndarray:
    """OpenAI presence/frequency penalties over output-token ``counts``
    [B, V] (vLLM semantics: generated tokens only, prompt excluded)."""
    logits = logits.astype(jnp.float32)
    return logits \
        - tensors.frequency[:, None] * counts.astype(jnp.float32) \
        - tensors.presence[:, None] * (counts > 0).astype(jnp.float32)


def update_counts(counts: jnp.ndarray, tokens: jnp.ndarray,
                  active: jnp.ndarray) -> jnp.ndarray:
    """Add this step's sampled ``tokens`` [B] to the output-token histogram
    (inactive slots unchanged)."""
    B = tokens.shape[0]
    return counts.at[jnp.arange(B), tokens].add(
        active.astype(counts.dtype))


def _apply_top_k_top_p(logits: jnp.ndarray, top_k: jnp.ndarray,
                       top_p: jnp.ndarray) -> jnp.ndarray:
    """Joint top-k + nucleus filtering from ONE descending sort of the
    logits (sorts over a 152k vocab are the dominant sampling-filter cost;
    softmax of the already-sorted values is monotone-equivalent to softmax of
    the originals, so both thresholds fall out of the same sorted array).

    top_k == 0 disables top-k; the nucleus set always keeps the top token.
    """
    vocab = logits.shape[-1]
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]          # desc
    # Top-k threshold: the kth largest logit.
    k = jnp.where(top_k > 0, top_k, vocab)
    kth = jnp.take_along_axis(
        sorted_logits, jnp.clip(k[:, None] - 1, 0, vocab - 1), axis=-1)
    # Nucleus: keep ranks whose *exclusive* cumulative mass is below top_p,
    # then convert the boundary rank back to a logit threshold (softmax is
    # monotone in logit, so prob-space and logit-space cuts are identical).
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(sorted_probs, axis=-1)
    num_keep = jnp.sum(cumulative - sorted_probs < top_p[:, None], axis=-1)
    nucleus_kth = jnp.take_along_axis(
        sorted_logits, jnp.clip(num_keep[:, None] - 1, 0, vocab - 1), axis=-1)
    return jnp.where(logits >= jnp.maximum(kth, nucleus_kth), logits,
                     _NEG_INF)


def _row_keys(tensors: SamplingTensors, key: jax.Array,
              positions: jnp.ndarray) -> jnp.ndarray:
    """Per-row PRNG keys [B, 2]: seeded rows use
    ``fold_in(PRNGKey(seed), position)`` (deterministic across batch
    compositions and restarts); unseeded rows split the shared step key."""
    B = positions.shape[0]
    seeded = jax.vmap(
        lambda s, p: jax.random.fold_in(
            jax.random.PRNGKey(jnp.maximum(s, 0)), p))(
        tensors.seed, positions)
    unseeded = jax.random.split(key, B)
    return jnp.where((tensors.seed >= 0)[:, None], seeded, unseeded)


def sample_tokens(logits: jnp.ndarray, tensors: SamplingTensors,
                  key: jax.Array, positions: Optional[jnp.ndarray] = None,
                  counts: Optional[jnp.ndarray] = None,
                  bias_ids: Optional[jnp.ndarray] = None,
                  bias_vals: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Sample one token per row of ``logits`` [B, V] → int32 [B].

    ``positions`` [B] (generation position per row) drives per-request
    seeded determinism; None falls back to the shared key for every row.
    ``counts`` [B, V] enables presence/frequency penalties.
    ``bias_ids``/``bias_vals`` [B, K] are the OpenAI logit_bias surface
    in padded sparse form (pad entries (0, +0.0) are additive no-ops);
    it applies to greedy too — reported logprobs stay those of the
    model's true distribution.
    """
    logits = logits.astype(jnp.float32)
    if bias_ids is not None:
        B = logits.shape[0]
        logits = logits.at[jnp.arange(B)[:, None], bias_ids].add(
            bias_vals)
    if counts is not None:
        logits = apply_penalties(logits, counts, tensors)
    greedy_tok = greedy(logits)
    temp = jnp.maximum(tensors.temperature, 1e-6)[:, None]
    scaled = logits / temp
    # The joint filter needs a full-vocab sort (~2 ms/step on a 128k vocab
    # — measured 20% of a 1B model's decode step). Greedy rows take the
    # argmax below and unfiltered rows keep every logit, so the sort only
    # runs when some sampled row actually set top_k/top_p: lax.cond
    # executes ONE branch at runtime inside jit.
    needs_filter = jnp.any(
        (tensors.temperature > 0.0)
        & ((tensors.top_k > 0) | (tensors.top_p < 1.0)))
    scaled = jax.lax.cond(
        needs_filter,
        lambda s: _apply_top_k_top_p(s, tensors.top_k, tensors.top_p),
        lambda s: s, scaled)
    if positions is None or tensors.seed is None:
        sampled = jax.random.categorical(key, scaled, axis=-1).astype(
            jnp.int32)
    else:
        keys = _row_keys(tensors, key, positions)
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row))(
            keys, scaled).astype(jnp.int32)
    return jnp.where(tensors.temperature <= 0.0, greedy_tok, sampled)


def compute_logprobs(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Log-prob of each chosen token: [B, V], [B] → [B] float32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]


def compute_top_logprobs(logits: jnp.ndarray, k: int
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-``k`` alternative logprobs of the model distribution:
    [B, V] → (ids [B, k] int32, logprobs [B, k] float32)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    top_lps, top_ids = jax.lax.top_k(logp, k)
    return top_ids.astype(jnp.int32), top_lps
