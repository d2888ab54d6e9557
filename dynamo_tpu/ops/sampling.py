"""Token sampling: greedy / temperature / top-k / top-p, fully vectorized.

Per-sequence sampling parameters are carried as arrays so one jitted step
serves a heterogeneous batch (mirrors the reference's per-request
sampling-option mapping, /root/reference/lib/llm/src/preprocessor.rs sampling
options → engine; here the engine is ours so the math lives here).

TPU-first design: no full-vocab sort (a 128k-row bitonic sort per token per
sequence dominated decode time).  Instead:

- greedy rows take ``argmax``;
- unconstrained temperature rows sample via the Gumbel-argmax trick, one
  O(V) pass;
- top-k / top-p rows work on a static top-``TOP_K_CAP`` slice from
  ``lax.top_k``.  Top-p mass is measured against the *full* softmax (one
  logsumexp pass) conditioned on the slice, so truncation is exact whenever
  the requested mass fits inside the slice; a wider-than-slice nucleus
  (high-entropy row) truncates to the slice, never leaking the tail.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.export import register_namedtuple_serialization

# static width of the candidate slice for top-k/top-p rows; requests with
# top_k > TOP_K_CAP are clamped (the standard engine-side cap)
TOP_K_CAP = 64


# (registered: a step program's operand, whose tree the program store
# writes with the program's lowered module, `jax.export`)
@partial(register_namedtuple_serialization,
         serialized_name="dynamo_tpu.SamplingParams")
class SamplingParams(NamedTuple):
    """Per-sequence sampling state, shape [B] each."""

    temperature: jax.Array  # 0.0 → greedy
    top_k: jax.Array  # 0 → disabled
    top_p: jax.Array  # 1.0 → disabled
    frequency_penalty: jax.Array  # 0.0 → disabled
    presence_penalty: jax.Array  # 0.0 → disabled

    @staticmethod
    def make(temperature, top_k, top_p,
             frequency_penalty=None, presence_penalty=None):
        n = len(temperature)
        return SamplingParams(
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32),
            jnp.asarray(top_p, jnp.float32),
            jnp.asarray(frequency_penalty
                        if frequency_penalty is not None else [0.0] * n,
                        jnp.float32),
            jnp.asarray(presence_penalty
                        if presence_penalty is not None else [0.0] * n,
                        jnp.float32),
        )


@jax.named_scope("sample")
def sample_tokens_maybe_greedy(logits, params, seeds, counters,
                               greedy: bool = False):
    """`sample_tokens`, or a STATICALLY greedy argmax when the caller
    knows every row is temperature-0.  The runtime all-greedy lax.cond
    below still costs ~0.9ms/step at a 128k vocab on v5e (XLA keeps the
    sampling branch's top_k in the critical path) — the engine compiles
    a separate greedy step variant instead (the benchmark/eval hot
    path)."""
    if greedy:
        return jnp.argmax(logits.astype(jnp.float32), axis=-1)
    return sample_tokens(logits, params, seeds, counters)


def sample_tokens(
    logits: jax.Array,  # [B, V] float
    params: SamplingParams,
    seeds: jax.Array,  # [B] uint32 — per-request sampling seed
    counters: jax.Array,  # [B] int32 — tokens generated so far (stream position)
) -> jax.Array:
    """Sample one token per row. Greedy rows (temperature==0) take argmax.

    Each row draws from its own PRNG stream keyed by (seed, counter), so a
    request with an explicit seed is reproducible regardless of how it was
    batched with other requests.
    """
    B, V = logits.shape
    K = min(TOP_K_CAP, V)
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    # all-greedy batches (common: benchmark + temperature-0 workloads) skip
    # the sampling math entirely at runtime
    return jax.lax.cond(
        jnp.all(params.temperature <= 0.0),
        lambda: greedy,
        lambda: _sample_nongreedy(logits, greedy, params, seeds, counters, K),
    )


def _sample_nongreedy(logits, greedy, params, seeds, counters, K):
    B, V = logits.shape
    temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    scaled = logits / temp

    keys = jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c)
    )(seeds, counters)
    k_full, k_sub = jnp.moveaxis(jax.vmap(jax.random.split)(keys), 1, 0)

    # unconstrained temperature sampling: Gumbel-argmax over the full vocab
    g_full = jax.vmap(lambda k: jax.random.gumbel(k, (V,), jnp.float32))(k_full)
    full_sample = jnp.argmax(scaled + g_full, axis=-1)

    # truncated rows: static top-K slice (sorted descending by lax.top_k)
    vals, idx = jax.lax.top_k(scaled, K)  # [B, K]
    j = jnp.arange(K)[None, :]
    k_eff = jnp.where(params.top_k > 0, jnp.minimum(params.top_k, K), K)
    topk_keep = j < k_eff[:, None]
    # exact mass under the full softmax (one logsumexp over V)
    lse = jax.nn.logsumexp(scaled, axis=-1, keepdims=True)
    probs = jnp.exp(vals - lse)  # [B, K] true probabilities
    cum = jnp.cumsum(probs, axis=-1)
    # top-p threshold on mass *conditioned on the slice* (p · slice mass):
    # exact whenever the nucleus fits inside the slice (slice mass ≈ 1 for
    # peaked LLM rows); a wider-than-slice nucleus truncates to the slice
    # rather than leaking to the full vocab.  Keep positions whose
    # *previous* cumulative mass is below the threshold; position 0 always
    # kept so top_p <= 0 degrades to greedy rather than masking all.
    topp_keep = (cum - probs) < params.top_p[:, None] * cum[:, -1:]
    keep = (topk_keep & topp_keep).at[:, 0].set(True)
    masked = jnp.where(keep, vals, -jnp.inf)
    g_sub = jax.vmap(lambda k: jax.random.gumbel(k, (K,), jnp.float32))(k_sub)
    sub_pick = jnp.argmax(masked + g_sub, axis=-1)  # [B]
    sub_sample = jnp.take_along_axis(idx, sub_pick[:, None], axis=1)[:, 0]

    truncated = (params.top_k > 0) | (params.top_p < 1.0)
    sampled = jnp.where(truncated, sub_sample, full_sample)
    return jnp.where(params.temperature <= 0.0, greedy, sampled)


@jax.named_scope("sample")
def sample_tokens_block(
    logits: jax.Array,  # [B, S, V] — one distribution per chunk position
    params: SamplingParams,  # [B] each
    seeds: jax.Array,  # [B]
    counters: jax.Array,  # [B] — stream position of the FIRST chunk slot
    greedy: bool = False,
):
    """Sample one token per POSITION of a logits block: position j of row
    b draws from the row's PRNG stream at counter ``counters[b] + j`` —
    exactly the tokens S sequential decode steps would sample, computed
    in one fused pass (the verify tail of self-speculative decoding;
    this counter alignment is what makes speculative decode
    token-identical to plain decode even for seeded sampling).
    Returns (tokens [B, S] int32, logprobs [B, S] float32)."""
    B, S, V = logits.shape
    flat = logits.reshape(B * S, V)
    if greedy:
        out = jnp.argmax(flat.astype(jnp.float32), axis=-1)
    else:
        flat_params = jax.tree.map(lambda a: jnp.repeat(a, S, axis=0), params)
        out = sample_tokens(
            flat, flat_params, jnp.repeat(seeds, S, axis=0),
            (counters[:, None] + jnp.arange(S)[None, :]).reshape(-1),
        )
    logp = compute_logprobs(flat, out)
    return out.reshape(B, S), logp.reshape(B, S)


@jax.named_scope("sample")
def speculative_accept(
    sampled: jax.Array,  # [B, S] — per-position verify samples
    fed: jax.Array,  # [B, S] — [last accepted token | S-1 draft tokens]
) -> jax.Array:
    """Length of the accepted draft prefix per row ([B] int32): draft j
    (``fed[:, j+1]``) is accepted iff every earlier draft matched AND the
    model's own sample at its position (``sampled[:, j]``) equals it.

    For a DETERMINISTIC drafter (n-gram lookup proposes a point mass)
    this token-matching rule IS Leviathan-style rejection sampling:
    accept probability = p(draft) either way, and on rejection the
    emitted token ``sampled[:, j]`` is already distributed as the target
    conditional with the draft token's mass excluded — so temperature>0
    verification preserves the sampling distribution exactly."""
    match = (sampled[:, :-1] == fed[:, 1:]).astype(jnp.int32)
    return jnp.cumprod(match, axis=1).sum(axis=1)


@jax.named_scope("sample")
def compute_logprobs(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Log-probability of `tokens` [B] under `logits` [B, V]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, tokens[:, None], axis=1)[:, 0]


@jax.named_scope("sample")
def apply_penalties(
    logits: jax.Array,  # [B, V]
    counts: jax.Array,  # [B, V] float — output-token occurrence counts
    frequency_penalty: jax.Array,  # [B]
    presence_penalty: jax.Array,  # [B]
) -> jax.Array:
    """OpenAI frequency/presence penalties over generated tokens (vLLM
    semantics: prompt tokens are not penalized; the engine builds `counts`
    from output tokens only).  Applied before greedy argmax and sampling
    alike (reference maps these into engine sampling options,
    preprocessor.rs:102)."""
    logits = logits.astype(jnp.float32)
    return (
        logits
        - frequency_penalty[:, None] * counts
        - presence_penalty[:, None] * (counts > 0).astype(jnp.float32)
    )


@jax.named_scope("sample")
def top_logprobs(logits: jax.Array, k: int):
    """Top-k (ids, logprobs) per row for OpenAI `top_logprobs` responses."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(logp, k)  # [B, k] each
    return idx.astype(jnp.int32), vals
