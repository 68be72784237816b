"""Token sampling, fully vectorized in-graph (no host round-trip of
logits): temperature, top-k, top-p and greedy, per-slot parameters so one
decode batch mixes sampling configs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def apply_penalties(logits: jnp.ndarray, counts: jnp.ndarray,
                    prompt_mask: jnp.ndarray,
                    presence: jnp.ndarray, frequency: jnp.ndarray,
                    repetition: jnp.ndarray) -> jnp.ndarray:
    """Sampling penalties, vectorized per row.

    OpenAI semantics for presence/frequency (over tokens *generated*
    so far) and vLLM/HF semantics for repetition (over prompt +
    generated: positive logits divided by r, negative multiplied).

    Args:
      logits:      [B, vocab] f32
      counts:      [B, vocab] int32 occurrences in the OUTPUT so far
      prompt_mask: [B, vocab] bool, True where the token appears in
                   the prompt
      presence/frequency: [B] f32 (0 disables)
      repetition:  [B] f32 (1 disables)

    Returns penalized [B, vocab] logits.
    """
    countsf = counts.astype(logits.dtype)
    seen_out = countsf > 0
    # Repetition applies FIRST, on the raw logits (vLLM/HF order);
    # presence/frequency subtract from the result.
    seen_any = seen_out | prompt_mask
    rep = repetition[:, None]
    repeated = jnp.where(logits > 0, logits / rep, logits * rep)
    logits = jnp.where(seen_any, repeated, logits)
    logits = logits - presence[:, None] * seen_out.astype(logits.dtype)
    return logits - frequency[:, None] * countsf


def token_logprobs(logits: jnp.ndarray, sampled: jnp.ndarray,
                   k: int):
    """Logprob of each sampled token + the top-k alternatives.

    Computed from the UNMODIFIED model distribution (before
    temperature/penalties), the OpenAI ``logprobs`` contract.

    Args:
      logits:  [B, vocab] f32 raw logits
      sampled: [B] int32 sampled token ids
      k:       static top-k width (>= 1)

    Returns (sampled_logprob [B], top_ids [B, k], top_logprobs [B, k]).
    """
    lp = jax.nn.log_softmax(logits, axis=-1)
    sampled_lp = jnp.take_along_axis(
        lp, sampled[:, None].astype(jnp.int32), axis=1)[:, 0]
    top_lp, top_ids = jax.lax.top_k(lp, k)
    return sampled_lp, top_ids.astype(jnp.int32), top_lp


def _mask_top_k_top_p(scaled: jnp.ndarray, top_p: jnp.ndarray,
                      top_k: jnp.ndarray) -> jnp.ndarray:
    """NEG_INF-mask every logit outside its row's top-k/top-p set.

    Shared by ``sample_tokens`` and ``spec_verify`` so the sampling
    and speculative-verification distributions cannot drift.

    Args:
      scaled: [B, vocab] temperature-scaled logits
      top_p:  [B] (1.0 => disabled)
      top_k:  [B] int32 (0 => disabled)
    """
    b, vocab = scaled.shape
    # Rank of each logit within its row (0 = largest).
    sort_idx = jnp.argsort(-scaled, axis=-1)
    sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)

    # top-k: keep ranks < k (k==0 disables).
    ranks = jnp.arange(vocab)[None, :]
    k = jnp.where(top_k > 0, top_k, vocab)
    topk_mask = ranks < k[:, None]

    # top-p: keep the smallest prefix with cumulative prob >=
    # top_p, always including the most likely token.
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumprobs = jnp.cumsum(sorted_probs, axis=-1)
    topp_mask = (cumprobs - sorted_probs) < top_p[:, None]

    keep_sorted = topk_mask & topp_mask
    masked_sorted = jnp.where(keep_sorted, sorted_logits, NEG_INF)
    # Scatter the mask back to vocab order.
    return jnp.zeros_like(scaled).at[
        jnp.arange(b)[:, None], sort_idx
    ].set(masked_sorted)


def sample_tokens(logits: jnp.ndarray, temperature: jnp.ndarray,
                  top_p: jnp.ndarray, top_k: jnp.ndarray,
                  key: jax.Array,
                  seeds: "jnp.ndarray | None" = None,
                  emitted: "jnp.ndarray | None" = None,
                  seed_mask: "jnp.ndarray | None" = None) -> jnp.ndarray:
    """Sample one token per row.

    Args:
      logits:      [B, vocab] float32
      temperature: [B] (0 => greedy)
      top_p:       [B] (1.0 => disabled)
      top_k:       [B] int32 (0 => disabled)
      key:         PRNG key (the engine's stream; used for unseeded rows)
      seeds:       optional [B] int32 per-row request seeds, carrying
                   the FULL 32-bit user seed (two's-complement
                   reinterpretation — no folding, so distinct user
                   seeds never collide). A seeded row's randomness
                   derives ONLY from (seed, emitted-token index), so
                   identical seeded requests reproduce identical
                   samples regardless of batch composition or engine
                   history.
      emitted:     [B] int32 tokens generated so far per row (required
                   with ``seeds``)
      seed_mask:   [B] bool — True where the row is seeded. Required
                   with ``seeds``: the seed value itself cannot gate
                   seededness without surrendering a bit of seed space.

    Returns [B] int32 token ids.
    """
    b, vocab = logits.shape
    greedy_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    safe_temp = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_temp[:, None]

    def categorical(masked):
        if seeds is None:
            return jax.random.categorical(key, masked, axis=-1)
        # Per-row keys (legacy uint32[2] key form, what the engine's
        # PRNGKey stream uses): unseeded rows fold the row index into
        # the engine key; seeded rows rebuild their key from
        # (seed, emitted index) only.
        if seed_mask is None:
            # Seeds carry full 32-bit values: the sign bit is seed
            # payload, NOT an unseeded marker, so there is no valid
            # way to gate without the mask (a >= 0 fallback would
            # silently drop seeding for half the seed space).
            raise ValueError(
                "sample_tokens: seeds requires seed_mask")
        row_keys = jax.vmap(
            lambda i: jax.random.fold_in(key, i))(jnp.arange(b))
        seeded_keys = jax.vmap(
            lambda s, e: jax.random.fold_in(
                jax.random.PRNGKey(s.astype(jnp.uint32)), e)
        )(seeds, emitted)
        keys = jnp.where(seed_mask[:, None], seeded_keys, row_keys)
        return jax.vmap(jax.random.categorical)(keys, masked)

    def masked_sample():
        return categorical(_mask_top_k_top_p(scaled, top_p, top_k))

    def plain_sample():
        # No top-k/top-p anywhere in the batch: skip the vocab sort.
        return categorical(scaled)

    def sample_path():
        needs_mask = jnp.any((top_k > 0) | (top_p < 1.0))
        return jax.lax.cond(
            needs_mask, masked_sample, plain_sample
        ).astype(jnp.int32)

    # Runtime fast path: an all-greedy batch (the common serving case
    # at temperature 0) never executes the sort/softmax at all.
    any_stochastic = jnp.any(temperature > 0)
    sampled = jax.lax.cond(
        any_stochastic, sample_path, lambda: greedy_tokens
    )
    return jnp.where(temperature > 0, sampled, greedy_tokens).astype(
        jnp.int32
    )


def sampling_probs(logits: jnp.ndarray, temperature: jnp.ndarray,
                   top_p: jnp.ndarray, top_k: jnp.ndarray) -> jnp.ndarray:
    """A stochastic row's FULL sampling distribution (temperature, then
    top-k/top-p by ``sample_tokens``' mask): ``[B, vocab]``
    probabilities. A greedy row (temperature 0) gets the softmax of its
    raw logits, which nothing reads. What a proposer that samples
    hands ``spec_verify`` as its ``draft_probs``, and what
    ``spec_verify`` measures the target by. The vocabulary is sorted
    only where some row has a top-k or a top-p."""
    safe_temp = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_temp[:, None]
    needs_mask = jnp.any((top_k > 0) | (top_p < 1.0))
    return jax.nn.softmax(jax.lax.cond(
        needs_mask, lambda: _mask_top_k_top_p(scaled, top_p, top_k),
        lambda: scaled), axis=-1)


def _verify_proposal(logits, drafts, in_draft, draft_probs, temperature,
                     top_p, top_k, key, accept_greedy, greedy_final):
    """``spec_verify``'s stochastic rows for a proposal that is a
    distribution ``q`` (``draft_probs [B, S-1, vocab]``): draft j is
    accepted with ``min(1, p_j(d_j) / q_j(d_j))``; the one replacement
    a row needs, at its first rejected offset ``a`` or at the bonus
    offset, is drawn from ``norm(max(0, p_a - q_a))`` (``q`` is zero at
    the bonus offset and where a row offered no draft, so there it is
    ``p_a`` itself). One draw a row, at offset ``a`` alone."""
    b, s, vocab = logits.shape
    stochastic = temperature > 0
    dsafe = jnp.clip(drafts, 0)
    probs = sampling_probs(
        logits.reshape(b * s, vocab), jnp.repeat(temperature, s),
        jnp.repeat(top_p, s), jnp.repeat(top_k, s)).reshape(b, s, vocab)
    q = jnp.where(in_draft[..., None], draft_probs, 0.0)
    p_draft = jnp.take_along_axis(
        probs[:, :-1], dsafe[..., None], axis=-1)[..., 0]
    q_draft = jnp.take_along_axis(q, dsafe[..., None], axis=-1)[..., 0]
    key_u, key_r = jax.random.split(key)
    u = jax.random.uniform(key_u, (b, s - 1))
    accept = jnp.where(stochastic[:, None], u * q_draft < p_draft,
                       accept_greedy) & in_draft
    a = jnp.cumprod(accept.astype(jnp.int32), axis=-1).sum(axis=-1)
    at = a[:, None, None]
    p_a = jnp.take_along_axis(probs, at, axis=1)[:, 0]
    q_a = jnp.take_along_axis(
        jnp.pad(q, ((0, 0), (0, 1), (0, 0))), at, axis=1)[:, 0]
    residual = jnp.maximum(p_a - q_a, 0.0)
    resampled = jax.random.categorical(
        key_r, jnp.where(residual > 0, jnp.log(residual), NEG_INF),
        axis=-1).astype(jnp.int32)
    final_a = jnp.where(
        stochastic, resampled,
        jnp.take_along_axis(greedy_final, a[:, None], axis=1)[:, 0])
    return accept, jnp.broadcast_to(final_a[:, None], (b, s))


def spec_verify(logits: jnp.ndarray, drafts: jnp.ndarray,
                draft_lens: jnp.ndarray, temperature: jnp.ndarray,
                top_p: jnp.ndarray, top_k: jnp.ndarray,
                key: jax.Array,
                draft_probs: "jnp.ndarray | None" = None) -> jnp.ndarray:
    """Vectorized speculative-decoding acceptance rule.

    One verify forward pass scored S = K+1 positions per row: the
    row's last committed token followed by its K draft tokens (padded
    with invalid slots). ``logits[:, j]`` is the target model's
    distribution for the token at offset j past the committed length.

    Acceptance (Leviathan et al. rejection sampling). Without
    ``draft_probs`` the proposal is a deterministic point mass, the
    n-gram draft (``q`` = one-hot at the draft: ``p/q`` is ``p(d)`` and
    ``max(0, p - q)`` is ``p`` with the draft removed); with it, the
    proposal is the distribution each draft was drawn from (a draft
    model, a prediction module): accept with ``min(1, p(d)/q(d))``,
    else draw from ``norm(max(0, p - q))`` (``_verify_proposal``).
    Either way:
      * greedy rows (temperature 0): draft j is accepted iff it equals
        the raw-logits argmax at offset j — the emitted stream is
        byte-identical to non-speculative greedy decode.
      * stochastic rows: draft j is accepted with probability
        p_j(d_j) under the row's FULL sampling distribution
        (temperature + top-k/top-p via the same mask as
        ``sample_tokens``); on rejection the replacement is drawn from
        the residual distribution (the draft token masked out), which
        leaves the output distribution exactly the target model's.
    Acceptance stops at the first rejection; the row always emits one
    token beyond its accepted prefix (the resample, or the bonus token
    when every draft was accepted), so progress is >= 1 token/step.

    Args:
      logits:      [B, S, vocab] raw logits
      drafts:      [B, S-1] int32 draft tokens, -1 padded
      draft_lens:  [B] int32 in [0, S-1]; 0 = plain decode row
      temperature: [B] (0 => greedy)
      top_p:       [B] (1.0 => disabled)
      top_k:       [B] int32 (0 => disabled)
      key:         PRNG key for acceptance draws + residual samples
      draft_probs: optional [B, S-1, vocab] float32, the distribution
                   each stochastic row's draft was drawn from, under
                   the row's own sampling parameters
                   (``sampling_probs``); a greedy row's draft is its
                   proposer's argmax and its entry is not read

    Returns [B, S] int32: row i's emitted tokens in its first
    ``accepted_i + 1`` slots, -1 beyond.
    """
    b, s, vocab = logits.shape
    pos = jnp.arange(s)[None, :]
    in_draft = pos[:, :-1] < draft_lens[:, None]  # [B, S-1]
    dsafe = jnp.clip(drafts, 0)
    stochastic = temperature > 0  # [B]

    # Residual removal mask: at offset j the (rejected) draft token is
    # excluded from the replacement draw. Greedy rows share it — a
    # rejected draft is by definition not the argmax, so removal never
    # changes the greedy winner; the padded final column (bonus
    # position) removes nothing.
    remove = (jax.nn.one_hot(dsafe, vocab, dtype=bool)
              & in_draft[..., None])
    remove = jnp.pad(remove, ((0, 0), (0, 1), (0, 0)))  # [B, S, V]

    greedy_targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    greedy_final = jnp.argmax(
        jnp.where(remove, NEG_INF, logits), axis=-1).astype(jnp.int32)
    accept_greedy = (drafts == greedy_targets[:, :-1]) & in_draft

    def greedy_only():
        # All-greedy batch (the common serving case): two argmaxes,
        # no softmax/sort/randomness — mirrors sample_tokens' fast
        # path.
        return accept_greedy, greedy_final

    def with_stochastic():
        safe_temp = jnp.where(stochastic, temperature, 1.0)
        scaled = (logits / safe_temp[:, None, None]).reshape(
            b * s, vocab)
        masked = _mask_top_k_top_p(
            scaled, jnp.repeat(top_p, s), jnp.repeat(top_k, s)
        ).reshape(b, s, vocab)
        probs = jax.nn.softmax(masked, axis=-1)
        p_draft = jnp.take_along_axis(
            probs[:, :-1], dsafe[..., None], axis=-1)[..., 0]
        key_u, key_r = jax.random.split(key)
        u = jax.random.uniform(key_u, (b, s - 1))
        accept_st = u < p_draft
        accept = jnp.where(stochastic[:, None], accept_st,
                           accept_greedy[:, :] | False)
        # Residual (and bonus) draw at every offset; only the offset
        # at the first rejection / past the accepted prefix is used.
        resampled = jax.random.categorical(
            key_r,
            jnp.where(remove, NEG_INF, masked).reshape(b * s, vocab),
            axis=-1).reshape(b, s).astype(jnp.int32)
        final = jnp.where(stochastic[:, None], resampled,
                          greedy_final)
        return accept & in_draft, final

    def with_proposal():
        return _verify_proposal(
            logits, drafts, in_draft, draft_probs, temperature, top_p,
            top_k, key, accept_greedy, greedy_final)

    accept, final = jax.lax.cond(
        jnp.any(stochastic),
        with_stochastic if draft_probs is None else with_proposal,
        greedy_only)
    # Accepted prefix length: drafts accept left-to-right until the
    # first rejection.
    a = jnp.cumprod(accept.astype(jnp.int32), axis=-1).sum(axis=-1)
    drafts_padded = jnp.pad(drafts, ((0, 0), (0, 1)))
    return jnp.where(
        pos < a[:, None], drafts_padded,
        jnp.where(pos == a[:, None], final, -1)).astype(jnp.int32)
