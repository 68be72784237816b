"""Token sampling, fully vectorized in-graph (no host round-trip of
logits): temperature, top-k, top-p and greedy, per-slot parameters so one
decode batch mixes sampling configs.
"""

from __future__ import annotations

import math

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

    Shared by ``sample_tokens``, ``spec_verify`` and the proposal's
    pair (``draw_proposal``, ``verify_proposal``) so the sampling and
    speculative-verification distributions cannot drift.

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


def _accepted_length(accept: jnp.ndarray) -> jnp.ndarray:
    """Drafts accept left to right until the first rejection: ``[B]``
    accepted prefix lengths of ``accept [B, K]``."""
    return jnp.cumprod(accept.astype(jnp.int32), axis=-1).sum(axis=-1)


def _emitted(drafts: jnp.ndarray, a: jnp.ndarray,
             final: jnp.ndarray) -> jnp.ndarray:
    """What both verify rules hand back: row i's accepted drafts in its
    first ``a_i`` slots, ``final`` (``[B, 1]``, or ``[B, K + 1]`` by
    offset) at slot ``a_i``, -1 beyond."""
    pos = jnp.arange(drafts.shape[1] + 1)[None, :]
    drafts_padded = jnp.pad(drafts, ((0, 0), (0, 1)))
    return jnp.where(
        pos < a[:, None], drafts_padded,
        jnp.where(pos == a[:, None], final, -1)).astype(jnp.int32)


def _inverse_temperature(temperature: jnp.ndarray) -> jnp.ndarray:
    """``[B]`` factor of a row's exponent (1 for a greedy row): the
    proposal is drawn and measured under the same product, so no
    divided copy of the logits exists on either side."""
    return 1.0 / jnp.where(temperature > 0, temperature, 1.0)


def _needs_mask(top_p: jnp.ndarray, top_k: jnp.ndarray) -> jnp.ndarray:
    return jnp.any((top_k > 0) | (top_p < 1.0))


def draw_proposal(logits: jnp.ndarray, temperature: jnp.ndarray,
                  top_p: jnp.ndarray, top_k: jnp.ndarray,
                  key: jax.Array) -> jnp.ndarray:
    """A proposer's draft from its own logits ``[B, vocab]`` float32,
    ``[B]`` int32: a stochastic row's is drawn from its FULL sampling
    distribution ``softmax(mask(logits / T))`` (``sample_tokens``'
    mask), which ``verify_proposal`` measures it by from the same
    logits; a greedy row's is the argmax. Gumbel-max on the scaled
    logits in ONE pass over the plane: a greedy row's noise is
    multiplied by zero, so one argmax serves every row. No
    probabilities are written, and the vocabulary is sorted only where
    some row has a top-k or a top-p."""
    stochastic = temperature > 0
    scale = _inverse_temperature(temperature)

    def drawn(mask):
        # Scaled inside the branch: the product is part of its pass.
        scaled = mask(logits * scale[:, None])
        noise = jax.random.gumbel(key, scaled.shape, scaled.dtype)
        return jnp.argmax(
            scaled + noise * stochastic.astype(scaled.dtype)[:, None],
            axis=-1)

    def sampled():
        return jax.lax.cond(
            _needs_mask(top_p, top_k),
            lambda: drawn(lambda x: _mask_top_k_top_p(x, top_p, top_k)),
            lambda: drawn(lambda x: x))

    return jax.lax.cond(
        jnp.any(stochastic), sampled,
        lambda: jnp.argmax(logits, axis=-1)).astype(jnp.int32)


def _by_offset(a: jnp.ndarray, values) -> jnp.ndarray:
    """Row i's ``values[a_i]`` (the last where ``a_i`` is beyond) of a
    short sequence of ``[B]`` or ``[B, vocab]`` arrays: selects, which
    fuse into the pass that reads the result, where a gather over an
    axis of two or three would be a pass of its own."""
    out = values[-1]
    for j in range(len(values) - 2, -1, -1):
        here = a == j
        out = jnp.where(here if values[j].ndim == 1 else here[:, None],
                        values[j], out)
    return out


def _element(planes, j: int, index: jnp.ndarray) -> jnp.ndarray:
    """``planes[j][i, index[i]]`` for every row i, ``[B]``: one element
    a row, gathered from ``[S, B, vocab]`` as it stands (plane j sliced
    out for the gather would be written out first)."""
    rows = jnp.arange(index.shape[0])
    if isinstance(planes, (tuple, list)):
        return planes[j][rows, index]
    return planes[j, rows, index]


def _log_norm(planes, j: int, scale: jnp.ndarray, top=None):
    """``(m, lse)``, both ``[B]``: row i's ``softmax(planes[j] * scale)``
    is ``exp((planes[j] - m) * scale - lse)``. ``top`` is the plane's
    argmax where the caller has it (the max is then one element a row
    and not a pass)."""
    m = (jnp.max(planes[j], axis=-1) if top is None
         else _element(planes, j, top))
    lse = jnp.log(jnp.sum(
        jnp.exp((planes[j] - m[:, None]) * scale[:, None]), axis=-1))
    return m, lse


def _proposal_rule(logits, proposal, drafts, draft_lens, temperature,
                   top_p, top_k, tops, finish):
    """The part of ``verify_proposal`` that draws nothing: the rows'
    two distributions as statistics of the planes, for a batch with a
    stochastic row. ``finish(p_draft, q_draft, log_weights)`` gets
    ``p_j(d_j)`` and ``q_j(d_j)`` (both ``[B, K]``) and a function from
    accepted lengths ``a [B]`` to the ``[B, vocab]`` log-weights of
    each row's replacement at offset ``a``, ``log max(0, p_a - q_a)``
    with ``q`` zero from ``draft_lens`` on; it is called inside the
    branch that ran (plain, or masked where some row has a top-k or a
    top-p) and what it returns comes back. ``tops`` are the target
    planes' argmaxes, which the plain branch takes each max from.
    Planes of ``[S, B, vocab]`` are taken inside the branch that reads
    them, where the slice is part of the reading pass: sliced out
    before, each would be a branch's operand, written out first."""
    k = len(proposal)
    b = drafts.shape[0]
    dsafe = jnp.clip(drafts, 0)

    def on(targets, proposals, scale, target_tops):
        # ``softmax(plane * scale)`` are the rows' distributions.
        def log_prob(x, norm):
            shape = (b,) + (1,) * (x.ndim - 1)
            m, lse = (n.reshape(shape) for n in norm)
            return (x - m) * scale.reshape(shape) - lse

        def at_drafts(planes, norms):
            return jnp.stack([
                jnp.exp(log_prob(_element(planes, j, dsafe[:, j]),
                                 norms[j])) for j in range(k)], axis=1)

        p_norm = [_log_norm(targets, j, scale, target_tops[j])
                  for j in range(k + 1)]
        q_norm = [_log_norm(proposals, j, scale) for j in range(k)]

        def log_weights(a):
            # One pass: the row's target plane at a, and the
            # proposal's where its draft at a was rejected.
            def at(planes, norms):
                return jnp.exp(log_prob(
                    _by_offset(a, [planes[j] for j in range(len(norms))]),
                    [_by_offset(a, [n[i] for n in norms])
                     for i in (0, 1)]))
            residual = at(targets, p_norm) - jnp.where(
                (a < draft_lens)[:, None], at(proposals, q_norm), 0.0)
            return jnp.where(residual > 0, jnp.log(residual), NEG_INF)

        return finish(at_drafts(targets, p_norm),
                      at_drafts(proposals, q_norm), log_weights)

    scale = _inverse_temperature(temperature)

    def masked(planes):
        return [_mask_top_k_top_p(planes[j] * scale[:, None], top_p, top_k)
                for j in range(len(planes))]

    return jax.lax.cond(
        _needs_mask(top_p, top_k),
        lambda: on(masked(logits), masked(proposal),
                   jnp.ones_like(scale), [None] * (k + 1)),
        lambda: on(logits, proposal, scale, tops))


def verify_proposal(logits, drafts: jnp.ndarray, draft_lens: jnp.ndarray,
                    proposal, temperature: jnp.ndarray,
                    top_p: jnp.ndarray, top_k: jnp.ndarray,
                    key: jax.Array) -> jnp.ndarray:
    """``spec_verify``'s rule for a proposal that is a DISTRIBUTION (a
    draft model, a prediction module): draft j, drawn from ``q_j``, is
    accepted with ``min(1, p_j(d_j) / q_j(d_j))``; the one replacement
    a row needs, at its first rejected offset ``a`` or at the bonus
    offset, is drawn from ``norm(max(0, p_a - q_a))`` (``q`` counts as
    zero at the bonus offset and where the row offered no draft, so
    there it is ``p_a`` itself). The output distribution is exactly
    the target's (Leviathan et al.). A greedy row accepts a draft iff
    it is the raw argmax and its replacement is the raw argmax at
    ``a``: the stream non-speculative greedy decode gives.

    Positions are PLANES: ``logits`` is a sequence of S dense
    ``[B, vocab]`` float32 arrays (or one ``[S, B, vocab]``, S
    outermost), never ``[B, S, vocab]``, whose minor tiles of S rows an
    elementwise pass pays for several times over. The proposer hands
    over what its head wrote, and both distributions are measured here
    from logits, under the row's temperature (in the exponent) and,
    where some row has one, its top-k/top-p (``sample_tokens``' mask on
    target and proposal alike; the vocabulary is sorted only then). No
    probabilities are written out. Per plane one pass for the argmax
    (whose value is the max) and one for the log-sum-exp; ``p_j(d_j)``
    and ``q_j(d_j)`` are one element a row; then ONE pass over the
    planes selected per row by ``a`` forms the residual's log-weights,
    adds the Gumbel noise and reduces to the replacement. The
    point-mass rule's ``remove`` mask is not computed here.

    Args:
      logits:      S planes ``[B, vocab]`` float32, the target's raw
                   logits at offsets 0 .. S-1
      drafts:      [B, S-1] int32 draft tokens, -1 padded
      draft_lens:  [B] int32 in [0, S-1]; 0 = a row without a draft
      proposal:    S-1 planes ``[B, vocab]`` float32: the raw logits
                   draft j was drawn from by ``draw_proposal`` under
                   the row's own sampling parameters (a row without a
                   draft: not read)
      temperature, top_p, top_k: [B], as ``sample_tokens`` takes them
      key:         PRNG key for the acceptance draws and the
                   replacement

    Returns [B, S] int32: row i's emitted tokens in its first
    ``accepted_i + 1`` slots, -1 beyond.
    """
    k = len(proposal)
    if len(logits) != k + 1 or k < 1:
        raise ValueError(
            f"verify_proposal: {len(logits)} target planes need "
            f"{len(logits) - 1} proposal planes (at least one), got {k}")
    in_draft = jnp.arange(k)[None, :] < draft_lens[:, None]  # [B, K]
    stochastic = temperature > 0
    tops = [jnp.argmax(logits[j], axis=-1).astype(jnp.int32)
            for j in range(k + 1)]
    accept_greedy = (drafts == jnp.stack(tops[:-1], axis=1)) & in_draft

    def greedy_only():
        # An all-greedy batch: an argmax a position, nothing else.
        a = _accepted_length(accept_greedy)
        return a, _by_offset(a, tops)

    def draws(p_draft, q_draft, log_weights):
        key_u, key_r = jax.random.split(key)
        u = jax.random.uniform(key_u, drafts.shape)
        accept = jnp.where(stochastic[:, None], u * q_draft < p_draft,
                           accept_greedy) & in_draft
        a = _accepted_length(accept)
        resampled = jax.random.categorical(
            key_r, log_weights(a), axis=-1).astype(jnp.int32)
        return a, jnp.where(stochastic, resampled, _by_offset(a, tops))

    a, final = jax.lax.cond(
        jnp.any(stochastic),
        lambda: _proposal_rule(logits, proposal, drafts, draft_lens,
                               temperature, top_p, top_k, tops, draws),
        greedy_only)
    return _emitted(drafts, a, final[:, None])


def spec_verify(logits: jnp.ndarray, drafts: jnp.ndarray,
                draft_lens: jnp.ndarray, temperature: jnp.ndarray,
                top_p: jnp.ndarray, top_k: jnp.ndarray,
                key: jax.Array) -> jnp.ndarray:
    """Vectorized speculative-decoding acceptance rule.

    One verify forward pass scored S = K+1 positions per row: the
    row's last committed token followed by its K draft tokens (padded
    with invalid slots). ``logits[:, j]`` is the target model's
    distribution for the token at offset j past the committed length.

    Acceptance (Leviathan et al. rejection sampling) for a proposal
    that is a deterministic point mass, the n-gram draft (``q`` =
    one-hot at the draft: ``p/q`` is ``p(d)`` and ``max(0, p - q)`` is
    ``p`` with the draft removed, which is what the ``remove`` mask
    below is for). A proposal that is a distribution (a draft model, a
    prediction module) has its own function, ``verify_proposal``: it
    wants a plane of logits a draft where this one wants a token id,
    and the two share nothing over the vocabulary, only ``_emitted``.
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

    accept, final = jax.lax.cond(
        jnp.any(stochastic), with_stochastic, greedy_only)
    return _emitted(drafts, _accepted_length(accept), final)


# Which masked places of a block a denoising pass commits (the
# published ``remasking_strategy`` names of the block-diffusion
# families' ``generate.py``), as the integer a row carries.
REMASKING_STRATEGIES = ("sequential", "low_confidence_static",
                        "low_confidence_dynamic")


# Ids a block of ``draw_by_blocks``: one tile of the minor axis, so a
# block's sum is a reduction inside a tile and the chosen block of a
# row is one aligned slice of the plane.
DRAW_BLOCK = 128


def draw_by_blocks(planes, j: int, scale: jnp.ndarray, u: jnp.ndarray,
                   stochastic: jnp.ndarray):
    """A token a row from ``softmax(planes[j] * scale)`` by inversion:
    the first id whose running sum of ``exp(planes[j] * scale - m)``
    passes ``u * Z`` (``m`` the row's maximum, ``Z`` the whole sum), so
    one uniform variate a row where Gumbel-max wants one a logit. A row
    that is not ``stochastic`` gets its first maximum. Float32
    throughout, every id weighed.

    Two passes over the plane: its argmax (whose value is ``m``, and
    a greedy row's token), then the sums of ``exp(. - m)`` over blocks
    of ``DRAW_BLOCK`` ids (the vocabulary padded with weight 0 to whole
    blocks), so that no pass scans the vocabulary serially and no
    float32 running sum is long. The rest is small: the running sum
    over a row's block sums picks its block, that block alone is
    gathered from the plane (from ``[S, B, vocab]`` as it stands, as
    ``_element`` does), and the running sum inside it picks the id. An
    id of weight 0 (``NEG_INF`` under ``_mask_top_k_top_p``, the
    padding) is never drawn whatever ``u``: the comparisons are strict
    and block and id are held to the last of positive weight, so
    ``u * Z`` rounding to or past a last partial sum picks no masked id.

    Args:
      planes:     ``[S, B, vocab]`` float32 logits or a sequence of
                  ``[B, vocab]`` planes (masked entries ``NEG_INF``)
      j:          the plane drawn from
      scale:      [B] float32 > 0, the row's inverse temperature
      u:          [B] float32 in [0, 1)
      stochastic: [B] bool

    Returns ``(x [B] int32, p [B] float32)``: the ids and their
    probabilities ``exp(planes[j][x] * scale - m) / Z``.
    """
    b, vocab = planes[j].shape
    w = DRAW_BLOCK
    nb = -(-vocab // w)
    # Pass 1. The scale is positive, so the scaled plane's argmax is the
    # raw one's and the pass reads the plane as it stands.
    top = jnp.argmax(planes[j], axis=-1).astype(jnp.int32)
    m = _element(planes, j, top) * scale
    if isinstance(planes, (tuple, list)) or nb * w != vocab:
        planes, j = jnp.pad(planes[j], ((0, 0), (0, nb * w - vocab)),
                            constant_values=NEG_INF)[None], 0
    # Pass 2. Rows by eights beside the blocks: on a tiled device this
    # view is the planes' own memory (tiles of 8 rows x 128 ids), where
    # [B, blocks, 128] would be a copy of the plane in another layout.
    # The barrier keeps it ONE pass: without it ``Z`` is fused into a
    # reduction over the plane of its own.
    sub = math.gcd(b, 8)
    tiles = planes.reshape(planes.shape[0], b // sub, sub, nb, w)
    by_tile = (b // sub, sub, 1, 1)
    block_sum = jax.lax.optimization_barrier(jnp.sum(jnp.exp(
        tiles[j] * scale.reshape(by_tile) - m.reshape(by_tile)),
        axis=3)).reshape(b, nb)

    def first_past(weights, target):
        """Per row the first index whose running sum of ``weights``
        passes ``target`` (>= 0), held to the last positive weight, and
        the running sum before it."""
        run = jnp.cumsum(weights, axis=-1)
        index = jnp.arange(weights.shape[-1])[None, :]
        last = jnp.max(jnp.where(weights > 0, index, 0), axis=-1)
        at = jnp.minimum(jnp.sum(run <= target[:, None], axis=-1), last)
        before = jnp.take_along_axis(run - weights, at[:, None], axis=1)
        return at.astype(jnp.int32), before[:, 0]

    z = jnp.sum(block_sum, axis=-1)
    target = u * z
    k, before = first_past(block_sum, target)
    rows = jnp.arange(b)
    weights = jnp.exp(tiles[j, rows // sub, rows % sub, k] * scale[:, None]
                      - m[:, None])                             # [B, w]
    i, _ = first_past(weights, jnp.maximum(target - before, 0.0))
    weight = jnp.take_along_axis(weights, i[:, None], axis=1)[:, 0]
    return (jnp.where(stochastic, k * w + i, top),
            jnp.where(stochastic, weight, 1.0) / z)


def unmask_block(logits, masked: jnp.ndarray, quota: jnp.ndarray,
                 strategy: jnp.ndarray, threshold: jnp.ndarray,
                 temperature: jnp.ndarray, top_p: jnp.ndarray,
                 top_k: jnp.ndarray, key: jax.Array):
    """One denoising pass's draws and choice for a block of ``T``
    places a row (block-diffusion decoding, docs/block_diffusion.md).

    At every place a token ``x0`` is drawn from the place's own
    distribution ``softmax(mask(logits / T))`` (``sample_tokens``'
    temperature, top-k and top-p; ``draw_by_blocks``: one uniform
    variate a row and two passes over the plane; the argmax for a
    greedy row) with its confidence ``p(x0)`` under that same
    distribution (a greedy row's under the raw softmax). Of the places
    still masked, ``quota`` are then committed by the row's rule:

    - 0 ``sequential``: the first masked place and the places after it,
      ``quota`` of them;
    - 1 ``low_confidence_static``: the ``quota`` masked places of
      highest confidence (ties: the leftmost first);
    - 2 ``low_confidence_dynamic``: every masked place whose confidence
      is over ``threshold`` if they are at least ``quota``, else as
      static.

    Only masked places are ever committed (a quota larger than what is
    left commits what is left).

    Args:
      logits:    [T, B, vocab] float32, position-major: each place a
                 dense plane (the draft burst's layout, PERF.md PR 44)
      masked:    [B, T] bool, places not yet committed
      quota:     [B] int32, places to commit this pass
      strategy:  [B] int32, index into ``REMASKING_STRATEGIES``
      threshold: [B] float32 (the dynamic rule's)
      temperature, top_p, top_k: [B], as ``sample_tokens``
      key:       PRNG key of the pass

    Returns ``(x0 [B, T] int32, commit [B, T] bool, confidence [B, T]
    float32)``.
    """
    t = len(logits) if isinstance(logits, (tuple, list)) else logits.shape[0]
    b = masked.shape[0]
    stochastic = temperature > 0
    scale = _inverse_temperature(temperature)
    keys = jax.random.split(key, t)

    def draw(j, masked_form: bool):
        u = jax.random.uniform(keys[j], (b,), jnp.float32)
        if masked_form:
            # The mask's NEG_INF entries weigh nothing in the draw.
            return draw_by_blocks(
                (_mask_top_k_top_p(logits[j] * scale[:, None], top_p,
                                   top_k),),
                0, jnp.ones_like(scale), u, stochastic)
        return draw_by_blocks(logits, j, scale, u, stochastic)

    with jax.named_scope("unmask_block"):
        # A place at a time, each under its own choice of form: the
        # vocabulary is sorted only where some row has a top-k or a
        # top-p, and the sort's buffers are one plane's, not T planes'.
        needs_mask = _needs_mask(top_p, top_k)
        drawn = [jax.lax.cond(
            needs_mask, lambda j=j: draw(j, True),
            lambda j=j: draw(j, False)) for j in range(t)]
        x0 = jnp.stack([x for x, _ in drawn], axis=1)
        conf = jnp.stack([c for _, c in drawn], axis=1)
        place = jnp.arange(t)[None, :]
        n = quota[:, None]
        first = jnp.argmax(masked, axis=1)[:, None]
        sequential = (place >= first) & (place < first + n)
        # A place's rank by confidence among the masked ones.
        c = jnp.where(masked, conf, -jnp.inf)
        ahead = ((c[:, None, :] > c[:, :, None])
                 | ((c[:, None, :] == c[:, :, None])
                    & (place[:, None, :] < place[:, :, None])))
        static = jnp.sum(ahead, axis=2) < n
        high = c > threshold[:, None]
        dynamic = jnp.where(
            jnp.sum(high, axis=1, keepdims=True) >= n, high, static)
        kind = strategy[:, None]
        commit = jnp.where(kind == 0, sequential,
                           jnp.where(kind == 1, static, dynamic))
        return x0, commit & masked, conf
