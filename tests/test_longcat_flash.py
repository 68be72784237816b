"""The LongCat-Flash model and what it added to the ops: latent
attention (MLA) over one latent plane a sublayer, absorbed, against
per-head keys and values made by hand, the Pallas latent decode kernel,
the softmax router with a bias in the choice and zero-compute experts,
two attention
sublayers a layer around a shortcut-connected expert branch, and the
share of an expert-parallel group tied to the whole layer (through the
engine: tests/test_longcat_flash_engine.py).

Tiny widths, float32, seeded, on the CPU; the oracle is the family's
plain reference (chipbench/reference/longcat_family.py: materialised
attention, no cache). ``FLOAT32`` 2e-5 on log-probabilities: both sides
float32 on one CPU with the same weights, differing in the order of
sums (the readings are under 2e-6); ``INTERPRET`` 2e-4 where a Pallas
kernel in interpret mode sums in another order. A term left out or put
in has to fail ``FLOAT32`` by ``CLEAR`` = 100 times.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import longcat_family as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    ModelConfig,
    tiny_longcat_flash_config,
)
from production_stack_tpu.models import longcat_flash
from production_stack_tpu.models.registry import init_hybrid_cache
from production_stack_tpu.ops import mla_attention
from production_stack_tpu.ops.mla_attention_pallas import (
    latent_paged_decode_attention,
)
from production_stack_tpu.ops.moe import identity_weight, route_softmax_bias

FLOAT32 = 2e-5
INTERPRET = 2e-4
CLEAR = 100


def model_config(**over):
    config = tiny_longcat_flash_config()
    config.attention_impl = "xla"
    return dataclasses.replace(config, **over)


def prompt_of(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, 512, size=n)]


def served_log_probs(config, params, tokens, prompt, chunk,
                     forward=longcat_flash.forward):
    """Row 1 of two (row 0 is padding): the prompt in padded chunks of
    at most ``chunk`` real tokens, each written to the latent pages and
    read back by the next, then one cached decode step a token.
    Log-softmax of every position, and the caches."""
    k_cache, v_cache = init_hybrid_cache(config, 32, 16, 0)
    table = np.zeros((2, 8), np.int32)
    table[1, :6] = [3, 4, 5, 6, 7, 8]
    step = jax.jit(lambda *a, **k: forward(params, config, *a, **k))
    width = -(-chunk // 16) * 16
    got, start = [], 0
    while start < prompt:
        n = min(chunk, prompt - start)
        tok = np.zeros((2, width), np.int32)
        pos = np.zeros((2, width), np.int32)
        valid = np.zeros((2, width), bool)
        tok[1, :n] = tokens[start:start + n]
        pos[1, :n] = np.arange(start, start + n)
        valid[1, :n] = True
        logits, k_cache, v_cache = step(
            tok, pos, table, np.array([0, start + n], np.int32), valid,
            k_cache, v_cache)
        got.append(jax.nn.log_softmax(logits[1, :n]))
        start += n
    for p in range(prompt, len(tokens)):
        logits, k_cache, v_cache = step(
            np.array([[0], [tokens[p]]], np.int32),
            np.array([[0], [p]], np.int32), table,
            np.array([0, p + 1], np.int32),
            np.array([[False], [True]]), k_cache, v_cache)
        got.append(jax.nn.log_softmax(logits[1, :1]))
    return np.concatenate(got), k_cache, v_cache


@functools.lru_cache(maxsize=None)
def tiny():
    """(config, params, 56 tokens, the program's log-probabilities of
    them: two chunks of 24, a third of 2, then six cached steps)."""
    config = model_config()
    params = longcat_flash.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(prompt_of(56, seed=1))
    got, _, _ = served_log_probs(config, params, tokens, 50, 24)
    return config, params, tokens, got


def reference_log_probs(config, params, tokens, **levers):
    model = dataclasses.replace(reference.model_of(config, params),
                                **levers)
    return np.asarray(reference.log_probs(model, tokens,
                                          list(range(len(tokens)))))


# ---- the model against the reference ---------------------------------------


@pytest.mark.parametrize("prompt,chunk", [
    (56, 56),    # one shot
    (50, 24),    # three chunks over the latent pages, then six steps
    (33, 16),    # chunks that end on a page's edge, then 23 steps
    (2, 1),      # two chunks of one token, then decode from the start
])
def test_prefill_then_decode_agree_with_one_full_forward(prompt, chunk):
    config, params, tokens, _ = tiny()
    want = reference_log_probs(config, params, tokens)
    got, k_cache, v_cache = served_log_probs(config, params, tokens,
                                             prompt, chunk)
    assert np.abs(got - want).max() < FLOAT32
    # Two latent planes a layer of one head of 24 + 8 rows, the six
    # counters after them, and no second plane anywhere.
    assert [e.shape for e in k_cache] == [(1, 32, 32, 16)] * 4 + [(7,)]
    assert v_cache == (None,) * 4
    # The row's pages hold its 56 latents in every sublayer's plane,
    # each sublayer its own; the other pages hold nothing.
    held = [np.asarray(plane)[0, 3:9] for plane in k_cache[:4]]
    for mine in held:
        filled = mine.transpose(0, 2, 1).reshape(96, 32)
        assert np.abs(filled[:56]).min(axis=1).max() > 0
        assert not filled[56:].any()
    assert np.abs(held[0] - held[1]).max() > 0.1
    for plane in k_cache[:4]:
        assert not np.asarray(plane)[0, 9:].any()
    counters = np.asarray(k_cache[4])
    assert counters[0] == (56 - prompt) * 2      # one branch a layer
    assert counters[1] == (56 - prompt) * 2 * 3  # three choices a token


def test_absorbed_attention_over_pages_is_materialised_attention():
    """The absorbed form over the pages against per-head keys and
    values made from the same latents by hand, to float32 rounding: 4
    heads of 16 + 8 over a latent of 24 + 8, two rows of 37 and 20
    cached tokens and a chunk of 5 queries each."""
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    n, dn, dr, rank, dv, page = 4, 16, 8, 24, 16, 16
    plane = jax.random.normal(keys[0], (1, 8, rank + dr, page), jnp.float32)
    w_uk = jax.random.normal(keys[1], (n, dn, rank), jnp.float32)
    w_uv = jax.random.normal(keys[2], (n, rank, dv), jnp.float32)
    q = jax.random.normal(keys[3], (2, 5, n, dn + dr), jnp.float32)
    table = jnp.array([[1, 2, 3], [4, 5, 0]], jnp.int32)
    lens = jnp.array([37, 20], jnp.int32)
    positions = (lens - 5)[:, None] + jnp.arange(5)[None, :]
    with jax.default_matmul_precision("highest"):
        got = mla_attention.latent_paged_attention(
            q, plane, table, positions, lens, w_uk, w_uv, 24 ** -0.5)
        for row in range(2):
            held = plane[0][table[row]].transpose(0, 2, 1).reshape(-1, 32)
            held = held[:int(lens[row])]                    # [S, 32]
            k_nope = jnp.einsum("sr,ndr->snd", held[:, :rank], w_uk)
            values = jnp.einsum("sr,nrv->snv", held[:, :rank], w_uv)
            scores = (jnp.einsum("tnd,snd->nts", q[row, ..., :dn], k_nope)
                      + jnp.einsum("tnd,sd->nts", q[row, ..., dn:],
                                   held[:, rank:])) * 24 ** -0.5
            causal = (jnp.arange(held.shape[0])[None, :]
                      <= positions[row][:, None])
            probs = jax.nn.softmax(jnp.where(causal[None], scores,
                                             -jnp.inf), -1)
            want = jnp.einsum("nts,snv->tnv", probs, values)
            assert np.abs(got[row] - want).max() < 2e-5 * np.abs(
                want).max()
            assert np.abs(want).max() > 1


# ---- each term left out or put in ------------------------------------------


def _rotate_half(x, theta):
    t, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    return x * cos + jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]],
                                     -1) * sin


def _choose(bias_in_choice=True, bias_in_weight=False, renormalise=False):
    def choose(m, w, scores):
        by = scores + w["router_bias"] if bias_in_choice else scores
        _, chosen = jax.lax.top_k(by, m.top_k)
        weight = jnp.take_along_axis(by if bias_in_weight else scores,
                                     chosen, axis=-1)
        if renormalise:
            weight = weight / jnp.sum(weight, -1, keepdims=True)
        return m.routed_scale * weight, chosen
    return choose


def _layer(branch_input="u", branch_at="end"):
    ref = reference

    def layer_forward(m, i, h):
        a1, a2 = m.sublayer(2 * i), m.sublayer(2 * i + 1)
        h1 = h + ref.attention(m, a1, ref.norm(h, a1["attn_norm"],
                                               m.rms_eps))
        u = ref.norm(h1, a1["ffn_norm"], m.rms_eps)
        branch = ref.moe(m, m.branch(i), u if branch_input == "u" else h1)
        h2 = h1 + ref.mlp(a1, u)
        if branch_at == "before A2":
            h2 = h2 + branch
        h3 = h2 + ref.attention(m, a2, ref.norm(h2, a2["attn_norm"],
                                                m.rms_eps))
        out = h3 + ref.mlp(a2, ref.norm(h3, a2["ffn_norm"], m.rms_eps))
        return out if branch_at == "before A2" else out + branch
    return layer_forward


WRONG = {
    "the query's low-rank scale left out": dict(levers={"q_scale": 1.0}),
    "the latent's low-rank scale left out": dict(levers={"kv_scale": 1.0}),
    "no rotary on the shared key": dict(
        patch=("key_rope", lambda m, k_r: k_r)),
    "the rotary's pairs half a head apart": dict(
        patch=("rope", _rotate_half)),
    "the latent's norm left out": dict(
        patch=("latent_norm", lambda m, w, c_raw: c_raw)),
    "the bias left out of the choice": dict(
        patch=("choose", _choose(bias_in_choice=False))),
    "the bias leaking into the weights": dict(
        patch=("choose", _choose(bias_in_weight=True))),
    "the routed scaling factor left out": dict(
        levers={"routed_scale": 1.0}),
    "the chosen weights renormalised": dict(
        patch=("choose", _choose(renormalise=True))),
    "the identity term left out": dict(
        patch=("identity_term", lambda m, weight, chosen, x: 0.0 * x)),
    "the identity term weighted once a token": dict(
        patch=("identity_term", lambda m, weight, chosen, x: x * jnp.any(
            chosen >= m.first_zero_expert, -1, keepdims=True))),
    "the branch read from h1": dict(
        patch=("layer_forward", _layer(branch_input="h1"))),
    "the branch added before A2": dict(
        patch=("layer_forward", _layer(branch_at="before A2"))),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_a_term_left_out_or_put_in_fails_the_limit_clearly(wrong,
                                                           monkeypatch):
    """The program against the reference with one term changed: the
    worst log-probability moves by over ``CLEAR`` times the limit, so
    the comparison would see the same term changed in the program. The
    bias is drawn wider here, a third of the scores' spread: at the
    init's own width a prompt of 56 tokens may meet no flipped
    choice."""
    config, params, tokens, _ = tiny()
    params = dict(params, router_bias=0.02 * jax.random.normal(
        jax.random.PRNGKey(9), params["router_bias"].shape, jnp.float32))
    got, _, _ = served_log_probs(config, params, tokens, 50, 24)
    assert np.abs(got - reference_log_probs(config, params, tokens)
                  ).max() < FLOAT32
    case = WRONG[wrong]
    if "patch" in case:
        monkeypatch.setattr(reference, *case["patch"])
    want = reference_log_probs(config, params, tokens,
                               **case.get("levers", {}))
    assert np.abs(got - want).max() > CLEAR * FLOAT32, wrong


def test_a1s_cache_served_to_a2_fails_the_limit_clearly(monkeypatch):
    """The program itself with the second sublayer of a layer reading
    (and appending to) the first one's plane: wrong from the second
    chunk on, where earlier latents come from the pages."""
    config, params, tokens, got = tiny()
    real, seen = longcat_flash.mla, []

    def mixed_up(config, lp, x, positions, page_table, kv_lens, valid,
                 plane, tail=None):
        if len(seen) % 2:
            plane = seen[-1]
        y, plane = real(config, lp, x, positions, page_table, kv_lens,
                        valid, plane, tail)
        seen.append(plane)
        return y, plane

    def forward(*args, **kwargs):
        seen.clear()
        return longcat_flash.forward(*args, **kwargs)

    monkeypatch.setattr(longcat_flash, "mla", mixed_up)
    wrong, _, _ = served_log_probs(config, params, tokens, 50, 24,
                                   forward=forward)
    assert np.abs(wrong[:24] - got[:24]).max() < FLOAT32   # no cache yet
    assert np.abs(wrong[24:48] - got[24:48]).max() > CLEAR * FLOAT32
    assert np.abs(wrong[50:] - got[50:]).max() > CLEAR * FLOAT32


# ---- the router -------------------------------------------------------------


def test_the_bias_changes_the_choice_and_the_weights_are_six_times_the_scores():
    """Four outputs, the last a zero-compute expert, top-2. Scores
    softmax(2, 1, 0, 0) = 0.610, 0.224, 0.083, 0.083: without a bias
    outputs 0 and 1. A bias of +0.2 on output 3 (0.283 > 0.224) puts it
    in output 1's place; its weight is 6 times its unbiased 0.083, and
    the two weights are not divided by their sum."""
    x = jnp.eye(3, dtype=jnp.float32)[:1]
    router = jnp.array([[2.0, 1.0, 0.0, 0.0], [0] * 4, [0] * 4])
    s = jax.nn.softmax(jnp.array([2.0, 1.0, 0.0, 0.0]))
    weights, ids = route_softmax_bias(x, router, jnp.zeros(4), 2, 6.0)
    assert ids.tolist() == [[0, 1]]
    np.testing.assert_allclose(weights[0], 6 * s[:2], rtol=1e-6)
    kept, count = identity_weight(weights, ids, 3)
    assert kept.tolist() == [0.0] and count.tolist() == [0]
    weights, ids = route_softmax_bias(
        x, router, jnp.array([0.0, 0.0, 0.0, 0.2]), 2, 6.0)
    assert ids.tolist() == [[0, 3]]
    np.testing.assert_allclose(weights[0], 6 * s[jnp.array([0, 3])],
                               rtol=1e-6)
    kept, count = identity_weight(weights, ids, 3)
    np.testing.assert_allclose(kept, [6 * s[3]], rtol=1e-6)
    assert count.tolist() == [1]


def test_random_init_draws_what_a_zero_or_a_one_would_switch_off():
    config = model_config()
    params = longcat_flash.init_params(config, jax.random.PRNGKey(0))
    for name in ("attn_norm", "ffn_norm", "final_norm", "q_a_norm",
                 "kv_a_norm"):
        w = np.asarray(params[name], np.float32)
        assert 0.05 < w.std() < 0.2 and abs(w.mean() - 1) < 0.05, name
    assert params["lm_head"].shape == (64, 512)        # untied
    assert params["router_bias"].dtype == jnp.float32
    assert params["router"].shape == (2, 64, 12)       # 8 routed + 4 zero
    assert params["w_uk"].shape == (4, 4, 16, 24)
    assert params["w_uv"].shape == (4, 4, 24, 16)


def test_the_bias_moves_a_visible_share_of_choices_at_the_published_width():
    """768 outputs, 12 chosen: the scores lie around 1 / 768 with a
    spread of 6e-4 here, so N(0, 5e-4) moves a third of the choices
    (0.357) and leaves the scores a say: under the bias alone every
    token would choose the same twelve."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(keys[0], (256, 512), jnp.float32)
    router = 0.02 * jax.random.normal(keys[1], (512, 768), jnp.float32)
    bias = 5e-4 * jax.random.normal(keys[2], (768,), jnp.float32)
    _, with_bias = route_softmax_bias(x, router, bias, 12, 6.0)
    _, without = route_softmax_bias(x, router, jnp.zeros(768), 12, 6.0)
    moved = np.mean([len(set(a) - set(b)) for a, b in
                     zip(np.asarray(with_bias), np.asarray(without))]) / 12
    assert 0.25 < moved < 0.5
    assert len({tuple(sorted(row)) for row in np.asarray(with_bias)}) > 200


def test_the_inits_own_count_at_the_published_widths_is_the_hand_sum():
    """Shapes alone: 4 layers of two MLA sublayers and two dense
    feed-forwards, a router with its bias and 16 held experts, four
    norms; the final norm, the embedding and the untied head."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs",
        "longcat-flash-omni-ep32.json")
    with open(path) as f:
        hf = json.load(f)
    config = ModelConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda key: longcat_flash.init_params(config, key),
        jax.random.PRNGKey(0))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    layer = 2 * 90572800 + 2 * 226492416 + 4718592 + 768 + 4 * 6144
    assert layer == 638873600 + 768
    assert count == 4 * (layer + 16 * 37748736) + 2 * 16384 * 6144 + 6144
    assert count == 5172749312
    assert shapes["e_w_gate_up_3"].shape == (16, 6144, 4096)
    assert config.router_width == 16 * 32 + 256
    assert config.mla_q_scale == 2.0
    assert abs(config.mla_kv_scale - 12 ** 0.5) < 1e-12
    assert config.page_cache == (8, 1, 576, 1)
    assert CacheConfig(page_size=128).kv_bytes_per_token(config) == 9216


# ---- the share tied to the model --------------------------------------------


@pytest.mark.parametrize("ranks", [2, 4])
def test_the_ranks_routed_parts_and_one_identity_term_add_up(ranks):
    """One expert branch, 8 routed experts over ``ranks`` ranks and 4
    zero-compute experts on every one: the program's ``moe_branch`` on
    each rank is its share of the reference (its held experts and the
    whole identity term), and the routed parts added, with the identity
    term counted once, are the uncut layer's ``moe(x)``."""
    whole = model_config()
    params = longcat_flash.init_params(whole, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 64), jnp.float32)
    valid = jnp.ones((1, 24), bool)
    w = reference.split_branch(whole, params, 1)
    uncut = reference.model_of(whole, params)
    with jax.default_matmul_precision("highest"):
        want = reference.moe(uncut, w, x[0])
        weight, chosen = reference.choose(
            uncut, w, jax.nn.softmax(x[0] @ w["w_router"], -1))
        identity = reference.identity_term(uncut, weight, chosen, x[0])
    assert np.abs(identity).max() > 1e-2
    held = 8 // ranks
    total, used, zero = 0.0, 0, None
    for rank in range(ranks):
        part = dataclasses.replace(whole, num_experts=held,
                                   expert_parallel_size=ranks,
                                   expert_parallel_rank=rank)
        assert part.router_width == 12
        mine = slice(held * rank, held * (rank + 1))
        lp = {"router": params["router"][1],
              "router_bias": params["router_bias"][1],
              "w_gate_up": params["e_w_gate_up_1"][mine],
              "w_down": params["e_w_down_1"][mine]}
        y, load, zero = longcat_flash.moe_branch(part, lp, x, valid)
        w_share = dict(w, e_gate=w["e_gate"][mine], e_up=w["e_up"][mine],
                       e_down=w["e_down"][mine])
        with jax.default_matmul_precision("highest"):
            want_part = reference.moe(reference.model_of(part, params),
                                      w_share, x[0])
        assert np.abs(y[0] - want_part).max() < 1e-5
        total = total + (y[0] - identity)
        used += int(load.sum())
    assert used + int(zero) == 24 * 3         # every choice on one rank
    assert np.abs(total + identity - want).max() < 1e-5
    assert np.abs(want - identity).max() > 1e-3


# ---- the latent decode kernel ----------------------------------------------


def _kernel_case(lens, steps=0, dtype=jnp.float32, page=16, max_pages=8,
                 slots=4):
    keys = jax.random.split(jax.random.PRNGKey(6), 5)
    n, dn, dr, rank, dv = 4, 16, 8, 24, 16
    b = len(lens)
    plane = jax.random.normal(keys[0], (1, 1 + b * max_pages, rank + dr,
                                        page), dtype)
    w_uk = jax.random.normal(keys[1], (n, dn, rank), dtype)
    w_uv = jax.random.normal(keys[2], (n, rank, dv), dtype)
    q = jax.random.normal(keys[3], (b, n, dn + dr), dtype)
    table = (1 + jnp.arange(b * max_pages).reshape(b, max_pages)
             ).astype(jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    tail = positions = None
    if steps:
        tail = jax.random.normal(keys[4], (b, slots, 1, rank + dr), dtype)
        positions = lens + steps - 1    # the burst's step ``steps``
    return q, plane, table, lens, w_uk, w_uv, tail, positions


# Pages of 16 under a table of 8: a link of 2 pages is 32 tokens.
@pytest.mark.parametrize("lens,steps,chunk,scale", [
    ((37, 20, 5), 0, None, 24 ** -0.5),   # no tail: an eager step
    ((37, 0, 20, 0), 3, None, 24 ** -0.5),  # pad rows between live, a tail
    ((1, 1), 1, None, 24 ** -0.5),        # rows of one token
    ((16, 32, 128), 2, None, 24 ** -0.5),  # lengths on a page's edge
    ((128, 97, 3), 4, None, 24 ** -0.5),  # the table's whole width
    ((0, 0), 2, None, 24 ** -0.5),        # nothing live but the tail
    # A row whose pages end on a link's edge (64 = two links of two
    # pages), one page past it (80), one token past it (65), and a
    # tail-only row and a pad-like row between them, slots partly seen.
    ((64, 80, 0, 65, 32), 2, 2, 24 ** -0.5),
    ((64, 0, 96, 17), 0, 2, 24 ** -0.5),  # the same edges with no tail
    ((48, 0, 49, 127), 4, 3, 24 ** -0.5),  # three pages a link
    # Seven pages a link: the last link's products run over 3, 6 or 7
    # pages, whichever holds the row's last 1, 4, 7, 7 and 1 (of 8).
    ((16, 60, 100, 112, 128), 2, 7, 24 ** -0.5),
    ((33, 0, 81, 97), 0, 7, 24 ** -0.5),  # 3, 6 and 7 pages, no tail
    ((37, 0, 64, 5), 3, 2, 0.25),         # a power of two: scaled in the query
    ((37, 20, 5), 0, None, 0.125),        # the same without a tail
])
def test_the_latent_kernel_in_interpret_mode_equals_the_xla_form(
        lens, steps, chunk, scale, latent_walk_at):
    q, plane, table, lens, w_uk, w_uv, tail, pos = _kernel_case(lens, steps)
    with jax.default_matmul_precision("highest"):
        got = latent_walk_at(latent_paged_decode_attention, chunk)(
            q, plane, table, lens, w_uk, w_uv, scale, tail=tail,
            q_positions=pos, interpret=True)
        want = mla_attention.latent_paged_attention(
            q[:, None], plane, table,
            (lens - 1 if pos is None else pos)[:, None], lens, w_uk, w_uv,
            scale, tail=tail)[:, 0]
    assert got.shape == want.shape == (len(lens), 4, 16)
    live = np.asarray((lens > 0) | (steps > 0))
    assert np.abs(np.asarray(got - want)[live]).max() < INTERPRET * max(
        1.0, np.abs(np.asarray(want)[live]).max())
    assert np.isfinite(np.asarray(got)).all()


def test_the_latent_rule_sizes_a_link_for_the_one_plane():
    """The cells' plane (576 wide, pages of 128, bfloat16) under their
    table of 34 pages: twelve pages a link, three links at the most,
    however wide the table (the links are a loop); a narrow table is
    one link; the K/V kernel's rule is not this one."""
    from production_stack_tpu.ops.mla_attention_pallas import (
        LATENT_CHUNK_BYTES, latent_pages_per_chunk)
    from production_stack_tpu.ops.paged_attention_pallas import (
        pages_per_chunk)
    page_bytes = 576 * 128 * 2
    pages = latent_pages_per_chunk(576, 128, 2, 34)
    assert pages == LATENT_CHUNK_BYTES // page_bytes == 12
    assert latent_pages_per_chunk(576, 128, 2, 256) == pages
    assert latent_pages_per_chunk(576, 128, 2, 2) == 2
    assert latent_pages_per_chunk(32, 128, 2, 12) == 12
    assert latent_pages_per_chunk(576, 128, 4, 34) == pages // 2
    assert pages_per_chunk(1, 576, 128, 2, 34) == 3


@pytest.mark.parametrize("lens,steps,chunk", [
    ((1400, 129, 0, 640), 3, 3),    # links of three pages, a pad row
    ((1400, 129, 0, 640), 3, None),  # the rule: the table is one link
    ((768, 769, 0, 1152), 9, 3),    # on a link's edge, one token past it
    ((100, 1500, 0, 640, 900), 5, 8),  # last links of 1, 4, 5 and 8 pages
    ((384, 0, 1536), 0, 3),         # no tail
])
def test_the_latent_kernel_walks_pages_of_128_in_several_chunks_in_bfloat16(
        lens, steps, chunk, latent_walk_at):
    """bfloat16 pages of 128 as the cell keeps them, a table of 12
    pages: at three pages a link the longest row walks several and
    hands its buffers to the next; the tail of 16 slots is the last
    link."""
    q, plane, table, lens, w_uk, w_uv, tail, pos = _kernel_case(
        lens, steps, jnp.bfloat16, page=128, max_pages=12, slots=16)
    got = latent_walk_at(latent_paged_decode_attention, chunk)(
        q, plane, table, lens, w_uk, w_uv, 24 ** -0.5, tail=tail,
        q_positions=pos, interpret=True)
    want = mla_attention.latent_paged_attention(
        q[:, None], plane, table,
        (lens - 1 if pos is None else pos)[:, None], lens, w_uk, w_uv,
        24 ** -0.5, tail=tail)[:, 0]
    assert got.dtype == want.dtype == jnp.bfloat16
    live = np.asarray((lens > 0) | (steps > 0))
    got, want = (np.asarray(x, np.float32)[live] for x in (got, want))
    assert np.abs(got - want).max() < 0.03 * np.abs(want).max()


def test_a_power_of_two_scale_in_the_query_gives_the_same_bits():
    """GLM's 1/16 goes into the query's block once a row, LongCat's
    192 ** -0.5 stays on the float32 scores. A power of two commutes
    with every rounding on the way (the absorption's, the products',
    the float32 sums'), so the kernel at scale 1/16 gives, bit for
    bit, what it gives at scale 1 on a query that was 1/16 of this
    one from the start: the bfloat16 query is not rounded twice."""
    q, plane, table, lens, w_uk, w_uv, tail, pos = _kernel_case(
        (300, 129, 640), 2, jnp.bfloat16, page=128, max_pages=12, slots=16)
    run = lambda scale, qq: latent_paged_decode_attention(  # noqa: E731
        qq, plane, table, lens, w_uk, w_uv, scale, tail=tail,
        q_positions=pos, interpret=True)
    in_kernel = run(2.0 ** -4, q)
    by_hand = run(1.0, q * jnp.asarray(2.0 ** -4, q.dtype))
    assert in_kernel.dtype == jnp.bfloat16
    assert bool((in_kernel == by_hand).all())
    want = mla_attention.latent_paged_attention(
        q[:, None], plane, table, pos[:, None], lens, w_uk, w_uv,
        2.0 ** -4, tail=tail)[:, 0]
    assert np.abs(np.asarray(in_kernel, np.float32)
                  - np.asarray(want, np.float32)).max() < 0.03 * np.abs(
        np.asarray(want, np.float32)).max()


def test_the_walk_benchmark_parses_its_arguments_and_runs_tiny(capsys):
    """``benchmarks/latent_walk_iteration.py``: by default the three
    calls the two latent cells make at their shapes; ``--tiny`` is the
    tests' widths, run here in interpret mode at the rule's link and at
    two pages a link: the same calls, so the same sums."""
    from benchmarks import latent_walk_iteration as bench
    args = bench.parse_args([])
    assert args.shapes == bench.SHAPES and list(args.shapes) == [
        "longcat", "glm-verify", "glm-module"]
    assert (args.rows, args.page, args.table_pages, args.calls) == (
        160, 128, 34, 64)
    assert args.chunks == [None]
    assert bench.parse_args(["--chunks", "3,rule,7"]).chunks == [3, None, 7]
    lens = np.asarray(bench.draw_lengths(jax.random.PRNGKey(0), 4096))
    assert 256 <= lens.min() and lens.max() <= 4096
    assert 1500 < lens.mean() < 1850
    line = bench.main(["--tiny", "--interpret", "--calls", "2",
                       "--repeats", "1", "--chunks", "rule,2"])
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == line
    assert line["device"]["platform"] == "cpu"
    assert set(line["ms_per_call"]) == {"tiny", "tiny-verify"}
    for shape, by_chunk in line["ms_per_call"].items():
        assert set(by_chunk) == {"rule", "2"}
        assert len(by_chunk["2"]["ms"]) == 1
        assert np.isfinite(by_chunk["rule"]["checksum"])
        assert abs(by_chunk["rule"]["checksum"]
                   - by_chunk["2"]["checksum"]) < 1e-4
        assert 0 < line["kv_lens"][shape]["min"]


def test_the_pallas_path_in_interpret_mode_equals_the_xla_path():
    config, params, tokens, got = tiny()
    interpret = dataclasses.replace(config,
                                    attention_impl="pallas-interpret")
    other, _, _ = served_log_probs(interpret, params, tokens, 50, 24)
    assert np.abs(other - got).max() < INTERPRET
