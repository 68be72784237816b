"""The GLM-4 MoE lite model and what it added to the ops: one latent-
attention sublayer a layer with both low-rank scales off, a leading
dense layer, the sigmoid router's second published form (epsilon 1e-20,
weights times ``routed_scaling_factor``), a shared expert added whole,
the multi-token-prediction module with a cache entry of its own, and
the latent kernel's verify form (``T`` query positions over one walk of
a row's pages). Through the engine:
tests/test_glm4_moe_lite_engine.py.

Tiny widths, float32, seeded, on the CPU; the oracle is the family's
plain reference (chipbench/reference/glm4_moe_lite_family.py:
materialised attention, no cache, no drafting). The served path is
``benchmarks/mtp_forced_acceptance.py`` ``served_log_probs``: the
family's ``forward`` and ``draft`` called as the runner calls them
(prefill chunks through the pages that also fill the module's entry,
then bursts of verify iterations of two positions a row over pages and
tails, flushed by count), on a sequence whose every draft is the token
that follows, so that every draft is accepted. ``FLOAT32`` 2e-5 on
log-probabilities: both sides float32 on one CPU with the same weights,
differing in the order of sums (the readings are under 2e-6);
``INTERPRET`` 2e-4 where a Pallas kernel in interpret mode sums in
another order. A term left out or put in has to fail ``FLOAT32`` by
``CLEAR`` = 100 times.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.mtp_forced_acceptance import served_log_probs
from chipbench.reference import glm4_moe_lite_family as reference
from production_stack_tpu.engine.config import (
    ModelConfig,
    tiny_glm4_moe_lite_config,
)
from production_stack_tpu.models import glm4_moe_lite
from production_stack_tpu.ops import mla_attention
from production_stack_tpu.ops.mla_attention_pallas import (
    latent_paged_verify_attention,
)
from production_stack_tpu.ops.moe import route_sigmoid

FLOAT32 = 2e-5
INTERPRET = 2e-4
CLEAR = 100
PROMPT, TOTAL = 50, 75


def model_config(**over):
    config = tiny_glm4_moe_lite_config()
    config.attention_impl = "xla"
    return dataclasses.replace(config, **over)


@pytest.fixture(scope="module")
def tiny():
    """(config, params, tokens, served main, served module, positions):
    a prompt of 50 in chunks of 24, then two positions an iteration."""
    config = model_config()
    params = glm4_moe_lite.init_params(config, jax.random.PRNGKey(0))
    # Wider than the init's, a fifth of the scores' spread at this
    # width: a sequence of 75 tokens must meet flipped choices.
    params = dict(params, router_bias=0.02 * jax.random.normal(
        jax.random.PRNGKey(9), params["router_bias"].shape, jnp.float32))
    tokens = [int(t) for t in
              np.random.RandomState(0).randint(0, 512, size=TOTAL)]
    main, module, n = served_log_probs(config, params, tokens, PROMPT, 24)
    return config, params, tokens, main, module, n


def reference_pair(config, params, tokens, n, **levers):
    m = dataclasses.replace(reference.model_of(config, params), **levers)
    positions = list(range(n))
    return (np.asarray(reference.log_probs(m, tokens, positions)),
            np.asarray(reference.draft_log_probs(m, tokens, positions)))


@pytest.mark.parametrize("impl,chunk,limit", [
    ("xla", 24, FLOAT32), ("xla", 64, FLOAT32),
    ("pallas-interpret", 24, INTERPRET)])
def test_chunks_pages_tails_and_drafts_agree_with_one_full_forward(
        impl, chunk, limit):
    """Logits, not tokens: the main model's after every position (a
    prompt of two or three chunks, or one; then both positions of every
    verify iteration, the second being an accepted draft's) and the
    module's ``q`` for the token two after every position, against the
    reference's full forward pass. ``pallas-interpret`` is what ``auto``
    resolves on the chip: the latent kernel's verify form beside the
    tails."""
    config = model_config(attention_impl=impl)
    params = glm4_moe_lite.init_params(config, jax.random.PRNGKey(0))
    tokens = [int(t) for t in
              np.random.RandomState(1).randint(0, 512, size=TOTAL)]
    main, module, n = served_log_probs(config, params, tokens, PROMPT,
                                       chunk)
    assert n == TOTAL - 1
    want_main, want_module = reference_pair(config, params, tokens, n)
    assert np.abs(main - want_main).max() < limit
    assert np.abs(module - want_module).max() < limit


# ---- each term left out or put in wrongly ------------------------------------


def _choose(bias_in_weight=False, renormalise=True, eps=reference.ROUTER_EPS):
    def choose(m, w, scores):
        by = scores + w["router_bias"]
        _, chosen = jax.lax.top_k(by, m.top_k)
        kept = jnp.take_along_axis(by if bias_in_weight else scores,
                                   chosen, axis=-1)
        if renormalise:
            kept = kept / (jnp.sum(kept, -1, keepdims=True) + eps)
        return m.routed_scale * kept, chosen
    return choose


def _no_shared(m, w, x):
    zero = jnp.zeros_like(w["s_down"])
    return reference_expert_block(m, dict(w, s_down=zero), x)


reference_expert_block = reference.expert_block
reference_layer = reference.layer_forward
reference_module = reference.module_hidden


def _dense_layer_left_out(m, i, h):
    if i >= m.num_dense_layers:
        return reference_layer(m, i, h)
    w = m.layer(i)
    return h + reference.attention(
        m, w, reference.norm(h, w["attn_norm"], m.rms_eps))


def _norms_swapped(m, tokens, hidden):
    module = dict(m.module, enorm=m.module["hnorm"], hnorm=m.module["enorm"])
    return reference_module(dataclasses.replace(m, module=module), tokens,
                            hidden)


def _halves_swapped(m, tokens, hidden):
    h = hidden.shape[-1]
    proj = m.module["eh_proj"]
    module = dict(m.module,
                  eh_proj=jnp.concatenate([proj[h:], proj[:h]]))
    return reference_module(dataclasses.replace(m, module=module), tokens,
                            hidden)


# name -> (what is patched in the reference, which side must move)
WRONG = {
    "the bias leaking into the weights": (
        ("choose", _choose(bias_in_weight=True)), "main"),
    "the chosen weights not renormalised": (
        ("choose", _choose(renormalise=False)), "main"),
    "the sum's epsilon lfm2's 1e-6 (too small to see: the control)": (
        ("choose", _choose(eps=1e-6)), None),
    "the shared expert left out": (("expert_block", _no_shared), "main"),
    "the dense first layer left out": (
        ("layer_forward", _dense_layer_left_out), "main"),
    "hnorm and enorm swapped": (("module_hidden", _norms_swapped),
                                "module"),
    "eh_proj's halves swapped": (("module_hidden", _halves_swapped),
                                 "module"),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_a_term_left_out_or_put_in_fails_the_limit_clearly(wrong, tiny,
                                                           monkeypatch):
    """The program against the reference with one term changed: the
    worst log-probability moves by over ``CLEAR`` times the limit, of
    the main model where the term is a layer's, of the module's ``q``
    alone where it is the module's. The control (an epsilon of 1e-6
    under a sum of three scores near a half) moves neither: the limit
    does not see it, and the router's hand computation below does."""
    config, params, tokens, main, module, n = tiny
    patch, side = WRONG[wrong]
    monkeypatch.setattr(reference, *patch)
    want_main, want_module = reference_pair(config, params, tokens, n)
    moved_main = np.abs(main - want_main).max()
    moved_module = np.abs(module - want_module).max()
    if side == "main":
        assert moved_main > CLEAR * FLOAT32, wrong
    elif side == "module":
        assert moved_main < FLOAT32
        assert moved_module > CLEAR * FLOAT32, wrong
    else:
        assert max(moved_main, moved_module) < FLOAT32


def test_the_routed_scaling_factor_left_out_fails_the_limit_clearly(tiny):
    config, params, tokens, main, _, n = tiny
    want_main, _ = reference_pair(config, params, tokens, n,
                                  routed_scale=1.0)
    assert np.abs(main - want_main).max() > CLEAR * FLOAT32


@pytest.mark.parametrize("wrong,lever", [
    ("the module fed t_i for t_{i+1}", dict(module_tokens_shift=0)),
    ("the module's cache left unfilled over the prompt",
     dict(fill_module=False)),
    ("the main model's cache served to the module",
     dict(module_entry=2)),
])
def test_a_wrong_program_around_the_module_fails_the_limit_clearly(
        wrong, lever, tiny):
    """The program itself run wrongly (the levers of
    ``served_log_probs``): the main model's numbers stay, the module's
    ``q`` moves by over ``CLEAR`` times the limit at the decode
    positions, where its attention reads what the prompt left in its
    cache."""
    config, params, tokens, main, module, n = tiny
    got_main, got_module, _ = served_log_probs(
        config, params, tokens, PROMPT, 24, **lever)
    assert np.abs(got_main - main).max() < FLOAT32
    assert np.abs(got_module[PROMPT:] - module[PROMPT:]
                  ).max() > CLEAR * FLOAT32, wrong


def test_the_reference_passes_before_any_term_is_changed(tiny):
    config, params, tokens, main, module, n = tiny
    want_main, want_module = reference_pair(config, params, tokens, n)
    assert np.abs(main - want_main).max() < FLOAT32
    assert np.abs(module - want_module).max() < FLOAT32


# ---- the router and the shared expert ----------------------------------------


def test_the_router_against_a_hand_computation():
    """Four experts, two chosen: the bias picks, the scores weigh,
    their sum (+ 1e-20) divides, 1.8 multiplies."""
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.5, 1.0, 0.4]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.5])
    weights, ids = route_sigmoid(x, router, bias, 2, scale=1.8, eps=1e-20)
    s = 1 / (1 + np.exp(-np.asarray(router)))
    # Token 0: scores .881 .731 .5 .269; with the bias the last reads
    # .769 and takes second place from .731.
    assert ids.tolist()[0] == [0, 3]
    np.testing.assert_allclose(
        weights[0], 1.8 * s[0, [0, 3]] / (s[0, 0] + s[0, 3]), rtol=1e-6)
    # Token 1: .5 .622 .731 .599 (+.5 = 1.099): the biased one first,
    # weighed by its score without the bias.
    assert ids.tolist()[1] == [3, 2]
    np.testing.assert_allclose(
        weights[1], 1.8 * s[1, [3, 2]] / (s[1, 3] + s[1, 2]), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), [1.8, 1.8], rtol=1e-6)
    # lfm2_moe's form, the defaults: no scale, epsilon 1e-6.
    plain, same = route_sigmoid(x, router, bias, 2)
    assert same.tolist() == ids.tolist()
    np.testing.assert_allclose(
        plain[0], s[0, [0, 3]] / (s[0, 0] + s[0, 3] + 1e-6), rtol=1e-6)


def test_the_shared_expert_is_added_whole_beside_the_routed_sum():
    """``expert_block`` with every routed expert zeroed is the shared
    expert alone, unweighted; with the shared one zeroed, the routed
    sum's weights add up to 1.8 of an expert that all eight share."""
    config = model_config()
    h, f = config.hidden_size, config.moe_intermediate_size
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    u = jax.random.normal(keys[0], (2, 5, h))
    one_gate_up = 0.1 * jax.random.normal(keys[1], (h, 2 * f))
    one_down = 0.1 * jax.random.normal(keys[2], (f, h))
    lp = {"router": 0.1 * jax.random.normal(keys[3], (h, 8)),
          "router_bias": jnp.zeros((8,)),
          "w_gate_up": jnp.tile(one_gate_up[None], (8, 1, 1)),
          "w_down": jnp.tile(one_down[None], (8, 1, 1)),
          "shared_gate_up": 0.1 * jax.random.normal(keys[4], (h, 2 * f)),
          "shared_down": 0.1 * jax.random.normal(keys[5], (f, h))}
    valid = jnp.ones((2, 5), bool)

    def swiglu(x, gate_up, down):
        hid = x @ gate_up
        return (jax.nn.silu(hid[..., :f]) * hid[..., f:]) @ down

    both, load = glm4_moe_lite.expert_block(config, lp, u, valid)
    shared = swiglu(u, lp["shared_gate_up"], lp["shared_down"])
    routed = 1.8 * swiglu(u, one_gate_up, one_down)
    assert np.abs(both - (shared + routed)).max() < FLOAT32
    assert int(load.sum()) == 2 * 5 * config.num_experts_per_tok
    alone, _ = glm4_moe_lite.expert_block(
        config, dict(lp, w_down=jnp.zeros_like(lp["w_down"])), u, valid)
    assert np.abs(alone - shared).max() < FLOAT32


def test_the_bias_moves_a_visible_share_of_choices_at_the_published_width():
    """A router of 2048 x 64 of N(0, 0.02) and the init's bias of N(0,
    1e-2): the fourth and fifth scores of a token are 1.3e-2 apart at
    the median, and the bias moves 5 to 12% of the choices, 20 to 45%
    of the tokens' chosen sets (read: 7.8%, 31%)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (1024, 2048)) * (
        1 + 0.1 * jax.random.normal(keys[1], (2048,)))
    router = 0.02 * jax.random.normal(keys[2], (2048, 64))
    scores = jax.nn.sigmoid(x @ router)
    ranked = -jnp.sort(-scores, axis=-1)
    assert 8e-3 < float(jnp.median(ranked[:, 3] - ranked[:, 4])) < 2e-2
    bias = 1e-2 * jax.random.normal(keys[3], (64,))
    _, plain = route_sigmoid(x, router, jnp.zeros((64,)), 4)
    _, biased = route_sigmoid(x, router, bias, 4)
    moved = [len(set(a) - set(b)) for a, b in
             zip(np.asarray(plain).tolist(), np.asarray(biased).tolist())]
    assert 0.05 < np.mean(moved) / 4 < 0.12
    assert 0.20 < np.mean(np.asarray(moved) > 0) < 0.45


def test_random_init_draws_what_a_zero_or_a_one_would_switch_off():
    config = model_config()
    params = glm4_moe_lite.init_params(config, jax.random.PRNGKey(0))
    for name in ("attn_norm", "q_a_norm", "kv_a_norm", "ffn_norm",
                 "final_norm", "mtp_enorm", "mtp_hnorm", "mtp_head_norm"):
        assert 0.05 < float(jnp.std(params[name])) < 0.2, name
    assert params["router_bias"].dtype == jnp.float32
    assert 5e-3 < float(jnp.std(params["router_bias"])) < 2e-2


def test_the_main_model_is_the_same_with_the_module_and_without():
    """The served switch compares one model with itself: the module's
    weights come from a key of their own."""
    with_module = glm4_moe_lite.init_params(model_config(),
                                            jax.random.PRNGKey(4))
    without = glm4_moe_lite.init_params(
        model_config(num_nextn_predict_layers=0), jax.random.PRNGKey(4))
    assert not any(name.startswith("mtp_") or name.endswith("_3")
                   for name in without)
    for name, value in without.items():
        kept = with_module[name][:value.shape[0]]
        assert kept.shape == value.shape and bool((kept == value).all())


def test_the_inits_own_count_at_the_published_widths_is_the_hand_sum():
    """ISSUE 43's arithmetic, from the shapes the init makes for the
    benchmark's configuration (no value is drawn)."""
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs",
        "glm-4.7-flash-pp8.json")
    with open(path) as f:
        hf = json.load(f)
    hf.pop("chipbench")
    config = ModelConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda key: glm4_moe_lite.init_params(config, key),
        jax.random.PRNGKey(0))
    total = sum(int(np.prod(s.shape)) for s in shapes.values())
    mla = (2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512
           + 512 * 20 * (192 + 256) + 5120 * 2048)
    assert mla == 21_759_232
    expert = 3 * 2048 * 1536
    outside = mla + expert + 2048 * 64 + 64 + 2 * 2048
    assert outside == 31_331_648
    layer = outside + 64 * expert
    assert layer == 635_311_424
    dense = mla + 3 * 2048 * 10240 + 2 * 2048
    assert dense == 84_677_888
    module = layer + 2 * 2048 * 2048 + 3 * 2048
    assert module == 643_706_176
    head = 154880 * 2048
    assert total == dense + 6 * layer + module + 2 * head + 2048
    assert total == 5_174_643_136
    assert config.page_cache.entries == 8
    assert config.page_cache.width == 576


def test_from_hf_config_refuses_in_words_what_it_does_not_serve():
    base = dict(architectures=["Glm4MoeLiteForCausalLM"], vocab_size=64,
                hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                num_attention_heads=2, kv_lora_rank=8, q_lora_rank=8,
                qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                n_routed_experts=4, num_experts_per_tok=2,
                moe_intermediate_size=16, first_k_dense_replace=1,
                num_nextn_predict_layers=1, routed_scaling_factor=1.8)
    config = ModelConfig.from_hf_config(base)
    assert (config.architecture, config.num_dense_layers,
            config.num_nextn_predict_layers, config.has_draft_module) == (
        "glm4_moe_lite", 1, 1, True)
    assert config.routed_scaling_factor == 1.8
    for key, value, said in (
            ("n_group", 2, "no group limit"),
            ("rope_scaling", {"type": "yarn"}, "unscaled"),
            ("num_nextn_predict_layers", 2, "one prediction layer"),
            ("attention_bias", True, "without a bias"),
            ("norm_topk_prob", False, "divided by their sum")):
        with pytest.raises(ValueError, match=said):
            ModelConfig.from_hf_config({**base, key: value})


# ---- the latent kernel's verify form -------------------------------------------


def _verify_case(lens, counts, t=2, dtype=jnp.float32, page=16, max_pages=8,
                 slots=8):
    keys = jax.random.split(jax.random.PRNGKey(6), 5)
    n, dn, dr, rank, dv = 4, 16, 8, 24, 16
    b = len(lens)
    plane = jax.random.normal(keys[0], (1, 1 + b * max_pages, rank + dr,
                                        page), dtype)
    w_uk = jax.random.normal(keys[1], (n, dn, rank), dtype)
    w_uv = jax.random.normal(keys[2], (n, rank, dv), dtype)
    q = jax.random.normal(keys[3], (b, t, n, dn + dr), dtype)
    table = (1 + jnp.arange(b * max_pages).reshape(b, max_pages)
             ).astype(jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    tail = jax.random.normal(keys[4], (b, slots, 1, rank + dr), dtype)
    # Each row's own tail count: its positions start there.
    positions = (lens + jnp.asarray(counts, jnp.int32))[:, None] + jnp.arange(t)
    return q, plane, table, lens, w_uk, w_uv, tail, positions


@pytest.mark.parametrize("lens,counts,chunk,scale", [
    ((37, 20, 5), (0, 3, 6), None, 24 ** -0.5),  # rows at their own counts
    ((37, 0, 20, 0), (2, 0, 5, 1), None, 24 ** -0.5),  # pad rows between
    ((16, 32, 128), (1, 1, 1), None, 24 ** -0.5),  # lengths on a page's edge
    ((128, 97, 3), (6, 0, 4), None, 24 ** -0.5),  # the table's whole width
    ((0, 0), (0, 2), None, 24 ** -0.5),          # nothing in the pages
    # Links of two pages of 16: a row that ends on a link's edge, one
    # page past it, a tail-only row between live rows, a pad-like row.
    ((64, 80, 0, 65, 0), (0, 5, 3, 6, 0), 2, 24 ** -0.5),
    ((48, 0, 49, 127), (1, 4, 0, 2), 3, 24 ** -0.5),  # three pages a link
    # Seven pages a link: last links of 1, 4, 7, 7 and 1 pages run
    # their products over 3, 6 or 7.
    ((16, 60, 100, 112, 128), (0, 2, 6, 1, 3), 7, 24 ** -0.5),
    ((37, 0, 64, 5), (2, 0, 6, 1), 2, 0.25),     # the scale in the query
])
def test_the_verify_form_in_interpret_mode_equals_the_xla_form(
        lens, counts, chunk, scale, latent_walk_at):
    """Two positions a row over ONE walk of its pages (the 2 x 4 heads
    are the kernel's rows), the causal cut between them in the tail,
    the walk's last link: slot s is position ``kv_lens + s``, so the
    first position's band of rows sees slots up to its own and the
    second's one more."""
    q, plane, table, lens, w_uk, w_uv, tail, pos = _verify_case(lens, counts)
    with jax.default_matmul_precision("highest"):
        got = latent_walk_at(latent_paged_verify_attention, chunk)(
            q, plane, table, lens, w_uk, w_uv, scale, tail=tail,
            q_positions=pos, interpret=True)
        want = mla_attention.latent_paged_attention(
            q, plane, table, pos, lens, w_uk, w_uv, scale, tail=tail)
    assert got.shape == want.shape == (len(lens), 2, 4, 16)
    assert np.abs(np.asarray(got - want)).max() < INTERPRET * max(
        1.0, np.abs(np.asarray(want)).max())
    assert np.isfinite(np.asarray(got)).all()


def test_the_verify_form_at_three_positions_cuts_each_band_at_its_own():
    """``T`` is the shape's: three positions a row are three bands of
    the one walk, and a tail of 5 slots is padded to the sublane tile
    with slots no position sees."""
    q, plane, table, lens, w_uk, w_uv, tail, pos = _verify_case(
        (37, 0, 64), (1, 0, 2), t=3, slots=5)
    scale = 24 ** -0.5
    with jax.default_matmul_precision("highest"):
        got = latent_paged_verify_attention(
            q, plane, table, lens, w_uk, w_uv, scale, tail=tail,
            q_positions=pos, interpret=True)
        want = mla_attention.latent_paged_attention(
            q, plane, table, pos, lens, w_uk, w_uv, scale, tail=tail)
    assert got.shape == want.shape == (3, 3, 4, 16)
    assert np.abs(np.asarray(got - want)).max() < INTERPRET * max(
        1.0, np.abs(np.asarray(want)).max())


def test_the_second_position_sees_one_tail_slot_more_than_the_first():
    """Change the tail slot at the second position: the first position's
    output stays to the last bit, the second's moves."""
    q, plane, table, lens, w_uk, w_uv, tail, pos = _verify_case(
        (20, 33), (2, 4))
    run = lambda t: latent_paged_verify_attention(  # noqa: E731
        q, plane, table, lens, w_uk, w_uv, 24 ** -0.5, tail=t,
        q_positions=pos, interpret=True)
    before = run(tail)
    slot = jnp.asarray([3, 5])          # counts + 1: the drafts' slots
    after = run(tail.at[jnp.arange(2), slot].add(1.0))
    assert bool((before[:, 0] == after[:, 0]).all())
    assert float(jnp.abs(before[:, 1] - after[:, 1]).max()) > 1e-3


@pytest.mark.parametrize("lens,counts,chunk", [
    ((1400, 129, 0, 640), (5, 0, 2, 7), None),  # the rule: one link
    ((1400, 129, 0, 640), (5, 0, 2, 7), 3),     # links of three pages
    ((768, 769, 0, 1152), (0, 9, 3, 14), 3),    # on a link's edge, past it
    ((100, 1500, 0, 640), (2, 0, 5, 7), 8),     # last links of 1, 4, 5 pages
])
def test_the_verify_form_walks_pages_of_128_in_bfloat16(lens, counts, chunk,
                                                        latent_walk_at):
    q, plane, table, lens, w_uk, w_uv, tail, pos = _verify_case(
        lens, counts, dtype=jnp.bfloat16, page=128, max_pages=12, slots=16)
    got = latent_walk_at(latent_paged_verify_attention, chunk)(
        q, plane, table, lens, w_uk, w_uv, 24 ** -0.5, tail=tail,
        q_positions=pos, interpret=True)
    want = mla_attention.latent_paged_attention(
        q, plane, table, pos, lens, w_uk, w_uv, 24 ** -0.5, tail=tail)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < 0.03 * np.abs(
        np.asarray(want, np.float32)).max()