"""The LFM2-MoE model and what it added to the ops: the sigmoid router
with its bias, the gated short convolution over a carried tail, the
share of an expert-parallel group tied to the whole layer, the
attention kernels at head size 64, and the grouped product at an expert
width that is no multiple of 1024 (through the engine:
tests/test_lfm2_moe_engine.py).

Tiny widths, float32, seeded, on the CPU; the oracle is the family's
plain reference (chipbench/reference/lfm2_family.py). ``FLOAT32`` 2e-5
on log-probabilities (the readings are under 2e-6); ``INTERPRET`` 2e-4
where a Pallas kernel in interpret mode sums in another order.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2_family as reference
from production_stack_tpu.engine.config import (
    ModelConfig,
    tiny_lfm2_moe_config,
)
from production_stack_tpu.models import lfm2_moe
from production_stack_tpu.models.registry import init_hybrid_cache
from production_stack_tpu.ops.moe import held_experts, route, route_sigmoid

FLOAT32 = 2e-5
INTERPRET = 2e-4


def model_config(**over):
    config = tiny_lfm2_moe_config()
    config.attention_impl = "xla"
    return dataclasses.replace(config, **over)


def prompt_of(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, 512, size=n)]


def served_log_probs(config, params, tokens, prompt, chunk):
    """Row 1 of two (row 0 is padding on the trash slot): the prompt in
    padded chunks of at most ``chunk`` real tokens, then one cached
    decode step a token. Log-softmax of every position, and the
    caches."""
    k_cache, v_cache = init_hybrid_cache(config, 32, 16, 4)
    table = np.zeros((2, 8), np.int32)
    table[1, :6] = [3, 4, 5, 6, 7, 8]
    slots = jnp.array([0, 2])
    step = jax.jit(lambda *a, **k: lfm2_moe.forward(params, config, *a, **k))
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
            k_cache, v_cache, state_slots=slots)
        got.append(jax.nn.log_softmax(logits[1, :n]))
        start += n
    for p in range(prompt, len(tokens)):
        logits, k_cache, v_cache = step(
            np.array([[0], [tokens[p]]], np.int32),
            np.array([[0], [p]], np.int32), table,
            np.array([0, p + 1], np.int32),
            np.array([[False], [True]]), k_cache, v_cache,
            state_slots=slots)
        got.append(jax.nn.log_softmax(logits[1, :1]))
    return np.concatenate(got), k_cache, v_cache


# ---- the model against the reference ---------------------------------------


@pytest.mark.parametrize("prompt,chunk", [
    (56, 56),    # one shot
    (50, 24),    # three chunks carrying the tail, then six steps
    (33, 16),    # chunks that end on a page's edge, then 23 steps
    (2, 1),      # the tail is longer than what the sequence has yet
])
def test_prefill_then_decode_agree_with_one_full_forward(prompt, chunk):
    config = model_config()
    assert config.layer_is_linear == (True, False, True, True, False)
    params = lfm2_moe.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(prompt_of(56, seed=1))
    want = reference.log_probs(reference.model_of(config, params),
                               tokens, list(range(56)))
    got, k_cache, v_cache = served_log_probs(config, params, tokens,
                                             prompt, chunk)
    assert np.abs(got - want).max() < FLOAT32
    # The row's slot holds its last two B * x; the slots of other
    # sequences hold nothing, and a conv layer has no k entry at all.
    assert k_cache[0] is None and k_cache[2] is None
    for layer in (0, 2, 3):
        tails = np.asarray(v_cache[layer])
        assert tails.shape == (5, 2, 64)
        assert np.abs(tails[2]).min() > 0
        assert not tails[1].any() and not tails[3:].any()
    assert np.asarray(k_cache[5])[0] == (56 - prompt) * 4   # expert layers


def test_the_tail_is_the_last_two_gated_inputs():
    """After a prompt of five tokens the slot holds ``B * x`` of tokens
    3 and 4, oldest first."""
    config = model_config()
    params = lfm2_moe.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(prompt_of(5, seed=2))
    _, _, v_cache = served_log_probs(config, params, tokens, 5, 16)
    x = params["embed"][tokens]
    u = lfm2_moe.rms_norm(x, params["op_norm"][0], config.rms_norm_eps)
    bcx = u @ params["c_in"][0]
    want = bcx[:, :64] * bcx[:, 128:]
    assert np.abs(np.asarray(v_cache[0])[2] - want[3:]).max() < 1e-6


# ---- the router -------------------------------------------------------------


def test_the_bias_changes_the_choice_and_not_the_weight():
    """Three experts, top-2. Scores sigmoid(2, 1, 0) = 0.881, 0.731,
    0.5: without a bias experts 0 and 1. A bias of +0.5 on expert 2
    (1.0 > 0.731) puts it in expert 1's place; its weight is its
    unbiased 0.5 over 0.881 + 0.5, not 1.0 over anything."""
    x = jnp.eye(3, dtype=jnp.float32)[:1] * 1.0
    router = jnp.array([[2.0, 1.0, 0.0], [0, 0, 0], [0, 0, 0]])
    s = jax.nn.sigmoid(jnp.array([2.0, 1.0, 0.0]))
    weights, ids = route_sigmoid(x, router, jnp.zeros(3), 2)
    assert ids.tolist() == [[0, 1]]
    np.testing.assert_allclose(
        weights[0], s[:2] / (s[0] + s[1] + 1e-6), rtol=1e-6)
    bias = jnp.array([0.0, 0.0, 0.5])
    weights, ids = route_sigmoid(x, router, bias, 2)
    assert ids.tolist() == [[2, 0]]           # by biased score: 1.0, 0.881
    np.testing.assert_allclose(
        weights[0], jnp.array([s[2], s[0]]) / (s[0] + s[2] + 1e-6),
        rtol=1e-6)
    # The softmax form beside it is what it was: probabilities, the
    # largest, divided by their sum.
    p = jax.nn.softmax(jnp.array([2.0, 1.0, 0.0]))
    weights, ids = route(x, router, 2, True)
    assert ids.tolist() == [[0, 1]]
    np.testing.assert_allclose(weights[0], p[:2] / (p[0] + p[1]), rtol=1e-6)


def test_random_init_draws_what_a_zero_or_a_one_would_switch_off():
    config = model_config()
    params = lfm2_moe.init_params(config, jax.random.PRNGKey(0))
    for name in ("op_norm", "ffn_norm", "final_norm", "q_norm", "k_norm"):
        w = np.asarray(params[name], np.float32)
        assert 0.05 < w.std() < 0.2 and abs(w.mean() - 1) < 0.05, name
    taps = np.asarray(params["c_conv"], np.float32)
    assert taps.shape == (3, 3, 64) and -0.5 <= taps.min() < -0.4
    assert 0.4 < taps.max() <= 0.5
    assert "lm_head" not in params                    # tied
    assert params["expert_bias"].dtype == jnp.float32
    # The bias changes which experts a visible share of tokens choose.
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 64))
    _, with_bias = route_sigmoid(x, params["router"][0],
                                 params["expert_bias"][0], 2)
    _, without = route_sigmoid(x, params["router"][0], jnp.zeros(8), 2)
    moved = np.mean(np.sort(with_bias, -1) != np.sort(without, -1))
    assert 0.1 < moved < 0.9


def test_the_inits_own_count_at_the_published_widths_is_the_hand_sum():
    """Shapes alone: 176 held experts, routers and biases, 18 conv and
    6 attention operators, 2 dense MLPs, the norms and the embedding
    that is the head."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs",
        "lfm2-8b-a1b-ep4.json")
    with open(path) as f:
        hf = json.load(f)
    config = ModelConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda key: lfm2_moe.init_params(config, key), jax.random.PRNGKey(0))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert count == (22 * 8 * 11010048 + 1442496 + 18 * 16783360
                     + 6 * 10485888 + 2 * 44040192 + 100352 + 134217728)
    assert count == 2526625216
    assert shapes["w_gate_up_2"].shape == (8, 2048, 3584)
    assert "w_gate_up_1" not in shapes and "w_gate_up_23" in shapes
    assert config.expert_parallel_rank * config.num_experts == 0


# ---- the share tied to the model --------------------------------------------


def test_the_four_ranks_expert_parts_add_up_to_the_uncut_layer():
    """One expert layer, 8 experts over 4 ranks of 2: the program's
    ``sparse_block`` on each rank, added, is the uncut reference's layer
    (this family has no part that every chip computes alike: no shared
    expert), and each rank's part is its share of the reference."""
    whole = model_config()
    params = lfm2_moe.init_params(whole, jax.random.PRNGKey(3))
    layer = 2                               # the second expert layer
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 64), jnp.float32)
    valid = jnp.ones((1, 24), bool)
    w = reference.split_layer(whole, params, layer)
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_block(reference.model_of(whole, params), w,
                                      x[0])
    total, used = 0.0, 0
    for rank in range(4):
        part = dataclasses.replace(whole, num_experts=2,
                                   expert_parallel_size=4,
                                   expert_parallel_rank=rank)
        assert part.router_width == 8
        lp = {"router": params["router"][layer - 1],
              "expert_bias": params["expert_bias"][layer - 1],
              "w_gate_up": params[f"w_gate_up_{layer}"][2 * rank:2 * rank + 2],
              "w_down": params[f"w_down_{layer}"][2 * rank:2 * rank + 2]}
        y, load = lfm2_moe.sparse_block(part, lp, x, valid)
        share = reference.model_of(part, params)
        w_share = dict(w, e_gate=w["e_gate"][2 * rank:2 * rank + 2],
                       e_up=w["e_up"][2 * rank:2 * rank + 2],
                       e_down=w["e_down"][2 * rank:2 * rank + 2])
        with jax.default_matmul_precision("highest"):
            want_part = reference.sparse_block(share, w_share, x[0])
        assert np.abs(y[0] - want_part).max() < 1e-5
        total = total + y[0]
        used += int(load.sum())
    assert used == 24 * 2                   # every choice on one rank
    assert np.abs(total - want).max() < 1e-5
    assert np.abs(want).max() > 1e-3


# ---- the kernels -----------------------------------------------------------


def test_the_pallas_paths_in_interpret_mode_equal_the_xla_paths():
    """Head size 64 under four query heads a KV head, as published,
    through the prefill kernel (two chunks, so the second reads the
    first's pages) and the decode kernel, and the grouped product in
    interpret mode beside them."""
    def log_probs(impl):
        config = model_config(
            hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
            head_dim=64, attention_impl=impl)
        params = lfm2_moe.init_params(config, jax.random.PRNGKey(0))
        k_cache, v_cache = init_hybrid_cache(config, 6, 128, 2)
        tokens = np.asarray(prompt_of(22, seed=5))
        table = np.array([[1, 2, 0, 0]], np.int32)
        slots = jnp.array([1])
        out = []
        for start, n in ((0, 16), (16, 4)):
            pos = (np.arange(16) + start)[None].astype(np.int32)
            tok = np.zeros((1, 16), np.int32)
            tok[0, :n] = tokens[start:start + n]
            valid = (np.arange(16) < n)[None]
            logits, k_cache, v_cache = lfm2_moe.forward(
                params, config, tok, np.where(valid, pos, 0), table,
                np.array([start + n], np.int32), valid, k_cache, v_cache,
                state_slots=slots)
            out.append(jax.nn.log_softmax(logits[0, :n]))
        for p in (20, 21):
            logits, k_cache, v_cache = lfm2_moe.forward(
                params, config, tokens[None, p:p + 1],
                np.array([[p]], np.int32), table,
                np.array([p + 1], np.int32), np.array([[True]]),
                k_cache, v_cache, state_slots=slots)
            out.append(jax.nn.log_softmax(logits[0]))
        return np.concatenate(out)

    assert np.abs(log_probs("pallas-interpret")
                  - log_probs("xla")).max() < INTERPRET


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_the_attention_kernels_at_head_64_group_4_equal_xla(kernel):
    """The kernels alone, bfloat16 pages of 128 as the cell keeps them:
    8 query heads over 2 KV heads of 64."""
    from production_stack_tpu.ops.attention import paged_attention
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention)
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    heads, kv, d, page = 8, 2, 64, 128
    k_pages = jax.random.normal(keys[0], (kv, 6, d, page), jnp.bfloat16)
    v_pages = jax.random.normal(keys[1], (kv, 6, d, page), jnp.bfloat16)
    table = jnp.array([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    if kernel == "decode":
        lens = jnp.array([300, 131], jnp.int32)
        q = jax.random.normal(keys[2], (2, 1, heads, d), jnp.bfloat16)
        got = paged_decode_attention(q[:, 0], k_pages, v_pages, table, lens,
                                     interpret=True)[:, None]
        positions = (lens - 1)[:, None]
    else:
        lens = jnp.array([272, 144], jnp.int32)
        q = jax.random.normal(keys[2], (2, 16, heads, d), jnp.bfloat16)
        positions = (lens - 16)[:, None] + jnp.arange(16)[None, :]
        got = paged_prefill_attention(q, k_pages, v_pages, table, positions,
                                      lens, interpret=True)
    want = paged_attention(q, k_pages, v_pages, table, positions, lens)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < 0.03


def _no_fitting_divisor():
    """An expert width over what one float32 tile holds beside the
    narrowest ``tn`` and 8 past a multiple of 128: no divisor of it can
    be the k tile, so the rule falls back to the constant tile and the
    kernel masks the remainder."""
    from production_stack_tpu.ops import moe
    return moe._RHS_TILE_BYTES // (128 * 4) + 8


@pytest.mark.parametrize("width", [1792, _no_fitting_divisor()], ids=[
    "a_width_that_is_the_k_tile", "a_width_with_no_fitting_divisor"])
def test_the_grouped_product_at(width):
    """Expert width 1792 = 14 x 128 is the contraction of ``w_down``:
    no multiple of 1024, and since the tiles follow the product's shape
    one k tile (no remainder, nothing masked). A width without a
    divisor that fits keeps the constant k tile 1024 and its masked
    last tile. Either way the kernel in interpret mode equals
    ``ragged_dot`` and the experts one by one."""
    from production_stack_tpu.ops.moe import expert_tiles
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    n, h, f, e = 24, 128, width, 2
    tk = expert_tiles(f, h, 4)[1]
    assert (tk == f) if f == 1792 else (tk == 1024 and f % tk == 8)
    x = jax.random.normal(keys[0], (n, h), jnp.float32)
    w_gate_up = 0.05 * jax.random.normal(keys[1], (e, h, 2 * f), jnp.float32)
    w_down = 0.05 * jax.random.normal(keys[2], (e, f, h), jnp.float32)
    ids = jax.random.randint(keys[3], (n, 2), 0, 4)   # half held elsewhere
    weights = jnp.full((n, 2), 0.5, jnp.float32)
    got, load = held_experts(x, weights, ids, w_gate_up, w_down, 0,
                             impl="pallas-interpret")
    want, want_load = held_experts(x, weights, ids, w_gate_up, w_down, 0)
    assert load.tolist() == want_load.tolist()
    assert np.abs(got - want).max() < 1e-3 * np.abs(want).max()
    by_hand = jnp.zeros_like(x)
    with jax.default_matmul_precision("highest"):
        for j in range(e):
            hidden = x @ w_gate_up[j]
            out = (jax.nn.silu(hidden[:, :f]) * hidden[:, f:]) @ w_down[j]
            by_hand += 0.5 * out * jnp.sum(ids == j, -1)[:, None]
    assert np.abs(want - by_hand).max() < 1e-3 * np.abs(by_hand).max()


# ---- the kernels lower for the TPU at the published widths -----------------


def _lowers_for_tpu(fn, *shapes):
    """Cross-lower for the TPU platform from this host (as
    tests/test_qwen3_next.py does): Mosaic's rules on tiling and block
    shapes run in Python while lowering. Shapes only."""
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_the_cells_kernels_lower_for_the_tpu_at_the_published_widths():
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention)
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention)
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    rows = 256
    # The grouped product: 256 rows x 4 choices over 8 held experts of
    # width 1792, gate | up and down.
    _lowers_for_tpu(
        lambda x, w, i, gu, dn: held_experts(x, w, i, gu, dn, 0,
                                             impl="pallas"),
        ((rows, 2048), bf16), ((rows, 4), f32), ((rows, 4), i32),
        ((8, 2048, 3584), bf16), ((8, 1792, 2048), bf16))
    # The attention kernels at 32 query heads over 8 KV heads of 64:
    # the prefill step's 16 rows x 128 tokens and the decode batch.
    cache = ((8, 4096, 64, 128), bf16)
    _lowers_for_tpu(
        paged_prefill_attention, ((16, 128, 32, 64), bf16), cache, cache,
        ((16, 32), i32), ((16, 128), i32), ((16,), i32))
    _lowers_for_tpu(
        paged_decode_attention, ((rows, 32, 64), bf16), cache, cache,
        ((rows, 32), i32), ((rows,), i32))
