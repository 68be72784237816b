"""Qwen3-Next on the normal path: Gated DeltaNet layers with a
recurrent state beside the paged cache, gated full attention with a
partial rotary embedding, and a share of the routed experts.

Tiny widths, float32, seeded, on the CPU. The oracle is the plain
reference of the family (chipbench/reference/qwen3_next_family.py),
which imports nothing of the program's models or ops and is given the
program's parameter values.

Tolerances, each with its reason:

- ``FLOAT32`` 2e-5 on log-probabilities and logits: both sides are
  float32 on one CPU with the same weights; they differ in the order
  of sums (chunkwise scan against token-by-token recurrence, grouped
  product against expert-by-expert). The readings are under 2e-6.
- ``INTERPRET`` 2e-4 between the Pallas kernels in interpret mode and
  the XLA attention at ``head_dim`` 256: the kernels keep an online
  softmax in float32 with another order of sums (what
  tests/test_pallas_attention.py allows them).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import qwen3_next_family as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    LoRAConfig,
    OffloadConfig,
    ParallelConfig,
    SchedulerConfig,
    tiny_qwen3_next_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_cache import (
    OutOfPagesError,
    PagedCacheManager,
)
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models import qwen3_next
from production_stack_tpu.models.registry import init_hybrid_cache
from production_stack_tpu.ops import gated_delta, moe
from production_stack_tpu.ops.rope import apply_rope

FLOAT32 = 2e-5
INTERPRET = 2e-4


def model_config(**over):
    config = tiny_qwen3_next_config()
    config.attention_impl = "xla"
    return dataclasses.replace(config, **over)


def engine_config(model=None, **scheduler):
    sched = dict(max_num_seqs=4, max_model_len=256, prefill_chunk_size=32,
                 prefill_batch_size=2, decode_steps=4)
    sched.update(scheduler)
    return EngineConfig(
        model=model or model_config(),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(**sched))


def prompt_of(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, 512, size=n)]


def finish(engine, seqs):
    while any(s.state.name not in ("FINISHED", "ABORTED") for s in seqs):
        engine.step()


def greedy(engine, prompts, max_tokens=9):
    ids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True))
        for p in prompts]
    seqs = [engine.sequences[i] for i in ids]
    finish(engine, seqs)
    return seqs


# ---- the model against the reference ---------------------------------------


def test_chunked_prefill_then_decode_agree_with_one_full_forward():
    """Prefill in three padded chunks, carrying S and the convolution
    tail from chunk to chunk, then six cached decode steps: the logits
    of every position agree with the reference's one forward."""
    config = model_config()
    params = qwen3_next.init_params(config, jax.random.PRNGKey(0))
    k_cache, v_cache = init_hybrid_cache(config, 32, 16, 4)
    total, prompt = 56, 50
    tokens = np.asarray(prompt_of(total, seed=1))
    want = reference.log_probs(reference.model_of(config, params),
                               tokens, list(range(total)))
    table = np.zeros((2, 8), np.int32)
    table[1, :6] = [3, 4, 5, 6, 7, 8]
    slots = jnp.array([0, 2])       # row 0 is padding: the trash slot
    forward = jax.jit(lambda *a, **k: qwen3_next.forward(
        params, config, *a, **k))
    got, start = [], 0
    while start < prompt:
        n = min(24, prompt - start)
        tok = np.zeros((2, 32), np.int32)
        pos = np.zeros((2, 32), np.int32)
        valid = np.zeros((2, 32), bool)
        tok[1, :n] = tokens[start:start + n]
        pos[1, :n] = np.arange(start, start + n)
        valid[1, :n] = True
        logits, k_cache, v_cache = forward(
            tok, pos, table, np.array([0, start + n], np.int32), valid,
            k_cache, v_cache, state_slots=slots)
        got.append(jax.nn.log_softmax(logits[1, :n]))
        start += n
    for p in range(prompt, total):
        logits, k_cache, v_cache = forward(
            np.array([[0], [tokens[p]]], np.int32),
            np.array([[0], [p]], np.int32), table,
            np.array([0, p + 1], np.int32),
            np.array([[False], [True]]), k_cache, v_cache,
            state_slots=slots)
        got.append(jax.nn.log_softmax(logits[1, :1]))
    assert np.abs(np.concatenate(got) - want).max() < FLOAT32
    # The padded row left the trash slot's neighbours alone and the
    # counters saw six decode steps of six expert layers.
    assert float(jnp.abs(k_cache[0][1]).max()) == 0.0
    assert float(k_cache[-1][0]) == 36.0


@pytest.mark.parametrize("tokens,chunk", [(1, 64), (16, 64), (50, 16),
                                          (64, 64), (150, 64)])
def test_the_chunkwise_delta_rule_equals_the_recurrence(tokens, chunk):
    """From a state that is not zero, with tokens that are not real in
    the middle of the block (no-ops) and a block that is no multiple
    of the chunk."""
    rng = np.random.RandomState(tokens)
    b, h, dk, dv = 2, 3, 8, 8
    q = gated_delta.l2_normalize(jnp.asarray(
        rng.randn(b, tokens, h, dk), jnp.float32)) * dk ** -0.5
    k = gated_delta.l2_normalize(jnp.asarray(
        rng.randn(b, tokens, h, dk), jnp.float32))
    v = jnp.asarray(rng.randn(b, tokens, h, dv), jnp.float32)
    real = jnp.asarray(rng.rand(b, tokens, 1) < 0.9)
    beta = jnp.where(real, jnp.asarray(rng.rand(b, tokens, h),
                                       jnp.float32), 0.0)
    g = jnp.where(real, -jnp.asarray(rng.rand(b, tokens, h),
                                     jnp.float32) * 2.0, 0.0)
    state = jnp.asarray(rng.randn(b, h, dk, dv), jnp.float32)
    outs, s = [], state
    for t in range(tokens):
        o, s = gated_delta.gated_delta_step(
            q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        outs.append(o)
    got, got_state = gated_delta.gated_delta_chunked(
        q, k, v, g, beta, state, chunk=chunk)
    assert np.abs(got - jnp.stack(outs, 1)).max() < FLOAT32
    assert np.abs(got_state - s).max() < FLOAT32


def test_the_decode_kernel_over_the_pool_equals_gather_step_scatter():
    """The Pallas kernel (interpret mode) reads each row's S from its
    slot, advances it and writes it back in place; against the XLA
    form. Two padded rows share the trash slot 0 and leave it as it
    was; a row that starts at position 0 starts from zero."""
    from production_stack_tpu.ops.gated_delta_pallas import (
        gated_delta_decode)
    rng = np.random.RandomState(0)
    b, h, dk, dv = 5, 4, 16, 128
    f32 = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)  # noqa
    q = gated_delta.l2_normalize(f32(b, h, dk)) * dk ** -0.5
    k = gated_delta.l2_normalize(f32(b, h, dk))
    v = f32(b, h, dv)
    padded = jnp.array([False, True, False, False, True])[:, None]
    g = jnp.where(padded, 0.0, -jnp.abs(f32(b, h)))
    beta = jnp.where(padded, 0.0, jax.nn.sigmoid(f32(b, h)))
    pool = f32(8, h, dk, dv)
    slots = jnp.array([3, 0, 5, 1, 0])
    keep = jnp.array([1.0, 1.0, 0.0, 1.0, 1.0])
    want_o, state = gated_delta.gated_delta_step(
        q, k, v, g, beta, pool[slots], keep=keep)
    got_o, got_pool = gated_delta_decode(
        q, k, v, jnp.exp(g) * keep[:, None], beta, pool, slots,
        interpret=True)
    assert np.abs(got_o - want_o).max() < FLOAT32
    assert np.abs(got_pool - pool.at[slots].set(state)).max() < FLOAT32
    assert np.array_equal(got_pool[0], pool[0])
    assert np.array_equal(got_pool[2], pool[2])          # nobody's slot


def test_the_convolution_carries_its_tail_over_real_tokens_only():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 9, 5), jnp.float32)
    w = jnp.asarray(rng.randn(4, 5), jnp.float32)
    whole, _ = gated_delta.causal_conv(
        x, jnp.zeros((2, 3, 5)), w, jnp.array([9, 9]))
    # Row 0 stops after 4 real tokens, row 1 after 6; the rest follow
    # in a second block.
    first, tail = gated_delta.causal_conv(
        x[:, :6], jnp.zeros((2, 3, 5)), w, jnp.array([4, 6]))
    second = jnp.stack([x[0, 4:9], jnp.pad(x[1, 6:9], ((0, 2), (0, 0)))])
    rest, _ = gated_delta.causal_conv(second, tail, w, jnp.array([5, 3]))
    assert np.abs(first[0, :4] - whole[0, :4]).max() < 1e-6
    assert np.abs(rest[0] - whole[0, 4:]).max() < 1e-6
    assert np.abs(rest[1, :3] - whole[1, 6:]).max() < 1e-6


# ---- the expert share ------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each of four engines holds a quarter of the experts, routes over
    all of them and computes its own experts' part; the parts, with the
    shared expert counted once, add up to what the reference gives for
    the whole layer."""
    whole = model_config()
    params = qwen3_next.init_params(whole, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 11, 64))
    valid = jnp.ones((3, 11), bool)
    layer = 1
    w = reference.split_layer(whole, params, layer)
    want = reference.sparse_block(reference.model_of(whole, params), w,
                                  x.reshape(33, 64))
    shared = (jax.nn.sigmoid(x.reshape(33, 64) @ w["w_shared_gate"])[:, None]
              * reference.expert(x.reshape(33, 64), w["s_gate"], w["s_up"],
                                 w["s_down"]))
    total, loads = shared, []
    for rank in range(4):
        share = tiny_qwen3_next_config(expert_parallel_size=4,
                                       expert_parallel_rank=rank)
        assert share.router_width == whole.num_experts == 16
        held = slice(rank * 4, rank * 4 + 4)
        lp = {k: params[k][layer] for k in qwen3_next.COMMON}
        lp["w_gate_up"] = params[f"w_gate_up_{layer}"][held]
        lp["w_down"] = params[f"w_down_{layer}"][held]
        part, load = qwen3_next.sparse_block(share, lp, x, valid)
        total = total + (part.reshape(33, 64) - shared)
        loads.append(int(load.sum()))
    assert np.abs(total - want).max() < FLOAT32
    # Every (token, choice) pair fell on exactly one share.
    assert sum(loads) == 33 * whole.num_experts_per_tok


def test_work_follows_the_held_choices_not_the_experts():
    """The grouped product takes the (token, choice) pairs that fell
    on held experts and nothing else: a token that is not real and a
    choice held elsewhere are in no group."""
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 16))
    ids = jnp.array([[0, 9], [1, 2], [8, 9], [3, 0], [2, 2], [1, 7]])
    weights = jnp.full((6, 2), 0.5)
    w_gate_up = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 8))
    w_down = jax.random.normal(jax.random.PRNGKey(2), (4, 4, 16))
    valid = jnp.array([True, True, True, True, False, True])
    y, load = moe.held_experts(x, weights, ids, w_gate_up, w_down, 0,
                               valid=valid)
    assert load.tolist() == [2, 2, 1, 1]
    assert float(jnp.abs(y[2]).max()) == 0.0      # both held elsewhere
    assert float(jnp.abs(y[4]).max()) == 0.0      # not a real token
    both = moe.held_experts(x, weights, ids, w_gate_up, w_down, 0,
                            valid=valid, impl="pallas-interpret")[0]
    assert np.abs(both - y).max() < FLOAT32


# ---- partial rotary and head_dim 256 through the kernels -------------------


def test_partial_rotary_turns_the_leading_dimensions_only():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 7, 3, 32))
    positions = jnp.arange(7)[None] + 5
    got = apply_rope(x, positions, 1e7, rotary_dim=8)
    assert np.array_equal(got[..., 8:], x[..., 8:])
    # The turned part is a head of 8 dimensions turned whole, with the
    # reference's rotate-half convention at the same positions.
    want = reference.partial_rope(
        jnp.concatenate([jnp.zeros((5, 3, 32)), x[0]]), 1e7, 8)[5:]
    assert np.abs(got[0] - want).max() < 1e-6
    assert np.array_equal(apply_rope(x, positions, 1e7, rotary_dim=32),
                          apply_rope(x, positions, 1e7))


def test_head_dim_256_through_the_pallas_kernels_in_interpret_mode():
    """The full-attention layers at the published head size, 64 of 256
    dimensions turned, through the prefill kernel (two chunks, so the
    second reads the first's pages) and the decode kernel, and the
    grouped expert product through the Pallas kernel too; against the
    XLA paths."""
    def log_probs(impl):
        config = model_config(head_dim=256, num_hidden_layers=3,
                              attention_impl=impl)
        params = qwen3_next.init_params(config, jax.random.PRNGKey(0))
        k_cache, v_cache = init_hybrid_cache(config, 6, 128, 2)
        tokens = np.asarray(prompt_of(21, seed=5))
        table = np.array([[1, 2, 0, 0]], np.int32)
        slots = jnp.array([1])
        out = []
        for start, n in ((0, 16), (16, 4)):
            pos = (np.arange(16) + start)[None].astype(np.int32)
            tok = np.zeros((1, 16), np.int32)
            tok[0, :n] = tokens[start:start + n]
            valid = (np.arange(16) < n)[None]
            logits, k_cache, v_cache = qwen3_next.forward(
                params, config, tok, np.where(valid, pos, 0), table,
                np.array([start + n], np.int32), valid, k_cache, v_cache,
                state_slots=slots)
            out.append(jax.nn.log_softmax(logits[0, :n]))
        logits, _, _ = qwen3_next.forward(
            params, config, tokens[None, 20:21],
            np.array([[20]], np.int32), table, np.array([21], np.int32),
            np.array([[True]]), k_cache, v_cache, state_slots=slots)
        out.append(jax.nn.log_softmax(logits[0]))
        return np.concatenate(out)

    assert np.abs(log_probs("pallas-interpret")
                  - log_probs("xla")).max() < INTERPRET


# ---- the kernels lower for the TPU at the published widths -----------------


def _lowers_for_tpu(fn, *shapes):
    """Cross-lower for the TPU platform from this host: Pallas's
    Mosaic rules (tiling, block shapes, scalar prefetch) run in Python
    while lowering, so a block the chip would refuse fails here
    (tests/test_pallas_lowering.py does the same for the attention
    kernels). Shapes only: nothing of this size is allocated."""
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    return text


def test_the_cells_kernels_lower_for_the_tpu_at_the_published_widths():
    from production_stack_tpu.ops.gated_delta_pallas import (
        gated_delta_decode)
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention)
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention)
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    rows, heads, dk, dv, slots = 128, 32, 128, 128, 137
    # The delta rule's step over the pool: 128 rows, a row's S 2 MB.
    _lowers_for_tpu(
        gated_delta_decode, ((rows, heads, dk), f32),
        ((rows, heads, dk), f32), ((rows, heads, dv), f32),
        ((rows, heads), f32), ((rows, heads), f32),
        ((slots, heads, dk, dv), f32), ((rows,), i32))
    # The grouped expert product: a decode step's 1280 (token, choice)
    # pairs and a prefill step's 20480 over 128 held experts.
    for pairs in (rows * 10, 8 * 256 * 10):
        _lowers_for_tpu(
            lambda x, w, ids, gate_up, down: moe.held_experts(
                x, w, ids, gate_up, down, 0, impl="pallas")[0],
            ((pairs // 10, 2048), bf16), ((pairs // 10, 10), f32),
            ((pairs // 10, 10), i32), ((128, 2048, 1024), bf16),
            ((128, 512, 2048), bf16))
    # The attention kernels at head_dim 256, 16 Q / 2 KV heads: the
    # prefill step's 8 rows x 256 tokens and the decode batch.
    cache = ((2, 2048, 256, 128), bf16)
    _lowers_for_tpu(
        paged_prefill_attention, ((8, 256, 16, 256), bf16), cache, cache,
        ((8, 64), i32), ((8, 256), i32), ((8,), i32))
    _lowers_for_tpu(
        paged_decode_attention, ((rows, 16, 256), bf16), cache, cache,
        ((rows, 64), i32), ((rows,), i32))
