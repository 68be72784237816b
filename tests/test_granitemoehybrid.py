"""Granite-MoE-hybrid (Mamba-2 mixers around no-position grouped-query
attention, held experts of a softmax router beside an ungated shared
expert in every layer, four scalar multipliers): the model against the
plain reference of the family
(chipbench/reference/granitemoehybrid_family.py), which imports nothing
of the program's models or ops and is given the program's parameter
values. The same through the engine (the scheduler, the cache
manager that owns pages and state slots, the eager and the deferred
decode burst, what start-up refuses): tests/test_granitemoehybrid_engine.py.
The recurrence's two forms and its kernel: tests/test_ssd.py.

Tiny widths, float32, seeded, on the CPU. Tolerances, each with its
reason:

- ``FLOAT32`` 2e-5 on log-probabilities: both sides are float32 on one
  CPU with the same weights and differ in the order of sums (the state
  kept ``[d_state, channels]``, a chunk's matrix form against a token
  at a time, attention over pages). The readings are under 2e-6.
- ``INTERPRET`` 2e-4 between the Pallas kernels in interpret mode and
  the XLA paths: the attention kernels keep an online softmax in
  float32 with another order of sums (what
  tests/test_pallas_attention.py allows them).
- ``LEFT_OUT`` 3e-4, three times the tiny configuration's limit on the
  worst log-probability (chipbench/rehearsal/configs/tiny-granite.json):
  a term of the layer left out or put in moves the top
  log-probabilities by more. ``TERMS`` gives each reading as its factor
  over ``LEFT_OUT``; the test holds each to half of what was read.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import granitemoehybrid_family as reference
from production_stack_tpu.engine.config import (
    ModelConfig,
    tiny_granitemoehybrid_config,
)
from production_stack_tpu.models import granitemoehybrid as granite
from production_stack_tpu.models.registry import init_hybrid_cache
from production_stack_tpu.ops import moe
from production_stack_tpu.ops.rope import apply_rope

FLOAT32 = 2e-5
INTERPRET = 2e-4
LEFT_OUT = 3e-4


def model_config(**over):
    config = tiny_granitemoehybrid_config()
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
    step = jax.jit(lambda *a, **k: granite.forward(params, config, *a, **k))
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
    (56, 56),    # one shot: seven Mamba chunks of 8 in one block
    (50, 24),    # three chunks carrying h and the tail, then six steps
    (33, 16),    # chunks that end on a page's edge, then 23 steps
])
def test_prefill_then_decode_agree_with_one_full_forward(prompt, chunk):
    config = model_config()
    assert config.layer_is_linear == (True, True, False, True)
    params = granite.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(prompt_of(56, seed=1))
    want = reference.log_probs(reference.model_of(config, params),
                               tokens, list(range(56)))
    got, k_cache, v_cache = served_log_probs(config, params, tokens,
                                             prompt, chunk)
    assert np.abs(got - want).max() < FLOAT32
    # The padded row left the trash slot's neighbours alone.
    assert float(jnp.abs(k_cache[0][1]).max()) == 0.0
    assert float(jnp.abs(v_cache[0][1]).max()) == 0.0
    assert float(jnp.abs(k_cache[0][2]).max()) > 0.0


# Each term of the layer, left out of the program or put into it, and
# what it read on the top five log-probabilities as a factor over
# LEFT_OUT (which is itself three times the tiny configuration's limit),
# at ``loud_params``; the program unchanged reads 9.5e-07 there.
TERMS = {
    "embedding_multiplier": 1087,
    "residual_multiplier": 733,
    "logits_scaling": 2363,
    "attention_multiplier": 123,
    "a rotary": 360,
    "the gate silu(z)": 359,
    "the gated norm": 288,
    "D": 290,
    "the convolution's bias": 310,
    "softmax over all experts, not renormalised": 32,
    "a gate on the shared expert": 270,
}


def loud_params(config):
    """The init's draws with the attention's and the experts' matrices
    scaled up. At 64 wide, N(0, 0.02) matrices give scores so small
    that every softmax is flat: the attention's scale and a rotary
    then move nothing, and the experts' part drowns beside an
    embedding times 12. Scaled, every term of the layer is heard; the
    reference is given the same values."""
    params = granite.init_params(config, jax.random.PRNGKey(0))
    scale = {"wq": 16.0, "wk": 16.0, "wv": 8.0, "wo": 8.0, "router": 24.0,
             "shared_gate_up": 6.0, "shared_down": 6.0}
    scale.update({f"{name}_{i}": 6.0 for name in granite.EXPERTS
                  for i in range(config.num_hidden_layers)})
    return {k: v * scale.get(k, 1.0) for k, v in params.items()}


def _changed(term, monkeypatch):
    """(config, params) of the program with one term left out or put
    in, by way of the one field, parameter or function that carries
    it."""
    config = model_config()
    params = loud_params(config)
    if term in ("embedding_multiplier", "residual_multiplier",
                "logits_scaling"):
        config = dataclasses.replace(config, **{term: 1.0})
    elif term == "attention_multiplier":
        # What every other family's attention is scaled by.
        config = dataclasses.replace(
            config, attention_multiplier=config.head_dim ** -0.5)
    elif term == "a rotary":
        real = granite.hybrid_attention

        def turned(cfg, q, k, v, k_cache, v_cache, table, positions,
                   *rest):
            return real(cfg, apply_rope(q, positions),
                        apply_rope(k, positions), v, k_cache, v_cache,
                        table, positions, *rest)
        monkeypatch.setattr(granite, "hybrid_attention", turned)
    elif term == "the gate silu(z)":
        monkeypatch.setattr(
            granite, "gated_norm",
            lambda y, z, w, eps: granite.rms_norm(y, w, eps))
    elif term == "the gated norm":
        monkeypatch.setattr(
            granite, "gated_norm",
            lambda y, z, w, eps: y * jax.nn.silu(z))
    elif term == "D":
        params["m_D"] = jnp.zeros_like(params["m_D"])
    elif term == "the convolution's bias":
        params["m_conv_b"] = jnp.zeros_like(params["m_conv_b"])
    elif term == "softmax over all experts, not renormalised":
        monkeypatch.setattr(
            granite, "route",
            lambda x, w, k, norm_topk: moe.route(x, w, k, False))
    elif term == "a gate on the shared expert":
        # As the other hybrid's shared expert has: a sigmoid of a
        # projection of the token, here at a projection of zero.
        monkeypatch.setattr(
            granite, "swiglu",
            lambda x, gate_up, down: 0.5 * moe.swiglu(x, gate_up, down))
    return config, params


@pytest.mark.parametrize("term", list(TERMS))
def test_a_term_left_out_or_put_in_fails_the_limit(term, monkeypatch):
    config = model_config()
    tokens = np.asarray(prompt_of(40, seed=2))
    want = np.asarray(reference.log_probs(
        reference.model_of(config, loud_params(config)), tokens,
        list(range(40))))
    config, params = _changed(term, monkeypatch)
    got, _, _ = served_log_probs(config, params, tokens, 30, 16)
    top = np.argsort(-want, -1)[:, :5]
    diff = np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1))
    assert diff.max() > LEFT_OUT * max(1.0, TERMS[term] / 2)


def test_the_four_ranks_expert_parts_add_up_to_the_uncut_layer():
    """The share test: one layer's expert block on each of the four
    chips of an EP-4 group (two of the eight experts each, the same
    router over all eight, the same shared expert), the shared expert
    counted once, adds up to what the reference gives with every
    expert held."""
    whole = model_config()
    params = granite.init_params(whole, jax.random.PRNGKey(0))
    ref = reference.model_of(whole, params)
    layer = ref.layer(1)
    x = jnp.asarray(np.random.RandomState(4).randn(2, 9, 64), jnp.float32)
    flat = x.reshape(18, 64)
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_block(ref, layer, flat)
        shared = reference.shared_expert(layer, flat)
    valid = jnp.ones((2, 9), bool)
    total, loads = jnp.zeros_like(flat), []
    for rank in range(4):
        config = model_config(num_experts=2, expert_parallel_size=4,
                              expert_parallel_rank=rank)
        assert config.router_width == 8
        lp = {"router": params["router"][1],
              "shared_gate_up": params["shared_gate_up"][1],
              "shared_down": params["shared_down"][1],
              "w_gate_up": params["w_gate_up_1"][2 * rank:2 * rank + 2],
              "w_down": params["w_down_1"][2 * rank:2 * rank + 2]}
        y, load = granite.sparse_block(config, lp, x, valid)
        total = total + (y.reshape(18, 64) - shared)
        loads.append(np.asarray(load))
        # No share is the whole: each leaves out what the others hold.
        assert np.abs(y.reshape(18, 64) - want).max() > 1e-3
    assert np.abs(total + shared - want).max() < FLOAT32
    # Every token's three choices fell on some chip, once.
    assert int(np.sum(loads)) == 18 * 3


def test_random_init_draws_what_a_zero_or_a_one_would_switch_off():
    config = model_config()
    params = granite.init_params(config, jax.random.PRNGKey(0))
    for name in ("attn_norm", "ffn_norm", "final_norm", "m_norm", "m_D"):
        spread = float(jnp.std(params[name].astype(jnp.float32)))
        assert 0.05 < spread < 0.2, name
        assert abs(float(jnp.mean(params[name])) - 1.0) < 0.1, name
    for name in ("m_conv", "m_conv_b"):
        assert float(jnp.abs(params[name]).max()) <= 0.5
        assert float(jnp.std(params[name])) > 0.2
    a = jnp.exp(params["m_A_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    step = jax.nn.softplus(params["m_dt_b"])
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 1e-1 + 1e-6
    assert "lm_head" not in params          # the head is the embedding


GRANITE_4_H_SMALL = dict(
    model_type="granitemoehybrid", attention_bias=False,
    attention_multiplier=0.0078125, embedding_multiplier=12,
    hidden_act="silu", hidden_size=4096, intermediate_size=768,
    layer_types=["attention" if i % 10 == 5 else "mamba"
                 for i in range(40)],
    logits_scaling=16, mamba_chunk_size=256, mamba_conv_bias=True,
    mamba_d_conv=4, mamba_d_head=64, mamba_d_state=128, mamba_expand=2,
    mamba_n_groups=1, mamba_n_heads=128, mamba_proj_bias=False,
    max_position_embeddings=131072, normalization_function="rmsnorm",
    num_attention_heads=32, num_experts_per_tok=10, num_hidden_layers=40,
    num_key_value_heads=8, num_local_experts=72,
    position_embedding_type="nope", residual_multiplier=0.22,
    rms_norm_eps=1e-5, rope_scaling=None, rope_theta=10000,
    shared_intermediate_size=1536, tie_word_embeddings=True,
    vocab_size=100352)


def test_the_published_config_is_read_as_the_family():
    config = ModelConfig.from_hf_config(GRANITE_4_H_SMALL)
    assert config.architecture == "granitemoehybrid"
    assert [i for i, m in enumerate(config.layer_is_linear) if not m] == [
        5, 15, 25, 35]
    assert (config.head_dim, config.rms_norm_eps) == (128, 1e-5)
    assert config.tie_word_embeddings
    assert (config.router_width, config.num_experts) == (72, 72)
    assert (config.moe_intermediate_size,
            config.shared_expert_intermediate_size) == (768, 1536)
    assert (config.embedding_multiplier, config.attention_multiplier,
            config.residual_multiplier, config.logits_scaling) == (
        12.0, 0.0078125, 0.22, 16.0)
    # h as [d_state, heads x d_head] float32, the tail over x | B | C.
    assert config.recurrent_state_shapes() == ((128, 8192), (3, 8448))
    assert config.recurrent_state_bytes() == 36 * (4194304 + 3 * 8448 * 2)
    # The class name names it too, and a chip's share is read as the
    # other expert families' is: the key counts the experts held.
    share = ModelConfig.from_hf_config(dict(
        GRANITE_4_H_SMALL, architectures=["GraniteMoeHybridForCausalLM"],
        num_hidden_layers=10, layer_types=GRANITE_4_H_SMALL[
            "layer_types"][:10], num_local_experts=18,
        expert_parallel_size=4, expert_parallel_rank=3))
    assert (share.architecture, share.router_width) == (
        "granitemoehybrid", 72)
    assert share.expert_parallel_rank * share.num_experts == 54
    assert share.recurrent_state_bytes() == 38204928


def test_the_inits_own_count_at_the_published_widths_is_the_hand_sum():
    """Shapes only: nothing of this size is made."""
    config = ModelConfig.from_hf_config(dict(
        GRANITE_4_H_SMALL, num_hidden_layers=10,
        layer_types=GRANITE_4_H_SMALL["layer_types"][:10],
        num_local_experts=18, expert_parallel_size=4))
    shapes = jax.eval_shape(
        lambda: granite.init_params(config, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    mamba = (4096 * (8192 + 8448 + 128) + 4 * 8448 + 8448 + 3 * 128
             + 8192 + 8192 * 4096)
    attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
    every = 18 * 3 * 4096 * 768 + 4096 * 72 + 3 * 4096 * 1536 + 2 * 4096
    assert (mamba, attention, every) == (102286976, 41943040, 189046784)
    assert count == (9 * mamba + attention + 10 * every
                     + 100352 * 4096 + 4096) == 3264039552


@pytest.mark.parametrize("change,word", [
    (dict(layer_types=["mamba", "sliding_attention"] + ["mamba"] * 38),
     r"layer_types entries \['sliding_attention'\]"),
    (dict(layer_types=["mamba"] * 39), "layer_types lists 39 layers"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias true"),
    (dict(mamba_conv_bias=False), "mamba_conv_bias false"),
    (dict(position_embedding_type="rope"),
     "position_embedding_type 'rope'"),
    (dict(mamba_n_groups=3),
     "mamba_n_groups 3, which does not divide mamba_n_heads 128"),
    (dict(mamba_n_groups=8), "mamba_n_groups 8: B and C are served"),
    (dict(mamba_d_head=32), "is not mamba_expand 2 x hidden_size 4096"),
    (dict(attention_bias=True), "attention_bias true"),
    (dict(shared_intermediate_size=0), "shared_intermediate_size 0"),
    (dict(expert_parallel_size=4, expert_parallel_rank=4),
     "expert_parallel_rank 4 is not one of"),
])
def test_a_granite_this_engine_does_not_serve_is_refused_in_words(
        change, word):
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(dict(GRANITE_4_H_SMALL, **change))


def test_a_granite_checkpoint_is_refused(tmp_path):
    from production_stack_tpu.engine.weights import load_weights
    with pytest.raises(NotImplementedError, match="--random-weights"):
        load_weights(str(tmp_path), model_config())


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_padded_and_stopped_rows_leave_their_slot_bit_identical(impl):
    """A decode step of three rows: a padded row on the trash slot, a
    row whose sequence stopped inside a burst (its own slot, not
    valid), and a live row. The first two slots hold after the step
    what they held before it, to the bit, in every Mamba layer: h and
    the tail; the live row's moved."""
    config = model_config(attention_impl=impl, attention_impl_decode="xla")
    params = granite.init_params(config, jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    k_cache, v_cache = init_hybrid_cache(config, 8, 16, 4)
    mamba = [i for i, m in enumerate(config.layer_is_linear) if m]
    k_cache, v_cache = list(k_cache), list(v_cache)
    for i in mamba:   # slots as sequences left them: full
        k_cache[i] = jnp.asarray(rng.randn(*k_cache[i].shape), jnp.float32)
        v_cache[i] = jnp.asarray(rng.randn(*v_cache[i].shape), jnp.float32)
    table = np.array([[0, 0], [1, 0], [2, 0]], np.int32)
    _, k_new, v_new = granite.forward(
        params, config, np.array([[0], [7], [9]], np.int32),
        np.array([[0], [5], [5]], np.int32), table,
        np.array([0, 5, 6], np.int32),
        np.array([[False], [False], [True]]), tuple(k_cache),
        tuple(v_cache), state_slots=jnp.array([0, 2, 3]))
    for i in mamba:
        for before, after in ((k_cache[i], k_new[i]),
                              (v_cache[i], v_new[i])):
            assert np.array_equal(after[0], before[0])    # trash slot
            assert np.array_equal(after[2], before[2])    # stopped
            assert np.array_equal(after[1], before[1])    # nobody's
            assert not np.array_equal(after[3], before[3])
    # One live row, three choices, four layers of experts.
    assert [float(v) for v in k_new[4]] == [4.0, 12.0, 12.0, 4.0, 12.0,
                                            0.0]


def test_the_pallas_paths_in_interpret_mode_equal_the_xla_paths():
    """Two query heads a KV head through the prefill kernel (two
    chunks, so the second reads the first's pages) and the decode
    kernel, the recurrence's decode kernel over the pool, and the
    grouped expert product."""
    def log_probs(impl):
        config = model_config(head_dim=128, attention_impl=impl)
        params = granite.init_params(config, jax.random.PRNGKey(0))
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
            logits, k_cache, v_cache = granite.forward(
                params, config, tok, np.where(valid, pos, 0), table,
                np.array([start + n], np.int32), valid, k_cache, v_cache,
                state_slots=slots)
            out.append(jax.nn.log_softmax(logits[0, :n]))
        for p in (20, 21):
            logits, k_cache, v_cache = granite.forward(
                params, config, tokens[None, p:p + 1],
                np.array([[p]], np.int32), table,
                np.array([p + 1], np.int32), np.array([[True]]),
                k_cache, v_cache, state_slots=slots)
            out.append(jax.nn.log_softmax(logits[0]))
        return np.concatenate(out)

    assert np.abs(log_probs("pallas-interpret")
                  - log_probs("xla")).max() < INTERPRET


def _lowers_for_tpu(fn, *shapes):
    """Cross-lower for the TPU platform from this host (as
    tests/test_qwen3_next.py does): Mosaic's rules on tiling and block
    shapes run in Python while lowering. Shapes only."""
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_the_cells_kernels_lower_for_the_tpu_at_the_published_widths():
    """The attention kernels at 8 KV heads under 32 (the prefill step's
    8 rows x 128 tokens and the decode batch) and the grouped product
    at 18 experts of width 768 (n = 1536 and k = 768, shapes no other
    cell has); the recurrence's kernel: tests/test_ssd.py."""
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention)
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention)
    bf16, i32 = jnp.bfloat16, jnp.int32
    cache = ((8, 4096, 128, 128), bf16)
    _lowers_for_tpu(
        paged_prefill_attention, ((8, 128, 32, 128), bf16), cache, cache,
        ((8, 33), i32), ((8, 128), i32), ((8,), i32))
    _lowers_for_tpu(
        paged_decode_attention, ((128, 32, 128), bf16), cache, cache,
        ((128, 33), i32), ((128,), i32))
    _lowers_for_tpu(
        lambda x, w, ids, up, down: moe.held_experts(
            x, w, ids, up, down, 0, impl="pallas")[0],
        ((128, 4096), bf16), ((128, 10), jnp.float32), ((128, 10), i32),
        ((18, 4096, 1536), bf16), ((18, 768, 4096), bf16))
