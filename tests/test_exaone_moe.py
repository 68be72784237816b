"""EXAONE-MoE (windowed layers whose K/V is a ring in the state pool
around full layers over pages, head norms, a rotary on the windowed
layers alone, norms on each sublayer's output, a leading dense layer,
held experts of a sigmoid router beside a shared expert): the model
against the plain reference of the family
(chipbench/reference/exaone_moe_family.py), which imports nothing of
the program's models or ops and is given the program's parameter
values. The same through the engine (the scheduler, the cache manager
that owns pages and slots, the eager and the deferred burst, what
start-up refuses): tests/test_exaone_moe_engine.py. The window's three
forms alone: tests/test_window_attention.py.

Tiny widths (a window of 16, one tiny page), float32, seeded, on the
CPU. Tolerances, each with its reason:

- ``FLOAT32`` 2e-5 on log-probabilities: both sides are float32 on one
  CPU with the same weights and differ in the order of sums (a ring
  and a chunk in one softmax against one ``[T, T]`` mask, attention
  over pages). The readings are under 2e-6.
- ``INTERPRET`` 2e-4 between the Pallas kernels in interpret mode and
  the XLA paths: the attention kernels keep an online softmax in
  float32 with another order of sums (what
  tests/test_pallas_attention.py allows them).
- ``LEFT_OUT`` 3e-4, three times the tiny configuration's limit on the
  worst log-probability (chipbench/rehearsal/configs/tiny-exaone.json):
  a term of the layer left out or put in wrongly moves the top
  log-probabilities by more. ``TERMS`` gives each reading as its factor
  over ``LEFT_OUT``; the test holds each to half of what was read.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import exaone_moe_family as reference
from production_stack_tpu.engine.config import (
    ModelConfig,
    tiny_exaone_moe_config,
)
from production_stack_tpu.models import exaone_moe as exaone
from production_stack_tpu.models import glm4_moe_lite
from production_stack_tpu.models.registry import init_hybrid_cache
from production_stack_tpu.ops import moe
from production_stack_tpu.ops import window_attention as wa
from production_stack_tpu.ops.attention import write_to_pages
from production_stack_tpu.ops.rope import apply_rope

FLOAT32 = 2e-5
INTERPRET = 2e-4
LEFT_OUT = 3e-4
LENGTH = 72


def model_config(**over):
    config = tiny_exaone_moe_config()
    config.attention_impl = "xla"
    return dataclasses.replace(config, **over)


def prompt_of(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, 512, size=n)]


class Served:
    """Row 1 of two (row 0 is padding on the trash slot) through the
    program: prompt chunks, eager single steps and deferred bursts, on
    six pages of 16 and slot 2 of four."""

    def __init__(self, config, params, page_size=16, pages=32):
        self.config, self.params = config, params
        self.k_cache, self.v_cache = init_hybrid_cache(
            config, pages, page_size, 4)
        self.table = np.zeros((2, 8), np.int32)
        self.table[1, :6] = [3, 4, 5, 6, 7, 8]
        self.slots = jnp.array([0, 2])
        self.step = jax.jit(
            lambda *a, **k: exaone.forward(params, config, *a, **k),
            static_argnames=())
        self.out = []

    def chunks(self, tokens, start, end, chunk):
        width = -(-chunk // 16) * 16
        while start < end:
            n = min(chunk, end - start)
            tok = np.zeros((2, width), np.int32)
            pos = np.zeros((2, width), np.int32)
            valid = np.zeros((2, width), bool)
            tok[1, :n] = tokens[start:start + n]
            pos[1, :n] = np.arange(start, start + n)
            valid[1, :n] = True
            logits, self.k_cache, self.v_cache = self.step(
                tok, pos, self.table, np.array([0, start + n], np.int32),
                valid, self.k_cache, self.v_cache, state_slots=self.slots)
            self.out.append(jax.nn.log_softmax(logits[1, :n]))
            start += n

    def steps(self, tokens, start, end):
        for p in range(start, end):
            logits, self.k_cache, self.v_cache = self.step(
                np.array([[0], [tokens[p]]], np.int32),
                np.array([[0], [p]], np.int32), self.table,
                np.array([0, p + 1], np.int32),
                np.array([[False], [True]]), self.k_cache, self.v_cache,
                state_slots=self.slots)
            self.out.append(jax.nn.log_softmax(logits[1, :1]))

    def burst(self, tokens, start, end):
        """A deferred-write burst as the runner makes it: planes and
        rings read and not written, every layer's K/V on a tail, one
        flush: a full layer's tail to its pages, a windowed layer's to
        its ring's places."""
        c, steps = self.config, end - start
        held = np.array([0, start], np.int32)
        shape = (2, steps, c.num_key_value_heads, c.head_dim)
        layers = c.num_hidden_layers
        k_tail = tuple(jnp.zeros(shape) for _ in range(layers))
        v_tail = tuple(jnp.zeros(shape) for _ in range(layers))
        stats = self.k_cache[layers]
        for p in range(start, end):
            logits, kt, v_tail = self.step(
                np.array([[0], [tokens[p]]], np.int32),
                np.array([[0], [p]], np.int32), self.table, held,
                np.array([[False], [True]]),
                self.k_cache[:layers] + (stats,), self.v_cache,
                kv_tail=(k_tail, v_tail), state_slots=self.slots)
            k_tail, stats = kt[:layers], kt[layers]
            self.out.append(jax.nn.log_softmax(logits[1, :1]))
        at = jnp.asarray(held)[:, None] + jnp.arange(steps)[None]
        real = jnp.asarray([[False] * steps, [True] * steps])
        after = jnp.asarray(held) + jnp.sum(real, axis=1)

        def flushed(cache, tails):
            return tuple(
                wa.write_to_ring(cache[i], tails[i], self.slots, at, real,
                                 after) if windowed
                else write_to_pages(cache[i], tails[i], self.table, at,
                                    real)
                for i, windowed in enumerate(c.layer_is_linear))
        self.k_cache = flushed(self.k_cache, k_tail) + (stats,)
        self.v_cache = flushed(self.v_cache, v_tail)

    def log_probs(self):
        return np.concatenate(self.out)


def served_log_probs(config, params, tokens, prompt, chunk, burst=0):
    served = Served(config, params)
    served.chunks(tokens, 0, prompt, chunk)
    if burst:
        served.burst(tokens, prompt, prompt + burst)
    served.steps(tokens, prompt + burst, len(tokens))
    return served.log_probs(), served.k_cache, served.v_cache


# ---- the model against the reference ---------------------------------------


@pytest.mark.parametrize("prompt,chunk,burst", [
    (60, 60, 0),     # the whole prompt at once: a chunk of 3.75 windows
    (50, 32, 0),     # chunks of two windows: the mask inside a chunk
    (50, 16, 0),     # chunks of one window: the ring between chunks
    (41, 8, 0),      # chunks shorter than the window, then 31 steps
    (9, 16, 0),      # a row shorter than the window grows past it
    (27, 16, 12),    # a burst over the ring's edge; its flush wraps
    (40, 32, 24),    # a burst longer than the window
])
def test_prefill_then_decode_agree_with_one_full_forward(prompt, chunk,
                                                         burst):
    config = model_config()
    assert config.layer_is_linear == (True, True, False, True)
    params = exaone.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(prompt_of(LENGTH, seed=1))
    want = reference.log_probs(reference.model_of(config, params),
                               tokens, list(range(LENGTH)))
    got, k_cache, v_cache = served_log_probs(config, params, tokens,
                                             prompt, chunk, burst)
    assert np.abs(got - want).max() < FLOAT32
    # The padded row left the trash slot's neighbours alone, and the
    # row's ring is full.
    for pool in (k_cache[0], v_cache[0]):
        assert float(jnp.abs(pool[:, 1]).max()) == 0.0
        assert float(jnp.abs(pool[:, 2]).min()) > 0.0
    # The counters: every single step of every windowed layer had a
    # window's keys in sight, or the row's while it was shorter.
    steps = LENGTH - prompt
    stats = [float(v) for v in k_cache[4]]
    assert stats[0] == 3 * steps and stats[7] == 3 * steps
    assert stats[6] == 3 * sum(min(p + 1, 16)
                               for p in range(prompt, LENGTH))


# Each term of the layer, left out of the program or put into it
# wrongly, and what it read on the top five log-probabilities as a
# factor over LEFT_OUT (which is itself three times the tiny
# configuration's limit); the program unchanged reads 9.5e-07.
TERMS = {
    "the window one key wider": 360,
    "the window one key narrower": 381,
    "a rotary on a full layer": 464,
    "no rotary on a windowed layer": 579,
    "a pre-norm for a post-norm": 2754,
    "no head norms": 497,
    "the bias in the weights": 288,
    "the factor 2.5": 671,
    "the shared expert": 1001,
    "the dense first layer": 223,
}


def loud_params(config):
    """The init's draws with the router's matrix and its bias scaled
    up: at 64 wide a router of N(0, 0.02) scores every expert within
    0.04 of one half, so the choice hangs on the bias alone and the
    weights are all an eighth. Scaled, the scores spread and the bias
    still moves a share of the choices. (The norms on the sublayers'
    outputs keep every other term heard at the init's own scale.) The
    reference is given the same values."""
    params = exaone.init_params(config, jax.random.PRNGKey(0))
    scale = {"router_bias": 20.0, "router": 24.0}
    return {k: v * scale.get(k.rsplit("_", 1)[0], 1.0)
            for k, v in params.items()}


def _changed(term, monkeypatch):
    """(config, params) of the program with one term left out or put
    in wrongly, by way of the one field, parameter or function that
    carries it. ``a pre-norm for a post-norm`` changes the REFERENCE's
    layer (the program's has no seam for it): the comparison is the
    same from either side."""
    config = model_config()
    params = loud_params(config)
    real_in_window = wa.in_window
    if term == "the window one key wider":
        monkeypatch.setattr(
            wa, "in_window",
            lambda key, q, window: real_in_window(key, q, window + 1))
    elif term == "the window one key narrower":
        monkeypatch.setattr(
            wa, "in_window",
            lambda key, q, window: real_in_window(key, q, window - 1))
    elif term == "a rotary on a full layer":
        real = exaone.hybrid_attention

        def turned(cfg, q, k, v, k_cache, v_cache, table, positions,
                   *rest):
            return real(cfg, apply_rope(q, positions, cfg.rope_theta),
                        apply_rope(k, positions, cfg.rope_theta), v,
                        k_cache, v_cache, table, positions, *rest)
        monkeypatch.setattr(exaone, "hybrid_attention", turned)
    elif term == "no rotary on a windowed layer":
        monkeypatch.setattr(exaone, "apply_rope",
                            lambda x, positions, theta: x)
    elif term == "a pre-norm for a post-norm":
        def pre_norm(m, i, x):
            w = m.layer(i)
            x = x + reference.attention(
                m, w, reference.norm(x, w["post_attn_norm"], m.rms_eps),
                m.windowed[i])
            u = reference.norm(x, w["post_ffn_norm"], m.rms_eps)
            if i < m.num_dense_layers:
                return x + reference.swiglu(u, w["w_gate"], w["w_up"],
                                            w["w_down"])
            return x + reference.expert_block(m, w, u)
        monkeypatch.setattr(reference, "layer_forward", pre_norm)
    elif term == "no head norms":
        params["q_norm"] = jnp.ones_like(params["q_norm"])
        real = exaone.rms_norm
        monkeypatch.setattr(
            exaone, "rms_norm",
            lambda x, w, eps: x if x.ndim == 4 else real(x, w, eps))
    elif term == "the bias in the weights":
        def biased(x, router_w, bias, top_k, scale=1.0, eps=1e-6):
            scores = jax.nn.sigmoid(jnp.dot(
                x, router_w, preferred_element_type=jnp.float32)) + bias
            weights, ids = jax.lax.top_k(scores, top_k)
            return scale * weights / (
                jnp.sum(weights, -1, keepdims=True) + eps), ids
        monkeypatch.setattr(glm4_moe_lite, "route_sigmoid", biased)
    elif term == "the factor 2.5":
        config = dataclasses.replace(config, routed_scaling_factor=1.0)
    elif term == "the shared expert":
        params = {k: jnp.zeros_like(v) if k.startswith("shared_down")
                  else v for k, v in params.items()}
    elif term == "the dense first layer":
        params["w_down"] = jnp.zeros_like(params["w_down"])
    return config, params


def _term_reading(term, monkeypatch):
    config = model_config()
    tokens = np.asarray(prompt_of(56, seed=2))
    config, params = _changed(term, monkeypatch)
    # After the change: a term changed on the reference's side shows.
    want = np.asarray(reference.log_probs(
        reference.model_of(model_config(), loud_params(model_config())),
        tokens, list(range(56))))
    served = Served(config, params)
    served.chunks(tokens, 0, 40, 24)
    served.steps(tokens, 40, 56)
    got = served.log_probs()
    top = np.argsort(-want, -1)[:, :5]
    return np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1)).max()


@pytest.mark.parametrize("term", list(TERMS))
def test_a_term_left_out_or_put_in_wrongly_fails_the_limit(term,
                                                           monkeypatch):
    assert _term_reading(term, monkeypatch) > LEFT_OUT * max(
        1.0, TERMS[term] / 2)


def test_the_sixteen_ranks_expert_parts_add_up_to_the_uncut_layer():
    """The share test: one layer's expert block on each of the sixteen
    chips of an EP-16 group (one of sixteen experts each, the same
    router over all sixteen, the same shared expert), the shared expert
    counted once, adds up to what the reference gives with every
    expert held."""
    whole = model_config(num_experts=16)
    params = loud_params(whole)
    ref = reference.model_of(whole, params)
    layer = ref.layer(2)
    x = jnp.asarray(np.random.RandomState(4).randn(2, 9, 64), jnp.float32)
    flat = x.reshape(18, 64)
    with jax.default_matmul_precision("highest"):
        want = reference.expert_block(ref, layer, flat)
        shared = reference.swiglu(flat, layer["s_gate"], layer["s_up"],
                                  layer["s_down"])
    valid = jnp.ones((2, 9), bool)
    total, loads = jnp.zeros_like(flat), []
    for rank in range(16):
        config = model_config(num_experts=1, expert_parallel_size=16,
                              expert_parallel_rank=rank)
        assert config.router_width == 16
        lp = {"router": params["router_2"],
              "router_bias": params["router_bias_2"],
              "shared_gate_up": params["shared_gate_up_2"],
              "shared_down": params["shared_down_2"],
              "w_gate_up": params["e_w_gate_up_2"][rank:rank + 1],
              "w_down": params["e_w_down_2"][rank:rank + 1]}
        y, load = glm4_moe_lite.expert_block(config, lp, x, valid)
        total = total + (y.reshape(18, 64) - shared)
        loads.append(np.asarray(load))
        # No share is the whole: each leaves out what the others hold.
        assert np.abs(y.reshape(18, 64) - want).max() > 1e-3
    assert np.abs(total + shared - want).max() < FLOAT32
    # Every token's three choices fell on some chip, once.
    assert int(np.sum(loads)) == 18 * 3


def test_random_init_draws_what_a_zero_or_a_one_would_switch_off():
    config = model_config()
    params = exaone.init_params(config, jax.random.PRNGKey(0))
    for name in ("q_norm", "k_norm", "post_attn_norm", "post_ffn_norm",
                 "final_norm"):
        spread = float(jnp.std(params[name].astype(jnp.float32)))
        assert 0.05 < spread < 0.2, name
        assert abs(float(jnp.mean(params[name])) - 1.0) < 0.1, name
    bias = jnp.stack([params[f"router_bias_{i}"] for i in (1, 2, 3)])
    assert bias.dtype == jnp.float32 and bias.shape == (3, 8)
    assert "router_0" not in params         # layer 0 is dense
    assert 0.003 < float(jnp.std(bias)) < 0.03
    assert "lm_head" in params              # the head is untied
    assert params["w_gate_up"].shape == (1, 64, 192)   # one dense layer


K_EXAONE = dict(
    model_type="exaone_moe", first_k_dense_replace=1, head_dim=128,
    hidden_act="silu", hidden_size=6144, intermediate_size=18432,
    layer_types=["full_attention" if i % 4 == 3 else "sliding_attention"
                 for i in range(48)],
    max_position_embeddings=262144,
    mlp_layer_types=["dense"] + ["sparse"] * 47,
    moe_intermediate_size=2048, mtp_layer_types=["full_attention"],
    mtp_sliding_windows=[0], n_group=1, norm_topk_prob=True,
    num_attention_heads=64, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=48, num_key_value_heads=8,
    num_nextn_predict_layers=1, num_shared_experts=1, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=2.5, scoring_func="sigmoid", sliding_window=128,
    sliding_window_pattern="LLLG",
    sliding_windows=[0 if i % 4 == 3 else 128 for i in range(48)],
    tie_word_embeddings=False, topk_group=1, vocab_size=153600)


def cut(**over):
    """The benchmark's cut of it: layer 0 and two whole periods."""
    kinds = K_EXAONE["layer_types"][:8]
    return dict(
        K_EXAONE, architectures=["ExaoneMoEForCausalLM"],
        num_hidden_layers=8, layer_types=kinds,
        mlp_layer_types=K_EXAONE["mlp_layer_types"][:8],
        sliding_windows=K_EXAONE["sliding_windows"][:8], num_experts=8,
        vocab_size=19200, expert_parallel_size=16, **over)


def test_the_published_config_is_read_as_the_family():
    config = ModelConfig.from_hf_config(K_EXAONE)
    assert config.architecture == "exaone_moe"
    assert [i for i, ring in enumerate(config.layer_is_linear)
            if not ring] == list(range(3, 48, 4))
    assert (config.sliding_window, config.head_dim,
            config.rope_theta) == (128, 128, 1e6)
    assert (config.num_dense_layers, config.routed_scaling_factor) == (
        1, 2.5)
    assert not config.tie_word_embeddings
    assert (config.router_width, config.num_experts) == (128, 128)
    assert (config.moe_intermediate_size,
            config.shared_expert_intermediate_size) == (2048, 2048)
    # The prediction layer is read and not served.
    assert config.num_nextn_predict_layers == 0
    assert not config.has_draft_module
    # A windowed layer's K ring and V ring, what one page of 128 holds.
    assert config.recurrent_state_shapes() == ((8, 128, 128),) * 2
    assert config.recurrent_state_bytes() == 36 * 524288
    assert (config.num_kv_layers, config.page_cache.entries) == (12, 12)
    # The class name names it too, and a chip's share is read as the
    # other expert families' is: the key counts the experts held.
    share = ModelConfig.from_hf_config(cut(expert_parallel_rank=15))
    assert (share.architecture, share.router_width) == ("exaone_moe", 128)
    assert share.expert_parallel_rank * share.num_experts == 120
    assert share.layer_is_linear == (True, True, True, False,
                                     True, True, True, False)
    assert share.recurrent_state_bytes() == 6 * 524288 == 3145728


def test_the_inits_own_count_at_the_published_widths_is_the_hand_sum():
    """Shapes only: nothing of this size is made."""
    config = ModelConfig.from_hf_config(cut())
    shapes = jax.eval_shape(
        lambda: exaone.init_params(config, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    attention = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144 + 2 * 128
    expert = 3 * 6144 * 2048
    outside = attention + 2 * 6144 + expert + 6144 * 128 + 128
    dense = attention + 2 * 6144 + 3 * 6144 * 18432
    assert (attention, expert, outside, dense) == (
        113246464, 37748736, 151794048, 452997376)
    assert outside + 128 * expert == 4983632256       # a layer, whole
    assert count == (dense + 7 * (outside + 8 * expert)
                     + 2 * 19200 * 6144 + 6144) == 3865420672
    # The whole model as published.
    assert (dense + 47 * (outside + 128 * expert) + 2 * 153600 * 6144
            + 6144) == 236571156352


@pytest.mark.parametrize("change,word", [
    (dict(layer_types=["chunked_attention"] + K_EXAONE["layer_types"][1:]),
     r"layer_types entries \['chunked_attention'\]"),
    (dict(layer_types=K_EXAONE["layer_types"][:47]),
     "layer_types lists 47 layers"),
    (dict(sliding_window=None), "sliding_window None with"),
    (dict(sliding_windows=[128] * 48), "sliding_windows does not say"),
    (dict(mlp_layer_types=["sparse"] * 48), "mlp_layer_types is not"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
     "rope_type 'yarn'"),
    (dict(n_group=8, topk_group=4), "n_group 8 / topk_group 4"),
    (dict(scoring_func="softmax"), "scoring_func 'softmax'"),
    (dict(norm_topk_prob=False), "norm_topk_prob false"),
    (dict(num_shared_experts=2), "num_shared_experts 2"),
    (dict(num_nextn_predict_layers=2), "num_nextn_predict_layers 2"),
    (dict(attention_bias=True), "attention_bias true"),
    (dict(expert_parallel_size=16, expert_parallel_rank=16),
     "expert_parallel_rank 16 is not one of"),
])
def test_an_exaone_this_engine_does_not_serve_is_refused_in_words(
        change, word):
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(dict(K_EXAONE, **change))


def test_an_exaone_checkpoint_is_refused(tmp_path):
    from production_stack_tpu.engine.weights import load_weights
    with pytest.raises(NotImplementedError, match="--random-weights"):
        load_weights(str(tmp_path), model_config())


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_padded_and_stopped_rows_leave_their_slot_bit_identical(impl):
    """A decode step of three rows: a padded row on the trash slot, a
    row whose sequence stopped inside a burst (its own slot, not
    valid), and a live row. The first two slots hold after the step
    what they held before it, to the bit, in every windowed layer; the
    live row's ring moved at its one place."""
    config = model_config(attention_impl=impl)
    params = exaone.init_params(config, jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    k_cache, v_cache = init_hybrid_cache(config, 8, 16, 4)
    rings = [i for i, ring in enumerate(config.layer_is_linear) if ring]
    k_cache, v_cache = list(k_cache), list(v_cache)
    for i in rings:   # slots as sequences left them: full
        k_cache[i] = jnp.asarray(rng.randn(*k_cache[i].shape), jnp.float32)
        v_cache[i] = jnp.asarray(rng.randn(*v_cache[i].shape), jnp.float32)
    table = np.array([[0, 0], [1, 0], [2, 0]], np.int32)
    _, k_new, v_new = exaone.forward(
        params, config, np.array([[0], [7], [9]], np.int32),
        np.array([[0], [21], [21]], np.int32), table,
        np.array([0, 21, 22], np.int32),
        np.array([[False], [False], [True]]), tuple(k_cache),
        tuple(v_cache), state_slots=jnp.array([0, 2, 3]))
    for i in rings:
        for before, after in ((k_cache[i], k_new[i]),
                              (v_cache[i], v_new[i])):
            for slot in (1, 2):               # nobody's, the stopped
                assert np.array_equal(after[:, slot], before[:, slot])
            moved = np.any(np.asarray(after[:, 3] != before[:, 3]),
                           axis=(0, 1))
            assert list(np.nonzero(moved)[0]) == [21 % 16]
    # One live row: three expert layers of three choices, three
    # windowed layers of a window's keys.
    assert [float(v) for v in k_new[4]] == [3.0, 9.0, 9.0, 3.0, 9.0, 0.0,
                                            48.0, 3.0]


@pytest.mark.parametrize("width,lean,chunks", [(32, 0.0, 1), (32, 4.0, 2),
                                               (2, 0.0, None)])
def test_held_choices_that_fit_their_room_go_through_alone(width, lean,
                                                           chunks):
    """128 tokens x 4 choices over 32 experts of which 2 are held: 32
    held choices to expect and a room of 128 (``ops/moe.py``
    ``expert_room``, from the shapes). Where they fit, the sum is taken
    over the room's rows and agrees with the sum over all 512 to
    float32's rounding; where the router leans on the held block they
    take a second chunk of the room, and the sum is as exact; with a
    router no wider than the held block there is no room, and the call
    is the call without the router's width, bit for bit."""
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    n, h, f, e = 128, 32, 16, 2
    first = 4 if width > e else 0
    x = jax.random.normal(keys[0], (n, h), jnp.float32)
    w_gate_up = 0.2 * jax.random.normal(keys[1], (e, h, 2 * f), jnp.float32)
    w_down = 0.2 * jax.random.normal(keys[2], (e, f, h), jnp.float32)
    scores = jax.random.gumbel(keys[3], (n, max(width, 4))).at[
        :, first:first + e].add(lean)
    ids = jax.lax.top_k(scores, 4)[1]
    weights = jax.random.uniform(keys[4], (n, 4), jnp.float32)
    valid = jnp.arange(n) < 120
    want, want_load = moe.held_experts(x, weights, ids, w_gate_up, w_down,
                                       first, valid)
    got, load = jax.jit(lambda *a: moe.held_experts(
        *a, first, valid, router_width=width))(
        x, weights, ids, w_gate_up, w_down)
    room = moe.expert_room(n, 4, e, width)
    assert room == (128 if chunks else None)
    if chunks:
        assert -(-int(want_load.sum()) // room) == chunks
    assert load.tolist() == want_load.tolist()
    assert np.abs(np.asarray(want)).max() > 0.1
    assert np.abs(np.asarray(got - want)).max() < FLOAT32
    if not chunks:
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_pallas_paths_in_interpret_mode_equal_the_xla_paths():
    """Two query heads a KV head through both kernels under their
    window term (three chunks, so the second and third read the ring
    the one before wrote, the last a padded one) and without it on the
    full layer, then steps over the ring's edge, and the grouped expert
    product."""
    def log_probs(impl):
        config = dataclasses.replace(
            tiny_exaone_moe_config(sliding_window=128), head_dim=128,
            attention_impl=impl)
        params = exaone.init_params(config, jax.random.PRNGKey(0))
        served = Served(config, params, page_size=128, pages=10)
        tokens = np.asarray(prompt_of(310, seed=5))
        served.chunks(tokens, 0, 300, 128)
        served.steps(tokens, 300, 310)
        return served.log_probs()

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
    """The window's kernel form at 8 KV heads under 64 (the prefill
    step's 8 rows x 256 tokens, 2048 query rows a KV head, over a
    step's own plane; a decode step's form is XLA's),
    the full layers' kernels at a table of 57 pages, and the grouped
    product at 8 experts of width 2048."""
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention)
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention)
    bf16, i32 = jnp.bfloat16, jnp.int32
    ring = ((8, 137, 128, 128), bf16)
    tail = ((128, 32, 8, 128), bf16)
    for chunk in (16, 256):
        new = ((8, chunk, 8, 128), bf16)
        _lowers_for_tpu(
            lambda q, kr, vr, slots, held, k, v, after:
            wa.window_prefill_pallas(q, kr, vr, slots, held, k, v, after),
            ((8, chunk, 64, 128), bf16), ring, ring, ((8,), i32),
            ((8,), i32), new, new, ((8,), i32))
    cache = ((8, 5120, 128, 128), bf16)
    _lowers_for_tpu(
        paged_prefill_attention, ((8, 256, 64, 128), bf16), cache, cache,
        ((8, 57), i32), ((8, 256), i32), ((8,), i32))
    _lowers_for_tpu(
        lambda q, k, v, table, lens, kt, vt, at: paged_decode_attention(
            q, k, v, table, lens, k_tail=kt, v_tail=vt, q_positions=at),
        ((128, 64, 128), bf16), cache, cache, ((128, 57), i32),
        ((128,), i32), tail, tail, ((128,), i32))
    _lowers_for_tpu(
        lambda x, w, ids, up, down: moe.held_experts(
            x, w, ids, up, down, 0, impl="pallas")[0],
        ((128, 6144), bf16), ((128, 8), jnp.float32), ((128, 8), i32),
        ((8, 6144, 4096), bf16), ((8, 2048, 6144), bf16))
