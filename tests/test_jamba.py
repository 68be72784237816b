"""Jamba (Mamba-1 mixers beside no-rope grouped-query attention): the
model and its ops against the plain reference of the family
(chipbench/reference/jamba_family.py), which imports nothing of the
program's models or ops and is given the program's parameter values.
The engine around it: tests/test_jamba_engine.py.

Tiny widths, float32, seeded, on the CPU. Tolerances, each with its
reason:

- ``FLOAT32`` 2e-5 on log-probabilities: both sides are float32 on one
  CPU with the same weights and differ in the order of sums (the state
  kept transposed, attention over pages). The readings are under 2e-6.
- ``INTERPRET`` 2e-4 between the Pallas kernels in interpret mode and
  the XLA paths: the attention kernels keep an online softmax in
  float32 with another order of sums (what
  tests/test_pallas_attention.py allows them).
- ``LEFT_OUT`` 3e-4, three times the tiny configuration's limit on the
  worst log-probability (chipbench/rehearsal/configs/tiny-jamba.json):
  a term of the block left out, or a rotary put in, moves the top
  log-probabilities by more; the readings are 0.046 (the three small
  norms), 0.109 (D * xs), 0.134 (the convolution's bias) and 0.0033
  (the rotary: one attention layer of four, at weights of N(0, 0.02)).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import jamba_family as reference
from production_stack_tpu.engine.config import tiny_jamba_config
from production_stack_tpu.models import jamba
from production_stack_tpu.models.registry import init_hybrid_cache
from production_stack_tpu.ops import selective_scan
from production_stack_tpu.ops.rope import apply_rope
from production_stack_tpu.ops.selective_scan_pallas import (
    selective_scan_decode,
)

FLOAT32 = 2e-5
INTERPRET = 2e-4
LEFT_OUT = 3e-4


def model_config(**over):
    config = tiny_jamba_config()
    config.attention_impl = "xla"
    return dataclasses.replace(config, **over)


def prompt_of(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, 512, size=n)]


def served_log_probs(config, params, tokens, prompt, chunk,
                     forward=jamba.forward):
    """Row 1 of two (row 0 is padding on the trash slot): the prompt in
    padded chunks of at most ``chunk`` real tokens, then one cached
    decode step a token. Log-softmax of every position, and the
    caches."""
    k_cache, v_cache = init_hybrid_cache(config, 32, 16, 4)
    table = np.zeros((2, 8), np.int32)
    table[1, :6] = [3, 4, 5, 6, 7, 8]
    slots = jnp.array([0, 2])
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
    (50, 24),    # three chunks carrying h and the tail, then six steps
    (33, 16),    # chunks that end on a page's edge, then 23 steps
])
def test_prefill_then_decode_agree_with_one_full_forward(prompt, chunk):
    config = model_config()
    assert config.layer_is_linear == (True, False, True, True)
    params = jamba.init_params(config, jax.random.PRNGKey(0))
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


def _without(name):
    """The program's forward with one term of the block left out, by
    way of the one parameter or function that carries it (the
    ``silu(z)`` gate has neither: it is left out of the reference's
    side, chipbench/tests/test_jamba_family.py)."""
    def forward(params, config, *args, **kwargs):
        params = dict(params)
        if name == "the three small norms":
            # x / rms(x) with no weight is still a norm: leave the
            # division out too, by a norm that returns its input.
            real = jamba.rms_norm
            small = {config.mamba_dt_rank, config.mamba_d_state}
            patched = lambda x, w, eps: (  # noqa: E731
                x if w.shape[-1] in small else real(x, w, eps))
            jamba.rms_norm = patched
            try:
                return jamba.forward(params, config, *args, **kwargs)
            finally:
                jamba.rms_norm = real
        if name == "D * xs":
            params["m_D"] = jnp.zeros_like(params["m_D"])
        elif name == "the convolution's bias":
            params["m_conv_b"] = jnp.zeros_like(params["m_conv_b"])
        return jamba.forward(params, config, *args, **kwargs)
    return forward


@pytest.mark.parametrize("term", ["the three small norms", "D * xs",
                                  "the convolution's bias"])
def test_a_term_left_out_of_the_program_fails_the_limit(term):
    config = model_config()
    params = jamba.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(prompt_of(40, seed=2))
    want = np.asarray(reference.log_probs(
        reference.model_of(config, params), tokens, list(range(40))))
    got, _, _ = served_log_probs(config, params, tokens, 30, 16,
                                 forward=_without(term))
    top = np.argsort(-want, -1)[:, :5]
    diff = np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1))
    assert diff.max() > LEFT_OUT


def test_a_rotary_wrongly_applied_fails_the_limit(monkeypatch):
    """The attention layers have no position term: a program that
    turned q and k as a Llama does is another model."""
    config = model_config()
    params = jamba.init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(prompt_of(40, seed=2))
    want = np.asarray(reference.log_probs(
        reference.model_of(config, params), tokens, list(range(40))))
    real = jamba.hybrid_attention

    def turned(cfg, q, k, v, k_cache, v_cache, table, positions, *rest):
        return real(cfg, apply_rope(q, positions), apply_rope(k, positions),
                    v, k_cache, v_cache, table, positions, *rest)

    monkeypatch.setattr(jamba, "hybrid_attention", turned)
    got, _, _ = served_log_probs(config, params, tokens, 30, 16)
    top = np.argsort(-want, -1)[:, :5]
    diff = np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1))
    assert diff.max() > LEFT_OUT


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_padded_and_stopped_rows_leave_their_slot_bit_identical(impl):
    """A decode step of three rows: a padded row on the trash slot, a
    row whose sequence stopped inside a burst (its own slot, not
    valid), and a live row. The first two slots hold after the step
    what they held before it, to the bit, in every Mamba layer: h and
    the tail; the live row's moved."""
    config = model_config(attention_impl=impl, attention_impl_decode="xla")
    params = jamba.init_params(config, jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    k_cache, v_cache = init_hybrid_cache(config, 8, 16, 4)
    mamba = [i for i, m in enumerate(config.layer_is_linear) if m]
    k_cache, v_cache = list(k_cache), list(v_cache)
    for i in mamba:   # slots as sequences left them: full
        k_cache[i] = jnp.asarray(rng.randn(*k_cache[i].shape), jnp.float32)
        v_cache[i] = jnp.asarray(rng.randn(*v_cache[i].shape), jnp.float32)
    table = np.array([[0, 0], [1, 0], [2, 0]], np.int32)
    _, k_new, v_new = jamba.forward(
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


# ---- the scan ---------------------------------------------------------------


def _scan_inputs(rng, b, t, d, n):
    f32 = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)  # noqa
    delta = jax.nn.softplus(f32(b, t, d) - 2.0)
    return (delta, delta * f32(b, t, d), f32(b, t, n), f32(b, t, n),
            -jnp.exp(f32(n, d)), f32(b, n, d))


@pytest.mark.parametrize("tokens", [1, 7, 8, 19])
def test_the_block_scan_equals_the_reference_recurrence(tokens):
    rng = np.random.RandomState(0)
    delta, dx, b, c, a_t, _ = _scan_inputs(rng, 2, tokens, 128, 8)
    got, state = selective_scan.selective_scan_block(
        delta, dx, b, c, a_t, jnp.zeros((2, 8, 128)))
    for row in range(2):
        # The reference takes x and delta apart and A as published.
        x = dx[row] / delta[row]
        want = reference.selective_scan(x, delta[row], a_t.T, b[row],
                                        c[row])
        assert np.abs(got[row] - want).max() < FLOAT32
    # Carried over two blocks, padded tokens (delta 0) between them.
    cut = tokens // 2
    pad = lambda a: jnp.concatenate(  # noqa: E731
        [a[:, :cut], jnp.zeros_like(a[:, :3]), a[:, cut:]], axis=1)
    padded, padded_state = selective_scan.selective_scan_block(
        pad(delta), pad(dx), pad(b), pad(c), a_t, jnp.zeros((2, 8, 128)))
    real = np.r_[0:cut, cut + 3:tokens + 3]
    assert np.abs(padded[:, real] - got).max() < FLOAT32
    assert np.abs(padded_state - state).max() < FLOAT32


def test_the_decode_kernel_over_the_pool_equals_gather_step_scatter():
    """The Pallas kernel (interpret mode) reads each row's h from its
    slot, advances it and writes it back in place; against the XLA
    step. Two padded rows share the trash slot 0 and leave it as it
    was, to the bit; a row that starts at position 0 starts from
    zero; nobody's slot is untouched."""
    rng = np.random.RandomState(0)
    rows, d, n = 5, 256, 16
    delta, dx, b, c, a_t, _ = _scan_inputs(rng, rows, 1, d, n)
    padded = jnp.array([False, True, False, False, True])[:, None]
    delta = jnp.where(padded, 0.0, delta[:, 0])
    dx = jnp.where(padded, 0.0, dx[:, 0])
    pool = jnp.asarray(rng.randn(8, n, d), jnp.float32)
    slots = jnp.array([3, 0, 5, 1, 0])
    keep = jnp.array([1.0, 1.0, 0.0, 1.0, 1.0])
    want_y, state = selective_scan.selective_scan_step(
        delta, dx, b[:, 0], c[:, 0], a_t, pool[slots], keep=keep)
    got_y, got_pool = selective_scan_decode(
        delta, dx, b[:, 0], c[:, 0], a_t, pool, slots, keep,
        interpret=True)
    assert np.abs(got_y - want_y).max() < FLOAT32
    assert np.abs(got_pool - pool.at[slots].set(state)).max() < FLOAT32
    assert np.array_equal(got_pool[0], pool[0])
    assert np.array_equal(got_pool[2], pool[2])
    # A fresh row's result does not depend on what its slot held.
    assert np.array_equal(
        got_pool[5], dx[2][None, :] * b[2, 0][:, None])


def test_the_pallas_paths_in_interpret_mode_equal_the_xla_paths():
    """One KV head under four query heads through the prefill kernel
    (two chunks, so the second reads the first's pages) and the decode
    kernel, and the scan's decode kernel over the pool."""
    def log_probs(impl):
        config = model_config(head_dim=128, attention_impl=impl)
        params = jamba.init_params(config, jax.random.PRNGKey(0))
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
            logits, k_cache, v_cache = jamba.forward(
                params, config, tok, np.where(valid, pos, 0), table,
                np.array([start + n], np.int32), valid, k_cache, v_cache,
                state_slots=slots)
            out.append(jax.nn.log_softmax(logits[0, :n]))
        for p in (20, 21):
            logits, k_cache, v_cache = jamba.forward(
                params, config, tokens[None, p:p + 1],
                np.array([[p]], np.int32), table,
                np.array([p + 1], np.int32), np.array([[True]]),
                k_cache, v_cache, state_slots=slots)
            out.append(jax.nn.log_softmax(logits[0]))
        return np.concatenate(out)

    assert np.abs(log_probs("pallas-interpret")
                  - log_probs("xla")).max() < INTERPRET


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
    rows, d, n, slots = 128, 5120, 16, 137
    # The scan's step over the pool: 128 rows, a row's h 327 680 B.
    _lowers_for_tpu(
        selective_scan_decode, ((rows, d), f32), ((rows, d), f32),
        ((rows, n), f32), ((rows, n), f32), ((n, d), f32),
        ((slots, n, d), f32), ((rows,), i32), ((rows,), f32))
    # The attention kernels at one KV head under 20: the prefill
    # step's 8 rows x 128 tokens and the decode batch.
    cache = ((1, 3072, 128, 128), bf16)
    _lowers_for_tpu(
        paged_prefill_attention, ((8, 128, 20, 128), bf16), cache, cache,
        ((8, 32), i32), ((8, 128), i32), ((8,), i32))
    _lowers_for_tpu(
        paged_decode_attention, ((rows, 20, 128), bf16), cache, cache,
        ((rows, 32), i32), ((rows,), i32))
