"""SDAR-MoE (the Qwen3-MoE layer under sight by block: per-head q/k
norms, a softmax router over held experts; a head whose row at ``t`` is
the token AT ``t``; masked places as a flag beside the ids): the model
against the plain reference of the family
(chipbench/reference/sdar_family.py), which imports nothing of the
program's models, ops or engine and is given the program's parameter
values; and the three ops the family brought (sight by block in the
prefill kernel, a block's queries folded into the decode forms' group
axis, ``unmask_block``). The same through the engine (scheduler,
pages, the block burst, hand-over, refusals):
tests/test_sdar_moe_engine.py.

Tiny widths, float32, seeded, on the CPU. Tolerances, each with its
reason:

- ``FLOAT32`` 2e-5 on log-probabilities: both sides are float32 on one
  CPU with the same weights and differ in the order of sums (pages and
  a tail in one softmax against one ``[T, T]`` mask). The readings are
  under 2e-6.
- ``INTERPRET`` 2e-4 between the Pallas kernels in interpret mode and
  the XLA paths (what tests/test_pallas_attention.py allows them).
- ``LEFT_OUT`` 3e-4, three times the tiny configuration's limit on the
  worst log-probability (chipbench/rehearsal/configs/tiny-sdar.json): a
  term left out or put in wrongly moves the top log-probabilities by
  more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import sdar_family as reference
from production_stack_tpu.engine.config import (
    ModelConfig,
    tiny_sdar_moe_config,
)
from production_stack_tpu.models import registry, sdar_moe
from production_stack_tpu.models.registry import init_hybrid_cache
from production_stack_tpu.ops import sampling
from production_stack_tpu.ops.attention import (
    fold_block_queries,
    paged_attention,
    unfold_block_queries,
    write_block_to_tail,
)

FLOAT32 = 2e-5
INTERPRET = 2e-4
LEFT_OUT = 3e-4
BLOCK = 4


def model_config(**over):
    config = tiny_sdar_moe_config()
    config.attention_impl = "xla"
    return dataclasses.replace(config, **over)


def prompt_of(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, 512, size=n)]


@pytest.fixture(scope="module")
def weights():
    config = model_config()
    return config, sdar_moe.init_params(config, jax.random.PRNGKey(3))


class Served:
    """Row 1 of two (row 0 is padding on the trash page) through the
    program's forward as the runner drives it: prefill chunks of whole
    blocks to pages of 16, then passes over one block against pages
    and a tail of two blocks."""

    def __init__(self, config, params, pages=16, page_size=16, slots=8):
        self.config, self.params = config, params
        self.k_cache, self.v_cache = init_hybrid_cache(
            config, pages, page_size, 0)
        self.table = np.zeros((2, 8), np.int32)
        self.table[1] = np.arange(1, 9)
        layers = config.num_hidden_layers
        tail = jnp.zeros((2, slots, config.num_key_value_heads,
                          config.head_dim), config.jax_dtype)
        self.tails = ((tail,) * layers, (tail,) * layers)
        self.stored = 0

    def prefill(self, tokens, start=0):
        n = len(tokens)
        ids = np.zeros((2, n), np.int32)
        ids[1] = tokens
        pos = np.zeros((2, n), np.int32)
        pos[1] = np.arange(start, start + n)
        valid = np.zeros((2, n), bool)
        valid[1] = True
        out, self.k_cache, self.v_cache = sdar_moe.forward(
            self.params, self.config, jnp.asarray(ids), jnp.asarray(pos),
            jnp.asarray(self.table), jnp.asarray([0, start + n]),
            jnp.asarray(valid), self.k_cache, self.v_cache, head=False)
        assert out is None
        self.stored = start + n

    def block_pass(self, tokens, masked, slot, head=True):
        """One pass over the block at tail slots ``slot..``: logits
        [B, vocab] of row 1 (None for a store pass)."""
        layers = self.config.num_hidden_layers
        ids = np.zeros((2, BLOCK), np.int32)
        ids[1] = tokens
        flags = np.zeros((2, BLOCK), bool)
        flags[1] = masked
        pos = np.tile(self.stored + slot + np.arange(BLOCK), (2, 1))
        valid = np.zeros((2, BLOCK), bool)
        valid[1] = True
        kt, vt = self.tails
        k_in = tuple(self.k_cache[:layers]) + (self.k_cache[layers],)
        logits, k_out, v_out = sdar_moe.forward(
            self.params, self.config, jnp.asarray(ids), jnp.asarray(pos),
            jnp.asarray(self.table), jnp.asarray([self.stored] * 2),
            jnp.asarray(valid), k_in, self.v_cache, kv_tail=(kt, vt),
            masked=jnp.asarray(flags), head=head, position_major=True)
        self.tails = (tuple(k_out[:layers]), tuple(v_out))
        self.k_cache = self.k_cache[:layers] + (k_out[layers],)
        return None if logits is None else np.asarray(
            jax.nn.log_softmax(logits[:, 1], -1))


def reference_rows(model, tokens, masked, rows, prefilled):
    with jax.default_matmul_precision("highest"):
        x = reference.forward_hidden(model, tokens, masked, prefilled)
        return np.asarray(reference.head(model, x[jnp.asarray(rows)]))


def served_states(config, params, prompt, answer):
    """The program's log-probabilities at the second block's places in
    two states (all masked; two known), after a prefill in two chunks,
    a first block denoised, stored, and seen through the tail."""
    served = Served(config, params)
    served.prefill(prompt[:16])
    served.prefill(prompt[16:], start=16)
    served.block_pass(answer[:2] + [0, 0], [False, False, True, True], 0)
    assert served.block_pass(answer[:4], [False] * 4, 0,
                             head=False) is None
    first = served.block_pass([0] * 4, [True] * 4, BLOCK)
    second = served.block_pass(answer[4:6] + [0, 0],
                               [False, False, True, True], BLOCK)
    return first, second


def test_prefill_and_blocks_through_pages_and_tails_agree_with_the_reference(
        weights):
    config, params = weights
    prompt, answer = prompt_of(24, 1), prompt_of(8, 2)
    first, second = served_states(config, params, prompt, answer)
    model = reference.model_of(config, params)
    known = prompt + answer[:4]
    want = reference_rows(model, known + [0] * 4,
                          [False] * 28 + [True] * 4, range(28, 32), 24)
    assert np.abs(first - want).max() < FLOAT32
    want = reference_rows(model, known + answer[4:6] + [0, 0],
                          [False] * 30 + [True] * 2, range(28, 32), 24)
    assert np.abs(second - want).max() < FLOAT32


@pytest.mark.parametrize("lever", [
    dict(head_norms=False), dict(own_block=False),
    dict(causal_prefill=True), dict(head_shift=1), dict(norm_topk=False),
    dict(stale_blocks=True)])
def test_a_term_left_out_or_put_in_moves_the_logits(weights, lever):
    """Each of the family's terms, turned in the reference: no head
    norms, a block without its own keys, a causal prompt, a next-token
    head, unnormalised expert weights, and a first block whose K/V are
    a denoising pass's (two places still masked) and not the store
    pass's."""
    config, params = weights
    prompt, answer = prompt_of(24, 1), prompt_of(8, 2)
    _, second = served_states(config, params, prompt, answer)
    model = reference.model_of(config, params, **lever)
    tokens = prompt + answer[:6] + [0, 0]
    masked = [False] * 30 + [True] * 2
    if model.stale_blocks:
        masked[26:28] = [True, True]
    rows = [r - model.head_shift for r in range(28, 32)]
    want = reference_rows(model, tokens, masked, rows, 24)
    top = np.argsort(-second, -1)[:, :5]
    moved = np.abs(np.take_along_axis(second, top, -1)
                   - np.take_along_axis(want, top, -1)).max()
    assert moved > LEFT_OUT, moved


def test_the_mask_is_a_flag_and_never_read_off_the_id(weights):
    """A known place that holds the mask's own id is that token; a
    masked place is the mask whatever id it carries."""
    config, params = weights
    mask_id = config.mask_token_id
    served = Served(config, params)
    served.prefill(prompt_of(16, 5))
    as_token = served.block_pass([mask_id, 7, 0, 0],
                                 [False, False, True, True], 0)
    other_ids = served.block_pass([mask_id, 7, 99, 300],
                                  [False, False, True, True], 0)
    as_mask = served.block_pass([0, 7, 0, 0],
                                [True, False, True, True], 0)
    assert np.array_equal(as_token, other_ids)
    assert np.array_equal(
        as_mask, served.block_pass([mask_id, 7, 0, 0],
                                   [True, False, True, True], 0))
    # With random weights the mask's row is a row like any other, so
    # the flag changes nothing where the id is the mask's own.
    assert np.array_equal(as_token, as_mask)
    assert np.abs(as_token - served.block_pass(
        [5, 7, 0, 0], [False, False, True, True], 0)).max() > LEFT_OUT


# ---- the configuration and the family ------------------------------------------

PUBLISHED = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128,
    hidden_act="silu", hidden_size=2048, intermediate_size=6144,
    max_position_embeddings=32768, max_window_layers=48,
    mlp_only_layers=[], model_type="sdar_moe", moe_intermediate_size=768,
    norm_topk_prob=True, num_attention_heads=32, num_experts=128,
    num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
    rms_norm_eps=1e-06, rope_scaling=None, rope_theta=1000000,
    sliding_window=None, tie_word_embeddings=False,
    use_sliding_window=False, vocab_size=151936)


def test_the_published_config_is_read_with_the_scripts_defaults():
    config = ModelConfig.from_hf_config(PUBLISHED)
    assert config.architecture == "sdar_moe"
    assert (config.num_hidden_layers, config.num_experts,
            config.num_experts_per_tok, config.moe_intermediate_size,
            config.head_dim, config.router_width) == (
        48, 128, 8, 768, 128, 128)
    # What the family's generate.py takes as arguments, at its defaults.
    assert (config.diffusion_block_length, config.mask_token_id,
            config.diffusion_steps, config.diffusion_remasking,
            config.diffusion_confidence_threshold) == (
        4, 151669, 4, "low_confidence_dynamic", 0.9)
    assert config.block_length == 4
    by_class = ModelConfig.from_hf_config(
        {**PUBLISHED, "model_type": None,
         "architectures": ["SDARMoeForCausalLM"],
         "diffusion_steps": 2, "diffusion_remasking": "sequential",
         "expert_parallel_size": 2, "expert_parallel_rank": 1,
         "num_experts": 64})
    assert (by_class.architecture, by_class.diffusion_steps,
            by_class.router_width, by_class.expert_parallel_rank) == (
        "sdar_moe", 2, 128, 1)
    fam = registry.family("sdar_moe")
    assert fam.deferred_kv and fam.block(config) == 4
    assert fam.counters[-5:] == ("denoise_passes", "store_passes",
                                 "blocks", "committed", "sorted_passes")
    assert registry.page_cache(config) == registry.PageCache(
        entries=48, heads=4, width=128, planes=2)
    assert [name for name, f in registry.FAMILIES.items()
            if f.block is not None] == ["sdar_moe"]
    assert ModelConfig().block_length == 0


@pytest.mark.parametrize("key, value, said", [
    ("mlp_only_layers", [3], "every layer is served as an expert layer"),
    ("decoder_sparse_step", 2, "every layer is served as an expert"),
    ("rope_scaling", {"type": "yarn"}, "served unscaled"),
    ("use_sliding_window", True, "the whole row up to the end of its"),
    ("attention_bias", True, "served without a bias"),
    ("hidden_act", "gelu", "the experts are SwiGLU"),
    ("diffusion_block_length", 6, "its length is a power of two"),
    ("diffusion_steps", 5, "a block takes 1 to its length"),
    ("diffusion_steps", 0, "a block takes 1 to its length"),
    ("diffusion_remasking", "random", "the rules served are sequential"),
    ("mask_token_id", 151936, "is no row of an embedding"),
])
def test_a_config_it_does_not_serve_is_refused_in_words(key, value, said):
    with pytest.raises(ValueError, match=said) as refused:
        ModelConfig.from_hf_config({**PUBLISHED, key: value})
    assert "SDAR-MoE config this engine does not serve" in str(
        refused.value)


def test_no_lora_and_no_stacked_cache(weights):
    config, params = weights
    ids = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="no LoRA targets"):
        sdar_moe.forward(params, config, ids, ids, ids, ids[0], ids,
                         (), (), lora=object())
    with pytest.raises(ValueError, match="per-layer caches"):
        sdar_moe.forward(params, config, ids, ids, ids, ids[0], ids,
                         jnp.zeros((2, 2)), jnp.zeros((2, 2)))


# ---- the ops -------------------------------------------------------------------


def _paged(rng, kv=2, d=16, pages=12, page=16):
    return (jnp.asarray(rng.standard_normal((kv, pages, d, page)),
                        jnp.float32),
            jnp.asarray(rng.standard_normal((kv, pages, d, page)),
                        jnp.float32))


def _softmax_rows(q, keys, values, sight):
    """q [T, h, d], keys/values [S, kv, d], sight [T, S]: numpy."""
    group = q.shape[1] // keys.shape[1]
    k, v = np.repeat(keys, group, 1), np.repeat(values, group, 1)
    scores = np.einsum("thd,shd->hts", q, k) / np.sqrt(q.shape[-1])
    scores = np.where(sight[None], scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("hts,shd->thd", w, v)


@pytest.mark.parametrize("start, length", [(0, 16), (16, 32), (32, 8)])
def test_sight_by_block_in_the_prefill_kernel_and_the_xla_form(start,
                                                               length):
    """A chunk of whole blocks over pages that hold it: the Pallas
    prefill kernel (interpret mode) with ``block=4``, the XLA form fed
    ``position | 3``, and a numpy softmax under the explicit mask; and
    without ``block`` the kernel is causal as before."""
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    rng = np.random.default_rng(start + length)
    k_plane, v_plane = _paged(rng)
    table = jnp.asarray([[3, 5, 7, 0], [2, 4, 6, 8]], jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, length, 4, 16)), jnp.float32)
    positions = jnp.asarray(np.tile(start + np.arange(length), (2, 1)),
                            jnp.int32)
    kv_lens = jnp.asarray([start + length] * 2, jnp.int32)
    kernel = paged_prefill_attention(q, k_plane, v_plane, table, positions,
                                     kv_lens, block=BLOCK, interpret=True)
    xla = paged_attention(q, k_plane, v_plane, table, positions | 3,
                          kv_lens)
    causal = paged_prefill_attention(q, k_plane, v_plane, table, positions,
                                     kv_lens, interpret=True)
    assert np.abs(np.asarray(kernel) - np.asarray(xla)).max() < INTERPRET
    assert np.abs(np.asarray(causal) - np.asarray(paged_attention(
        q, k_plane, v_plane, table, positions, kv_lens))).max() < INTERPRET
    assert np.abs(np.asarray(kernel) - np.asarray(causal)).max() > 0.01
    for row in range(2):
        pages = np.asarray(table[row])
        keys = np.concatenate([np.asarray(k_plane)[:, p] for p in pages],
                              -1).transpose(2, 0, 1)[:start + length]
        values = np.concatenate([np.asarray(v_plane)[:, p] for p in pages],
                                -1).transpose(2, 0, 1)[:start + length]
        at = start + np.arange(length)
        sight = np.arange(start + length)[None, :] <= (at | 3)[:, None]
        want = _softmax_rows(np.asarray(q[row]), keys, values, sight)
        assert np.abs(np.asarray(xla[row]) - want).max() < FLOAT32
    with pytest.raises(ValueError, match="power of two"):
        paged_prefill_attention(q, k_plane, v_plane, table, positions,
                                kv_lens, block=6, interpret=True)


@pytest.mark.parametrize("form", ["xla", "pallas-interpret"])
def test_a_blocks_queries_folded_into_the_group_axis(form):
    """The four queries of a block as 4 x group query heads of each KV
    head, one token a row, in both decode forms, against an einsum over
    the row's pages, the tail's finished block and the block itself;
    the tail's mask is fed the block's LAST position, so the slots
    after the block stay out of sight."""
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
    )
    rng = np.random.default_rng(7)
    k_plane, v_plane = _paged(rng)
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    kv_lens = jnp.asarray([40, 20], jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, BLOCK, 4, 16)), jnp.float32)
    k_tail = jnp.asarray(rng.standard_normal((2, 12, 2, 16)), jnp.float32)
    v_tail = jnp.asarray(rng.standard_normal((2, 12, 2, 16)), jnp.float32)
    # The second block of the burst: slots 0..3 finished, 4..7 its own,
    # 8..11 stale.
    last = kv_lens + 7
    folded = fold_block_queries(q, 2)
    assert folded.shape == (2, 2 * BLOCK * 2, 16)
    assert np.array_equal(np.asarray(unfold_block_queries(folded, BLOCK, 2)),
                          np.asarray(q))
    if form == "xla":
        out = paged_attention(folded[:, None], k_plane, v_plane, table,
                              last[:, None], kv_lens, k_tail=k_tail,
                              v_tail=v_tail)[:, 0]
    else:
        out = paged_decode_attention(
            folded, k_plane, v_plane, table, kv_lens, k_tail=k_tail,
            v_tail=v_tail, q_positions=last, interpret=True)
    out = np.asarray(unfold_block_queries(out, BLOCK, 2))
    for row in range(2):
        n = int(kv_lens[row])
        pages = np.asarray(table[row])
        keys = np.concatenate([np.asarray(k_plane)[:, p] for p in pages],
                              -1).transpose(2, 0, 1)[:n]
        values = np.concatenate([np.asarray(v_plane)[:, p] for p in pages],
                                -1).transpose(2, 0, 1)[:n]
        keys = np.concatenate([keys, np.asarray(k_tail[row, :8])])
        values = np.concatenate([values, np.asarray(v_tail[row, :8])])
        want = _softmax_rows(np.asarray(q[row]), keys, values,
                             np.ones((BLOCK, n + 8), bool))
        assert np.abs(out[row] - want).max() < (
            FLOAT32 if form == "xla" else INTERPRET)


def test_a_block_goes_to_its_tail_slots_over_what_was_there():
    tail = jnp.arange(2 * 8 * 1 * 2, dtype=jnp.float32).reshape(2, 8, 1, 2)
    new = -jnp.ones((2, BLOCK, 1, 2), jnp.float32)
    out = np.asarray(write_block_to_tail(
        tail, new, jnp.int32(4), jnp.asarray([True, False])))
    assert np.array_equal(out[0, 4:], np.asarray(new[0]))
    assert np.array_equal(out[0, :4], np.asarray(tail[0, :4]))
    assert np.array_equal(out[1], np.asarray(tail[1]))


def published_rule(conf, masked, quota, strategy, threshold):
    """A numpy transcription of the three rules of the published
    ``block_diffusion_generate`` for one row, kept to masked places
    (reference/sdar_family.py says why); ties to the leftmost."""
    open_ = [i for i in range(len(masked)) if masked[i]]
    by_conf = sorted(open_, key=lambda i: (-conf[i], i))
    if strategy == "sequential":
        pick = open_[:quota]
        if open_:   # the published slice: from the first masked place on
            pick = [i for i in range(open_[0], open_[0] + quota)
                    if i < len(masked) and masked[i]]
    elif strategy == "low_confidence_static":
        pick = by_conf[:quota]
    else:
        high = [i for i in open_ if conf[i] > threshold]
        pick = high if len(high) >= quota else by_conf[:quota]
    return [i in pick for i in range(len(masked))]


def test_unmask_block_against_the_published_rules():
    """Greedy rows (the argmax and its probability) under every rule,
    with ties in confidence, a threshold that fires, one that does not,
    a quota larger than what is left, and rows with nothing masked."""
    rng = np.random.default_rng(11)
    rows, vocab = 12, 32
    logits = rng.standard_normal((BLOCK, rows, vocab)).astype(np.float32)
    # Ties: rows 0 and 1 have the same distribution at every place.
    logits[:, 0] = logits[0, 0]
    logits[:, 1] = logits[0, 0]
    # A confident place: row 2's place 3 and row 3's places 1 and 2.
    for row, place in ((2, 3), (3, 1), (3, 2)):
        logits[place, row, 5] = 12.0
    masked = rng.random((rows, BLOCK)) < 0.7
    masked[0] = masked[2] = masked[3] = True
    masked[1] = [False, True, True, True]
    masked[4] = False
    quota = rng.integers(1, 5, rows).astype(np.int32)
    quota[3] = 2
    strategy = (np.arange(rows) % 3).astype(np.int32)
    strategy[2] = strategy[3] = 2
    threshold = np.full((rows,), 0.9, np.float32)
    zeros = jnp.zeros((rows,), jnp.float32)
    x0, commit, conf = sampling.unmask_block(
        jnp.asarray(logits), jnp.asarray(masked), jnp.asarray(quota),
        jnp.asarray(strategy), jnp.asarray(threshold), zeros,
        jnp.ones((rows,), jnp.float32), jnp.zeros((rows,), jnp.int32),
        jax.random.PRNGKey(0))
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    assert np.array_equal(np.asarray(x0), logits.argmax(-1).T)
    assert np.allclose(np.asarray(conf), probs.max(-1).T, atol=1e-6)
    for row in range(rows):
        want = published_rule(
            np.asarray(conf)[row], masked[row], int(quota[row]),
            sampling.REMASKING_STRATEGIES[strategy[row]],
            float(threshold[row]))
        assert list(np.asarray(commit)[row]) == want, row
    # The threshold fired on row 3 (two confident places, quota 2) and
    # not on row 2 (one, under its quota or not: static then).
    assert list(np.asarray(commit)[3]) == [False, True, True, False]
    assert not np.asarray(commit)[4].any()
    assert not (np.asarray(commit) & ~masked).any()


def test_unmask_block_draws_from_the_rows_own_distribution():
    """Stochastic rows: the draw follows softmax(logits / T) under
    top-k, and the confidence is the drawn token's probability under
    that same distribution."""
    rows, vocab = 64, 16
    base = np.linspace(0.0, 3.0, vocab, dtype=np.float32)
    logits = jnp.asarray(np.tile(base, (BLOCK, rows, 1)))
    ones = jnp.ones((rows,), jnp.float32)
    counts = np.zeros(vocab)
    for seed in range(30):
        x0, commit, conf = sampling.unmask_block(
            logits, jnp.ones((rows, BLOCK), bool),
            jnp.full((rows,), 4, jnp.int32), jnp.zeros((rows,), jnp.int32),
            ones, 0.5 * ones, ones, jnp.full((rows,), 4, jnp.int32),
            jax.random.PRNGKey(seed))
        assert bool(commit.all())
        counts += np.bincount(np.asarray(x0).ravel(), minlength=vocab)
        kept = np.exp(2.0 * base[-4:])
        kept /= kept.sum()
        assert np.allclose(np.asarray(conf),
                           kept[np.asarray(x0) - (vocab - 4)], atol=1e-5)
    assert counts[:-4].sum() == 0           # top-k 4
    share = counts[-4:] / counts.sum()
    assert np.abs(share - kept).max() < 0.02


# ---- the draw by blocks (ops/sampling.py draw_by_blocks) --------------------


# Jitted, as the burst runs it (one program, not an executable an op).
_draw = jax.jit(sampling.draw_by_blocks, static_argnums=1)


def _inversion(plane, scale, u):
    """Float64 ``searchsorted(cumsum(p), u)`` a row, with the running
    sum itself and ``p``: what ``draw_by_blocks`` is held to."""
    x = plane.astype(np.float64) * scale.astype(np.float64)[:, None]
    p = np.exp(x - x.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    run = np.cumsum(p, -1)
    want = np.array([np.searchsorted(run[r], u[r], side="right")
                     for r in range(len(u))])
    return want, run, p


def _held_to_inversion(plane, scale, u, slack=5e-6):
    """Draw with ``u`` given and check every row against the float64
    inversion: the id drawn has positive weight and its stretch of the
    running sum holds ``u`` (to ``slack``, float32 sums against float64
    ones: a ``u`` further than that from every edge must give the very
    id), and the probability returned is the id's."""
    rows = len(u)
    x, conf = _draw(
        (jnp.asarray(plane),), 0, jnp.asarray(scale), jnp.asarray(u),
        jnp.ones((rows,), bool))
    x, conf = np.asarray(x), np.asarray(conf)
    want, run, p = _inversion(plane, scale, u)
    at = np.arange(rows)
    assert ((0 <= x) & (x < plane.shape[1])).all()
    assert (p[at, x] > 0).all()
    below = np.where(x > 0, run[at, np.maximum(x - 1, 0)], 0.0)
    assert (below - slack <= u).all() and (u <= run[at, x] + slack).all()
    clear = np.abs(run - u[:, None]).min(-1) > slack
    assert np.array_equal(x[clear], want[clear])
    assert np.allclose(conf, p[at, x], rtol=1e-4, atol=1e-9)
    return x


def _us(rows, rng, edges=()):
    """``u`` a row: 0, the largest float32 under 1, the given edges of
    the running sum, the rest uniform."""
    u = rng.random(rows).astype(np.float32)
    fixed = [0.0, np.nextafter(np.float32(1.0), np.float32(0.0))]
    fixed += list(edges)
    u[:len(fixed)] = fixed
    return u


DRAW_CASES = ("vocab-16", "vocab-1000", "vocab-151936", "neg-inf-first",
              "neg-inf-last", "neg-inf-block", "dominant", "greedy-ties",
              "position-major", "frequency")


@pytest.mark.parametrize("case", DRAW_CASES)
def test_draw_by_blocks(case):
    rng = np.random.default_rng(DRAW_CASES.index(case))
    block = sampling.DRAW_BLOCK
    if case.startswith("vocab-"):
        # One block, no whole number of blocks, the published width:
        # against the float64 inversion at 0, just under 1, on the
        # edges of the first blocks (a uniform plane, where the running
        # sum at an edge is a float32) and anywhere.
        vocab = int(case.split("-")[1])
        rows = 8 if vocab > 10000 else 32
        scale = rng.uniform(0.5, 2.0, rows).astype(np.float32)
        plane = (2.0 * rng.standard_normal((rows, vocab))).astype(np.float32)
        _, run, _ = _inversion(plane, scale, np.zeros(rows))
        edge = min(block, vocab // 2) - 1
        _held_to_inversion(plane, scale, _us(rows, rng, [
            np.float32(run[2, edge]), np.float32(run[3, 2 * edge + 1])]))
        flat = np.zeros((rows, vocab), np.float32)
        ones = np.ones((rows,), np.float32)
        x = _held_to_inversion(flat, ones, _us(rows, rng, [
            np.float32(min(block, vocab // 2) / vocab)]), slack=1e-9)
        assert x[0] == 0 and x[1] == vocab - 1
        assert x[2] == min(block, vocab // 2)
    elif case.startswith("neg-inf"):
        # Masked ids (the sorted form's NEG_INF) weigh nothing and are
        # never drawn, whatever u: the first ids, the last (with the
        # padding behind them), a whole block in the middle.
        rows, vocab = 64, 300
        plane = rng.standard_normal((rows, vocab)).astype(np.float32)
        gone = {"neg-inf-first": slice(0, 5),
                "neg-inf-last": slice(vocab - 50, vocab),
                "neg-inf-block": slice(block, 2 * block)}[case]
        plane[:, gone] = sampling.NEG_INF
        ones = np.ones((rows,), np.float32)
        _, run, _ = _inversion(plane, ones, np.zeros(rows))
        kept = np.ones(vocab, bool)
        kept[gone] = False
        for edges in ([], [np.float32(run[2, gone.start])],
                      [np.float32(run[2, -1]), np.float32(run[3, 0])]):
            x = _held_to_inversion(plane, ones, _us(rows, rng, edges))
            assert kept[x].all()
        first, last = np.flatnonzero(kept)[[0, -1]]
        assert x[0] == first and x[1] == last
    elif case == "dominant":
        # One id holds all but 1e-15 of the mass, in the last block: u
        # of 0 alone falls before it (on the first id, whose weight is
        # small and not 0).
        rows, vocab = 16, 700
        plane = rng.standard_normal((rows, vocab)).astype(np.float32)
        plane[:, 650] = 40.0
        x = _held_to_inversion(plane, np.ones((rows,), np.float32),
                               _us(rows, rng))
        assert x[0] == 0 and (x[1:] == 650).all()
    elif case == "greedy-ties":
        # A row that is not stochastic: jnp.argmax, the first of equal
        # maxima (inside one block and across blocks), whatever u, with
        # its probability under softmax(plane * scale).
        rows, vocab = 12, 700
        plane = rng.standard_normal((rows, vocab)).astype(np.float32)
        for row, ids in enumerate(((3, 9), (130, 600), (127, 128),
                                   (0, 699), (699,))):
            plane[row, list(ids)] = 7.0
        scale = rng.uniform(0.5, 2.0, rows).astype(np.float32)
        x, conf = _draw(
            (jnp.asarray(plane),), 0, jnp.asarray(scale),
            jnp.asarray(_us(rows, rng)), jnp.zeros((rows,), bool))
        assert np.array_equal(np.asarray(x),
                              np.asarray(jnp.argmax(plane, axis=-1)))
        assert list(np.asarray(x)[:5]) == [3, 130, 127, 0, 699]
        _, _, p = _inversion(plane, scale, np.zeros(rows))
        assert np.allclose(np.asarray(conf), p.max(-1), rtol=1e-5)
    elif case == "position-major":
        # A plane of [S, B, vocab] drawn from as it stands gives what
        # the plane alone gives, rows a multiple of eight or not.
        for rows in (16, 6):
            planes = rng.standard_normal((3, rows, 256)).astype(np.float32)
            scale = rng.uniform(0.5, 2.0, rows).astype(np.float32)
            u = _us(rows, rng)
            mixed = jnp.asarray(np.arange(rows) % 2 == 0)
            for j in range(3):
                whole = _draw(jnp.asarray(planes), j, jnp.asarray(scale),
                              jnp.asarray(u), mixed)
                alone = _draw((jnp.asarray(planes[j]),), 0,
                              jnp.asarray(scale), jnp.asarray(u), mixed)
                assert np.array_equal(whole[0], alone[0])
                assert np.array_equal(whole[1], alone[1])
    else:
        # The stochastic draw through unmask_block at a vocabulary of
        # three blocks: 40960 draws follow softmax(logits / T).
        rows, vocab, temperature = 256, 300, 0.7
        base = (1.5 * rng.standard_normal(vocab)).astype(np.float32)
        logits = jnp.asarray(np.tile(base, (BLOCK, rows, 1)))
        ones = jnp.ones((rows,), jnp.float32)
        counts = np.zeros(vocab)
        p = np.exp(base.astype(np.float64) / temperature)
        p /= p.sum()
        for seed in range(40):
            x0, _, conf = jax.jit(sampling.unmask_block)(
                logits, jnp.ones((rows, BLOCK), bool),
                jnp.full((rows,), 4, jnp.int32),
                jnp.zeros((rows,), jnp.int32), ones, temperature * ones,
                ones, jnp.zeros((rows,), jnp.int32),
                jax.random.PRNGKey(100 + seed))
            counts += np.bincount(np.asarray(x0).ravel(), minlength=vocab)
            assert np.allclose(np.asarray(conf), p[np.asarray(x0)],
                               rtol=1e-4)
        share = counts / counts.sum()
        # Three standard deviations of the likeliest id's share.
        assert np.abs(share - p).max() < 3 * np.sqrt(
            p.max() / counts.sum()) + 1e-4
        assert counts.sum() == 40 * rows * BLOCK


def test_the_samplers_micro_benchmark_runs_both_forms(monkeypatch, capsys):
    """``benchmarks/unmask_iteration.py`` at its rehearsal's size: both
    forms run their passes in one program each, commit what the rule
    says, and draw from the softmax (the times are the CPU's and are
    not looked at)."""
    import json

    from benchmarks import unmask_iteration
    monkeypatch.setattr("sys.argv", [
        "unmask_iteration.py", "--rows", "8", "--vocab", "2048",
        "--passes", "2", "--repeats", "1"])
    unmask_iteration.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert (line["rows"], line["vocab"], line["passes"]) == (8, 2048, 2)
    for form in ("parent", "two_pass"):
        assert line[form]["committed_share"] == 0.5
        assert 0 < line[form]["mean_confidence"] < 1
        assert line[form]["frequency_gap"] < 0.01
        assert len(line[form]["ms_per_pass"]) == 1
