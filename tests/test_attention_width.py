"""The XLA paged attention gathers as many pages as the call's longest
row holds (ops/attention.py: ``block_pages``, ``gathered_blocks``, one
loop over blocks of the table in ``paged_attention``, the softmax
carried across them), and what it returns is what a softmax over the
whole table returns: positions past ``kv_lens`` weigh exactly 0.

The reference throughout is ``_attend_pages`` below: one softmax over
every page of the [B, max_pages] table, the code before the blocks
existed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops.attention import (
    BLOCK_TOKENS,
    NEG_INF,
    block_pages,
    gather_pages,
    gathered_blocks,
    paged_attention,
)
from production_stack_tpu.ops.quant_kv import QuantKV, quantize_kv


def _attend_pages(q, k_cache_layer, v_cache_layer, page_table,
                  q_positions, kv_lens, layer, k_tail, v_tail):
    """One softmax over every page of ``page_table`` and the tail."""
    if layer is not None:
        k_cache_layer = k_cache_layer[layer]
        v_cache_layer = v_cache_layer[layer]
    b, t, num_q_heads, head_dim = q.shape
    num_kv_heads = k_cache_layer.shape[0]
    group = num_q_heads // num_kv_heads
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, dtype=jnp.float32))

    k = gather_pages(k_cache_layer, page_table)  # [kv, B, P, d, page]
    v = gather_pages(v_cache_layer, page_table)
    quantized = isinstance(k, QuantKV)
    if quantized:
        # Scales broadcast as [B, kv, 1(group), 1(T), P, page].
        k_scale_b = k.scale.transpose(1, 0, 2, 3)[:, :, None, None]
        v_scale_b = v.scale.transpose(1, 0, 2, 3)[:, :, None, None]
        k, v = k.data, v.data
    p_cnt, page = k.shape[2], k.shape[4]

    qg = q.reshape(b, t, num_kv_heads, group, head_dim)
    # scores: [B, kv, group, T, P, page]
    scores = jnp.einsum(
        "btkgd,kbpdc->bkgtpc", qg, k,
        preferred_element_type=jnp.float32,
    ) * scale
    if quantized:
        scores = scores * k_scale_b  # fold k dequant into the logits

    token_pos = (jnp.arange(p_cnt)[:, None] * page
                 + jnp.arange(page)[None, :])  # [P, page]
    causal = (token_pos[None, None]
              <= q_positions[:, :, None, None])  # [B, T, P, page]
    in_len = token_pos[None] < kv_lens[:, None, None]  # [B, P, page]
    mask = causal & in_len[:, None]  # [B, T, P, page]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)

    shape = scores.shape
    flat = scores.reshape(*shape[:-2], p_cnt * page)

    if k_tail is not None:
        # Burst tail: S un-flushed tokens at positions kv_lens + s.
        s_len = k_tail.shape[1]
        t_scores = jnp.einsum(
            "btkgd,bskd->bkgts", qg, k_tail,
            preferred_element_type=jnp.float32,
        ) * scale  # [B, kv, group, T, S]
        tail_pos = (kv_lens[:, None]
                    + jnp.arange(s_len)[None, :])  # [B, S]
        t_mask = (tail_pos[:, None, :]
                  <= q_positions[:, :, None])  # [B, T, S]
        t_scores = jnp.where(t_mask[:, None, None], t_scores, NEG_INF)
        # One softmax over the joint pages+tail token axis.
        joint = jnp.concatenate([flat, t_scores], axis=-1)
        probs = jax.nn.softmax(joint, axis=-1)
        p_pages = probs[..., :p_cnt * page].reshape(shape)
        p_tail = probs[..., p_cnt * page:]
        if quantized:
            p_pages = p_pages * v_scale_b
        else:
            p_pages = p_pages.astype(v.dtype)
        out = jnp.einsum(
            "bkgtpc,kbpdc->btkgd", p_pages, v,
            preferred_element_type=jnp.float32,
        ) + jnp.einsum(
            "bkgts,bskd->btkgd", p_tail.astype(v_tail.dtype), v_tail,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(b, t, num_q_heads, head_dim).astype(q.dtype)

    # Softmax over the joint (P, page) token axis.
    probs = jax.nn.softmax(flat, axis=-1).reshape(shape)  # f32
    if quantized:
        probs = probs * v_scale_b  # fold v dequant; keep f32
    else:
        probs = probs.astype(v.dtype)
    out = jnp.einsum(
        "bkgtpc,kbpdc->btkgd", probs, v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, t, num_q_heads, head_dim).astype(q.dtype)


PAGE = 16
MAX_PAGES = 256  # four blocks of 64: edges at 1024, 2048, 3072, 4096 tokens
HEAD_DIM = 16
ROWS = 3  # the long row, a short one, a pad row


@pytest.mark.parametrize("max_pages,page,want", [
    (64, 128, 8),    # the chip cell's flags
    (512, 16, 64),   # page 16: 1024 tokens all the same
    (64, 256, 4),
    (16, 128, 8),
    (9, 128, 8),
    (8, 128, 8),
    (4, 128, 4),     # a table under a block: the table itself
    (16, 16, 16),
    (1, 16, 1),
    (3, 1024, 1),
])
def test_a_block_is_1024_tokens_or_the_table(max_pages, page, want):
    assert block_pages(max_pages, page) == want
    assert want == min(max_pages, -(-BLOCK_TOKENS // page))


@pytest.mark.parametrize("max_len,want", [
    (0, 1), (1, 1), (1023, 1), (1024, 1), (1025, 2), (1300, 2),
    (2048, 2), (2049, 3), (4096, 4), (4097, 5), (8192, 8),
    (9000, 8),  # past the table: all of it, never a block past it
])
def test_gathered_blocks_hold_the_longest_row(max_len, want):
    assert gathered_blocks(max_len, 64, 128) == want
    traced = jax.jit(lambda n: gathered_blocks(n, 64, 128))(max_len)
    assert int(traced) == want


@pytest.mark.parametrize("max_pages,page,max_len,want", [
    (100, 16, 1500, 2),   # 100 pages are one block and 36 pages
    (100, 16, 1600, 2),   # the table's end, past the last whole block
    (9, 128, 1100, 2),
    (4, 128, 500, 1),
    (16, 16, 256, 1),
])
def test_a_table_that_is_no_whole_number_of_blocks(max_pages, page,
                                                   max_len, want):
    assert gathered_blocks(max_len, max_pages, page) == want


def _case(heads=(16, 2), quant=False, stacked=False, tail=0, t=1,
          max_pages=MAX_PAGES, seed=0):
    """Caches, a table of distinct pages and queries; lengths come
    later, as data, so one compile serves every length of a variant."""
    nq, nkv = heads
    num_pages = ROWS * max_pages + 1
    rs = np.random.RandomState(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    lead = (3,) if stacked else ()
    shape = lead + (nkv, num_pages, HEAD_DIM, PAGE)
    k = jax.random.normal(keys[0], shape, jnp.bfloat16)
    v = jax.random.normal(keys[1], shape, jnp.bfloat16)
    if quant:
        def q8(x):  # [.., kv, pages, d, page] -> QuantKV, per-slot scale
            data, scale = quantize_kv(jnp.moveaxis(x, -2, -1))
            return QuantKV(jnp.moveaxis(data, -1, -2), scale)
        k, v = q8(k), q8(v)
    table = jnp.asarray(
        rs.permutation(np.arange(1, num_pages)).reshape(ROWS, max_pages),
        jnp.int32)
    table = table.at[ROWS - 1].set(0)  # the pad row reads the trash page
    q = jax.random.normal(keys[2], (ROWS, t, nq, HEAD_DIM), jnp.bfloat16)
    tails = None
    if tail:
        tails = tuple(
            jax.random.normal(kk, (ROWS, tail, nkv, HEAD_DIM),
                              jnp.bfloat16) for kk in keys[3:5])
    return q, k, v, table, tails, 1 if stacked else None


def _lens(longest, t, tail):
    """kv_lens and query positions for a call whose long row's pages
    hold ``longest`` tokens: without a tail the T queries are the last
    T cached tokens; with one they sit ``tail`` slots past the pages."""
    kv = np.asarray([longest, min(longest, 37), 0], np.int32)
    first = kv + (tail - 1 if tail else -t)
    pos = np.maximum(first[:, None] + np.arange(t)[None, :], 0)
    return jnp.asarray(kv), jnp.asarray(pos, jnp.int32)


VARIANTS = {
    "bf16": {},
    "bf16-tail": dict(tail=8),
    "int8": dict(quant=True),
    "int8-tail": dict(quant=True, tail=8),
    "gqa32x8": dict(heads=(32, 8)),
    "gqa32x8-int8-tail": dict(heads=(32, 8), quant=True, tail=8),
    "stacked": dict(stacked=True),
    "stacked-tail": dict(stacked=True, tail=8),
    "stacked-int8": dict(stacked=True, quant=True),
    "chunk": dict(t=24),
    "chunk-int8-stacked": dict(t=24, quant=True, stacked=True),
}
EDGES = [n * BLOCK_TOKENS + d for n in (1, 2, 3, 4)
         for d in (-1, 0, 1) if n * BLOCK_TOKENS + d <= MAX_PAGES * PAGE]


@pytest.fixture(scope="module")
def compiled():
    """One blockwise and one whole-table program per variant."""
    made = {}

    def get(name):
        if name not in made:
            kw = VARIANTS[name]
            q, k, v, table, tails, layer = _case(**kw)
            kt, vt = tails or (None, None)

            def run(fn):
                return jax.jit(lambda kv_lens, pos: fn(
                    q, k, v, table, pos, kv_lens, layer, kt, vt))

            made[name] = (run(paged_attention), run(_attend_pages),
                          kw.get("t", 1), kw.get("tail", 0))
        return made[name]

    return get


@pytest.mark.parametrize("longest", EDGES)
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_blockwise_equals_one_softmax_over_the_table(compiled, variant,
                                                    longest):
    blockwise, full, t, tail = compiled(variant)
    kv_lens, pos = _lens(longest, t, tail)
    got = np.asarray(blockwise(kv_lens, pos), np.float32)
    want = np.asarray(full(kv_lens, pos), np.float32)
    assert np.isfinite(got).all()
    # Within bf16 rounding of outputs of magnitude under 1: the sums
    # run in another order and the weights are rounded before they
    # are normalised, not after.
    np.testing.assert_allclose(got, want, atol=2 ** -8, rtol=2 ** -7)


@pytest.mark.parametrize("tail", [0, 8])
@pytest.mark.parametrize("longest,reads_past", [
    (1024, False), (1025, True), (2048, True)])
def test_pages_past_the_blocks_asked_for_are_not_read(longest,
                                                      reads_past, tail):
    """Table entries past the first block point at a page of NaN: a
    call that block holds never sees them, a longer one gathers a
    second block and does (through 0 x NaN in the value contraction),
    which shows the count follows the lengths and not the table."""
    q, k, v, table, tails, _ = _case(tail=tail)
    kt, vt = tails or (None, None)
    poison = k.shape[1] - 1
    v = v.at[:, poison].set(jnp.nan)
    table = jnp.where(
        jnp.arange(MAX_PAGES)[None, :] >= block_pages(MAX_PAGES, PAGE),
        poison, jnp.minimum(table, poison - 1))
    kv_lens, pos = _lens(longest, 1, tail)
    out = np.asarray(paged_attention(q, k, v, table, pos, kv_lens,
                                     k_tail=kt, v_tail=vt), np.float32)
    # The count is the batch's: the short row reads what the long one
    # needs.
    assert np.isnan(out[0]).any() == reads_past
    assert np.isnan(out[1]).any() == reads_past


@pytest.mark.parametrize("tail", [0, 8])
@pytest.mark.parametrize("variant", ["bf16", "int8"])
def test_a_row_does_not_depend_on_what_the_batch_asks_for(variant, tail):
    """A block past a row's length changes nothing of the row, to the
    bit: the short row alone (one block) against the short row beside
    a row that needs four."""
    q, k, v, table, tails, _ = _case(quant=variant == "int8", tail=tail)
    kt, vt = tails or (None, None)
    fn = jax.jit(lambda kv_lens, pos: paged_attention(
        q, k, v, table, pos, kv_lens, k_tail=kt, v_tail=vt))
    alone_lens, pos = _lens(37, 1, tail)
    beside_lens = alone_lens.at[0].set(4000)
    np.testing.assert_array_equal(
        np.asarray(fn(alone_lens, pos), np.float32)[1],
        np.asarray(fn(beside_lens, pos), np.float32)[1])


@pytest.mark.parametrize("variant", ["bf16", "bf16-tail", "int8-tail",
                                     "stacked", "chunk"])
def test_a_batch_of_pad_rows_asks_for_nothing(compiled, variant):
    """kv_lens all 0, the pad row's table the trash page: one block,
    finite, and the whole table's answer."""
    blockwise, full, t, _ = compiled(variant)
    kv_lens = jnp.zeros((ROWS,), jnp.int32)
    pos = jnp.zeros((ROWS, t), jnp.int32)
    assert gathered_blocks(0, MAX_PAGES, PAGE) == 1
    got = np.asarray(blockwise(kv_lens, pos), np.float32)
    assert np.isfinite(got).all()
    # Only the pad row's table is the trash page in this fixture; it is
    # the row a pad batch is made of.
    want = np.asarray(full(kv_lens, pos), np.float32)
    np.testing.assert_allclose(got[-1], want[-1], atol=2 ** -8,
                               rtol=2 ** -7)


@pytest.mark.parametrize("max_pages", [4, 16, 64, 100])
@pytest.mark.parametrize("tail", [0, 8])
def test_tables_of_one_block_and_of_no_whole_number_of_blocks(
        max_pages, tail):
    """A table within one block is gathered whole, with no loop in the
    program; one of 100 pages ends in a block of 36 and 28 of padding."""
    q, k, v, table, tails, _ = _case(tail=tail, max_pages=max_pages)
    kt, vt = tails or (None, None)
    kv_lens, pos = _lens(max_pages * PAGE - 3, 1, tail)

    def run(fn):
        return lambda kv_lens, pos: fn(q, k, v, table, pos, kv_lens,
                                       None, kt, vt)

    looped = "while" in str(jax.make_jaxpr(run(paged_attention))(
        kv_lens, pos))
    assert looped == (max_pages > block_pages(max_pages, PAGE))
    np.testing.assert_allclose(
        np.asarray(run(paged_attention)(kv_lens, pos), np.float32),
        np.asarray(run(_attend_pages)(kv_lens, pos), np.float32),
        atol=2 ** -8, rtol=2 ** -7)


@pytest.mark.parametrize("start", [1020, 2044])
def test_the_count_follows_a_row_that_grows_inside_a_scan(start):
    """An eager burst's carry: kv_lens grows a token a step and
    crosses a block's edge mid-scan; every step equals one softmax
    over the table."""
    q, k, v, table, _, _ = _case()

    def steps(fn):
        def body(kv_lens, _):
            out = fn(q, k, v, table, (kv_lens - 1)[:, None].clip(0),
                     kv_lens, None, None, None)
            return kv_lens + jnp.asarray([1, 1, 0]), out
        kv0 = jnp.asarray([start, 5, 0], jnp.int32)
        return jax.jit(lambda: jax.lax.scan(body, kv0, None, length=8))()

    (end, got), (_, want) = steps(paged_attention), steps(_attend_pages)
    assert int(end[0]) == start + 8
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[:, :2],
        np.asarray(want, np.float32)[:, :2], atol=2 ** -8, rtol=2 ** -7)
