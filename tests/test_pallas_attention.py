"""Pallas paged decode attention vs the XLA reference implementation.

Runs the kernel in interpreter mode (CPU); the same code path compiles
for real TPU. Ground truth is ops.attention.paged_attention at T=1.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from production_stack_tpu.ops.attention import (  # noqa: E402
    paged_attention,
)
from production_stack_tpu.ops.paged_attention_pallas import (  # noqa: E402
    paged_decode_attention,
)


def _setup(b=3, num_pages=16, page_size=8, kv_heads=2, q_heads=8,
           head_dim=64, max_pages=6, seed=0):
    rng = np.random.RandomState(seed)
    q = rng.randn(b, q_heads, head_dim).astype(np.float32)
    k_cache = rng.randn(
        kv_heads, num_pages, head_dim, page_size
    ).astype(np.float32)
    v_cache = rng.randn(
        kv_heads, num_pages, head_dim, page_size
    ).astype(np.float32)
    # Distinct physical pages per sequence (1.. reserved pool).
    page_table = np.zeros((b, max_pages), np.int32)
    next_page = 1
    kv_lens = np.zeros((b,), np.int32)
    for i in range(b):
        n_tokens = rng.randint(1, max_pages * page_size)
        kv_lens[i] = n_tokens
        n_pages = -(-n_tokens // page_size)
        for j in range(n_pages):
            page_table[i, j] = next_page % num_pages or 1
            next_page += 1
    return (jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
            jnp.asarray(page_table), jnp.asarray(kv_lens))


def test_matches_xla_reference():
    q, k_cache, v_cache, page_table, kv_lens = _setup()
    out = paged_decode_attention(
        q, k_cache, v_cache, page_table, kv_lens, interpret=True
    )
    # Reference: T=1 queries positioned at the last cached token.
    ref = paged_attention(
        q[:, None], k_cache, v_cache, page_table,
        (kv_lens - 1)[:, None], kv_lens,
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_single_token_sequence():
    q, k_cache, v_cache, page_table, kv_lens = _setup(b=2, seed=3)
    kv_lens = jnp.asarray([1, 1], jnp.int32)
    out = paged_decode_attention(
        q, k_cache, v_cache, page_table, kv_lens, interpret=True
    )
    ref = paged_attention(
        q[:, None], k_cache, v_cache, page_table,
        (kv_lens - 1)[:, None], kv_lens,
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_gqa_grouping():
    q, k_cache, v_cache, page_table, kv_lens = _setup(
        kv_heads=4, q_heads=16, seed=7
    )
    out = paged_decode_attention(
        q, k_cache, v_cache, page_table, kv_lens, interpret=True
    )
    ref = paged_attention(
        q[:, None], k_cache, v_cache, page_table,
        (kv_lens - 1)[:, None], kv_lens,
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def _prefill_setup(b=2, num_pages=32, page_size=8, kv_heads=2,
                   q_heads=8, head_dim=64, max_pages=6, chunk=16,
                   seed=0):
    """Mid-prefill state: each sequence has some cached context and a
    chunk of T new queries positioned after it."""
    rng = np.random.RandomState(seed)
    q = rng.randn(b, chunk, q_heads, head_dim).astype(np.float32)
    k_cache = rng.randn(
        kv_heads, num_pages, head_dim, page_size).astype(np.float32)
    v_cache = rng.randn(
        kv_heads, num_pages, head_dim, page_size).astype(np.float32)
    page_table = np.zeros((b, max_pages), np.int32)
    positions = np.zeros((b, chunk), np.int32)
    kv_lens = np.zeros((b,), np.int32)
    next_page = 1
    for i in range(b):
        prior = rng.randint(0, (max_pages - 3) * page_size)
        kv_lens[i] = prior + chunk
        n_pages = -(-int(kv_lens[i]) // page_size)
        for j in range(n_pages):
            page_table[i, j] = next_page % num_pages or 1
            next_page += 1
        positions[i] = np.arange(prior, prior + chunk)
    return (jnp.asarray(q), jnp.asarray(k_cache), jnp.asarray(v_cache),
            jnp.asarray(page_table), jnp.asarray(positions),
            jnp.asarray(kv_lens))


def test_prefill_kernel_matches_xla_reference():
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    args = _prefill_setup()
    out = paged_prefill_attention(*args, interpret=True)
    ref = paged_attention(*args)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_prefill_kernel_first_chunk():
    """Chunk starting at position 0 (no prior context)."""
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    (q, k_cache, v_cache, page_table, positions,
     kv_lens) = _prefill_setup(b=1, seed=4)
    positions = jnp.asarray(
        np.arange(q.shape[1], dtype=np.int32)[None])
    kv_lens = jnp.asarray([q.shape[1]], jnp.int32)
    out = paged_prefill_attention(
        q, k_cache, v_cache, page_table, positions, kv_lens,
        interpret=True)
    ref = paged_attention(
        q, k_cache, v_cache, page_table, positions, kv_lens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_prefill_kernel_gqa():
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    args = _prefill_setup(kv_heads=4, q_heads=16, seed=9)
    out = paged_prefill_attention(*args, interpret=True)
    ref = paged_attention(*args)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_decode_stacked_cache_layer_form():
    """The 5D + layer form (what the engine serves: SMEM layer index,
    cache passed through via input/output aliasing) must match the 4D
    per-layer slice at a NONZERO layer, and must hand the caches back
    through unchanged."""
    q, k_cache, v_cache, page_table, kv_lens = _setup(seed=11)
    L, layer = 3, 2
    rng = np.random.RandomState(21)
    k5 = jnp.asarray(rng.randn(L, *k_cache.shape).astype(np.float32))
    v5 = jnp.asarray(rng.randn(L, *v_cache.shape).astype(np.float32))
    out, k_thru, v_thru = paged_decode_attention(
        q, k5, v5, page_table, kv_lens, layer=layer, interpret=True
    )
    ref = paged_decode_attention(
        q, k5[layer], v5[layer], page_table, kv_lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_array_equal(np.asarray(k_thru), np.asarray(k5))
    np.testing.assert_array_equal(np.asarray(v_thru), np.asarray(v5))


def test_prefill_stacked_cache_layer_form():
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    (q, k_cache, v_cache, page_table, positions,
     kv_lens) = _prefill_setup(seed=13)
    L, layer = 3, 1
    rng = np.random.RandomState(23)
    k5 = jnp.asarray(rng.randn(L, *k_cache.shape).astype(np.float32))
    v5 = jnp.asarray(rng.randn(L, *v_cache.shape).astype(np.float32))
    out, k_thru, v_thru = paged_prefill_attention(
        q, k5, v5, page_table, positions, kv_lens, layer=layer,
        interpret=True
    )
    ref = paged_prefill_attention(
        q, k5[layer], v5[layer], page_table, positions, kv_lens,
        interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_array_equal(np.asarray(k_thru), np.asarray(k5))
    np.testing.assert_array_equal(np.asarray(v_thru), np.asarray(v5))


def test_layer_cache_rank_mismatch_raises():
    q, k_cache, v_cache, page_table, kv_lens = _setup()
    with pytest.raises(ValueError, match="layer index and cache rank"):
        paged_decode_attention(
            q, k_cache, v_cache, page_table, kv_lens, layer=0,
            interpret=True)
    k5 = jnp.asarray(np.zeros((2, *k_cache.shape), np.float32))
    with pytest.raises(ValueError, match="layer index and cache rank"):
        paged_decode_attention(
            q, k5, k5, page_table, kv_lens, interpret=True)
    with pytest.raises(ValueError, match="layer index and cache rank"):
        paged_attention(
            q[:, None], k_cache, v_cache, page_table,
            (kv_lens - 1)[:, None], kv_lens, layer=0)


def test_engine_generates_identically_with_pallas_decode(tmp_path):
    """Greedy generation with the pallas decode path (interpret mode)
    must match the XLA decode path token for token."""
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config,
    )
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sequence import SamplingParams

    prompt = list(range(1, 40))

    def gen(impl):
        model = tiny_model_config("llama")
        model.attention_impl = impl
        config = EngineConfig(
            model=model,
            cache=CacheConfig(page_size=16, num_pages=64),
            scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=128,
                                      prefill_chunk_size=64),
        )
        engine = LLMEngine(config)
        seq = engine.generate(prompt, SamplingParams(
            max_tokens=8, temperature=0.0, ignore_eos=True))
        return seq.output_token_ids

    assert gen("pallas-interpret") == gen("xla")


# ---- int8 quantized KV pages (docs/kv_quantization.md) ----------------------


def _quantize_cache(cache):
    """Quantize a [kv, pages, d, ps] (or [L, ...]) cache per
    (page, slot, head) row — the exact layout write_to_pages emits."""
    from production_stack_tpu.ops.quant_kv import QuantKV, quantize_kv
    perm = ((0, 1, 3, 2) if cache.ndim == 4 else (0, 1, 2, 4, 3))
    q, scale = quantize_kv(jnp.transpose(cache, perm))
    return QuantKV(jnp.transpose(q, perm), scale)


def test_paged_decode_attention_int8_parity():
    """bf16-vs-int8 parity for paged_decode_attention: on the SAME
    quantized cache the kernel must match the XLA reference exactly,
    and track the full-precision answer within the rounding budget."""
    q, k_cache, v_cache, page_table, kv_lens = _setup(seed=17)
    k8, v8 = _quantize_cache(k_cache), _quantize_cache(v_cache)
    out = paged_decode_attention(
        q, k8, v8, page_table, kv_lens, interpret=True
    )
    ref = paged_attention(
        q[:, None], k8, v8, page_table,
        (kv_lens - 1)[:, None], kv_lens,
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    full = paged_attention(
        q[:, None], k_cache, v_cache, page_table,
        (kv_lens - 1)[:, None], kv_lens,
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(full), atol=0.15
    )


def test_paged_prefill_attention_int8_parity():
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    (q, k_cache, v_cache, page_table, positions,
     kv_lens) = _prefill_setup(seed=19)
    k8, v8 = _quantize_cache(k_cache), _quantize_cache(v_cache)
    out = paged_prefill_attention(
        q, k8, v8, page_table, positions, kv_lens, interpret=True)
    ref = paged_attention(
        q, k8, v8, page_table, positions, kv_lens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    full = paged_attention(
        q, k_cache, v_cache, page_table, positions, kv_lens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(full), atol=0.15
    )


def test_decode_int8_stacked_cache_layer_form():
    """Stacked quantized caches flow through the aliased layer form:
    output matches the per-layer slice, and BOTH leaves (int8 data +
    scales) hand back through unchanged."""
    q, k_cache, v_cache, page_table, kv_lens = _setup(seed=29)
    L, layer = 3, 2
    rng = np.random.RandomState(31)
    k5 = _quantize_cache(jnp.asarray(
        rng.randn(L, *k_cache.shape).astype(np.float32)))
    v5 = _quantize_cache(jnp.asarray(
        rng.randn(L, *v_cache.shape).astype(np.float32)))
    out, k_thru, v_thru = paged_decode_attention(
        q, k5, v5, page_table, kv_lens, layer=layer, interpret=True
    )
    ref = paged_decode_attention(
        q, k5[layer], v5[layer], page_table, kv_lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    for thru, src in ((k_thru, k5), (v_thru, v5)):
        np.testing.assert_array_equal(np.asarray(thru.data),
                                      np.asarray(src.data))
        np.testing.assert_array_equal(np.asarray(thru.scale),
                                      np.asarray(src.scale))


# ---- the decode kernel beside a deferred-write burst's tail -----------------

# kv heads, head_dim, group: the four cells' attention layers (LFM2,
# Qwen2.5, Qwen3-Next, Jamba).
CELL_HEADS = {"kv8_d64_g4": (8, 64, 4), "kv2_d128_g8": (2, 128, 8),
              "kv2_d256_g8": (2, 256, 8), "kv1_d128_g20": (1, 128, 20)}


@pytest.mark.parametrize("cache", ["bf16", "int8"])
@pytest.mark.parametrize("fill", [0, 13, 32])
@pytest.mark.parametrize("heads", sorted(CELL_HEADS))
def test_decode_with_a_burst_tail_matches_xla(heads, fill, cache):
    """Pages of 128 as the cells keep them, ragged rows: a pad row
    (0), one token, a page's edge from both sides, and a row that
    fills its table; the tail empty (the query at the last cached
    token), part-filled and full. Ground truth is ``paged_attention``
    with the same tails."""
    kv, d, group = CELL_HEADS[heads]
    page, max_pages, slots = 128, 6, 32
    lens = np.array([0, 1, 128, 129, 300, max_pages * page], np.int32)
    b = len(lens)
    rng = np.random.RandomState(kv * d + fill)
    table = np.zeros((b, max_pages), np.int32)
    next_page = 1
    for i, n in enumerate(lens):
        for j in range(-(-int(n) // page)):
            table[i, j] = next_page
            next_page += 1

    def normal(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    k_cache, v_cache = (normal(kv, next_page, d, page) for _ in "kv")
    if cache == "int8":
        k_cache, v_cache = (_quantize_cache(c.astype(jnp.float32))
                            for c in (k_cache, v_cache))
    q = normal(b, kv * group, d)
    k_tail, v_tail = (normal(b, slots, kv, d) for _ in "kv")
    lens, table = jnp.asarray(lens), jnp.asarray(table)
    q_pos = lens + fill - 1
    got = paged_decode_attention(
        q, k_cache, v_cache, table, lens, k_tail=k_tail, v_tail=v_tail,
        q_positions=q_pos, interpret=True)
    want = paged_attention(
        q[:, None], k_cache, v_cache, table, q_pos[:, None], lens,
        k_tail=k_tail, v_tail=v_tail)[:, 0]
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    # A pad row with an empty tail attends nothing: any finite answer.
    rows = slice(1, None) if fill == 0 else slice(None)
    np.testing.assert_allclose(got[rows], want[rows], atol=0.03)


def test_decode_tail_and_positions_go_together():
    q, k_cache, v_cache, page_table, kv_lens = _setup()
    tail = jnp.zeros((q.shape[0], 4, k_cache.shape[0], q.shape[2]))
    with pytest.raises(ValueError, match="go together"):
        paged_decode_attention(q, k_cache, v_cache, page_table, kv_lens,
                               k_tail=tail, v_tail=tail, interpret=True)


def test_decode_stacked_form_with_a_tail_returns_the_output_alone():
    """The burst reads the planes and never writes them: with a tail
    nothing is aliased and nothing is handed back."""
    q, k_cache, v_cache, page_table, kv_lens = _setup(seed=5)
    k5, v5 = (jnp.stack([c * 0, c]) for c in (k_cache, v_cache))
    rng = np.random.RandomState(6)
    tails = [jnp.asarray(rng.randn(q.shape[0], 4, k_cache.shape[0],
                                   q.shape[2]), jnp.float32)
             for _ in "kv"]
    kw = dict(k_tail=tails[0], v_tail=tails[1], q_positions=kv_lens + 2,
              interpret=True)
    out = paged_decode_attention(q, k5, v5, page_table, kv_lens,
                                 layer=1, **kw)
    ref = paged_decode_attention(q, k_cache, v_cache, page_table,
                                 kv_lens, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_chunks_grow_where_the_table_outgrows_the_unroll(
        monkeypatch):
    """A chunk follows the bytes of a page over the kv heads until the
    table would take over MAX_CHUNKS chunks of it; then the chunk
    grows and the unroll stays. The answer is the XLA form's."""
    from production_stack_tpu.ops import paged_attention_pallas as mod
    # 8 kv heads of 128 in bfloat16, pages of 128: two pages a chunk by
    # bytes, four under a table of 32k tokens (tests/
    # test_pallas_lowering.py compiles that shape); the LFM2 cell's
    # four pages stand.
    assert mod.pages_per_chunk(8, 128, 128, 2, 128) == 2
    assert mod.pages_per_chunk(8, 128, 128, 2, 256) == 4
    assert mod.pages_per_chunk(8, 64, 128, 2, 32) == 4
    monkeypatch.setattr(mod, "CHUNK_BYTES", 4096)
    monkeypatch.setattr(mod, "MAX_CHUNKS", 2)
    q, k_cache, v_cache, page_table, kv_lens = _setup(b=4, seed=11)
    assert mod.pages_per_chunk(2, 64, 8, 4, page_table.shape[1]) == 3
    # Not through the jit: a cached trace would keep the old chunk.
    out = paged_decode_attention.__wrapped__(
        q, k_cache, v_cache, page_table, kv_lens, interpret=True)
    ref = paged_attention(
        q[:, None], k_cache, v_cache, page_table,
        (kv_lens - 1)[:, None], kv_lens)[:, 0]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---- fused ragged kernel (unified step, docs/unified_step.md) ---------------


def _ragged_setup(kv_lens, last_index, draft_lens=None, w=8,
                  num_pages=64, page_size=8, kv_heads=2, q_heads=8,
                  head_dim=64, max_pages=8, seed=0):
    """Unified-step state from explicit per-row descriptors, plus the
    [R, W] positions the XLA-composed path materializes (recovered
    through the engine's layout invariant q_start = kv_len - 1 -
    last_index — model_runner.run_unified)."""
    rng = np.random.RandomState(seed)
    r = len(kv_lens)
    kv_lens = np.asarray(kv_lens, np.int32)
    last_index = np.asarray(last_index, np.int32)
    q = rng.randn(r, w, q_heads, head_dim).astype(np.float32)
    k_cache = rng.randn(
        kv_heads, num_pages, head_dim, page_size).astype(np.float32)
    v_cache = rng.randn(
        kv_heads, num_pages, head_dim, page_size).astype(np.float32)
    page_table = np.zeros((r, max_pages), np.int32)
    next_page = 1
    for i in range(r):
        for j in range(-(-int(kv_lens[i]) // page_size)):
            page_table[i, j] = next_page % num_pages or 1
            next_page += 1
    positions = np.maximum(
        (kv_lens - 1 - last_index)[:, None]
        + np.arange(w, dtype=np.int32)[None], 0).astype(np.int32)
    dl = (None if draft_lens is None
          else jnp.asarray(np.asarray(draft_lens, np.int32)))
    return (jnp.asarray(q), jnp.asarray(k_cache),
            jnp.asarray(v_cache), jnp.asarray(page_table),
            jnp.asarray(kv_lens), jnp.asarray(last_index), dl,
            jnp.asarray(positions))


def _assert_live_parity(out, ref, kv_lens, last_index):
    """Compare the live slots only: the composed path computes
    garbage attention in pad slots where the fused kernel writes
    zeros — both are discarded by the sampler's span gather."""
    out, ref = np.asarray(out), np.asarray(ref)
    for i in range(out.shape[0]):
        if int(kv_lens[i]) == 0:
            continue
        n = int(last_index[i]) + 1
        np.testing.assert_allclose(
            out[i, :n], ref[i, :n], rtol=2e-5, atol=2e-5)


def test_ragged_kernel_pure_decode():
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    (q, kc, vc, pt, kv, li, dl, pos) = _ragged_setup(
        kv_lens=[17, 1, 48, 33], last_index=[0, 0, 0, 0], seed=43)
    out = paged_ragged_attention(q, kc, vc, pt, kv, li, dl,
                                 interpret=True)
    ref = paged_attention(q, kc, vc, pt, pos, kv)
    _assert_live_parity(out, ref, kv, li)


def test_ragged_kernel_pure_prefill():
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    # Full-width chunks: one first chunk (q_start 0), one mid-prompt.
    (q, kc, vc, pt, kv, li, dl, pos) = _ragged_setup(
        kv_lens=[8, 29], last_index=[7, 7], seed=47)
    out = paged_ragged_attention(q, kc, vc, pt, kv, li, dl,
                                 interpret=True)
    ref = paged_attention(q, kc, vc, pt, pos, kv)
    _assert_live_parity(out, ref, kv, li)


def test_ragged_kernel_mixed_rows_and_pads():
    """The flagship mix: decode + spec-verify + short chunk + full
    chunk + pad rows, one grid."""
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    (q, kc, vc, pt, kv, li, dl, pos) = _ragged_setup(
        kv_lens=[20, 23, 13, 30, 0, 0],
        last_index=[0, 3, 4, 7, 0, 0],
        draft_lens=[0, 3, 0, 0, 0, 0], seed=53)
    out = paged_ragged_attention(q, kc, vc, pt, kv, li, dl,
                                 interpret=True)
    ref = paged_attention(q, kc, vc, pt, pos, kv)
    _assert_live_parity(out, ref, kv, li)
    # Dead slots and pad rows are fully masked to zero (the composed
    # path leaves garbage there; both are sliced off by the span
    # gather — this contract is what makes the fused output safe to
    # gather from without a validity mask).
    out = np.asarray(out)
    assert np.all(out[1, 4:] == 0)
    assert np.all(out[4] == 0) and np.all(out[5] == 0)


def test_ragged_kernel_verify_span_matches_composed():
    """A spec-verify row's draft span must score exactly like the
    composed prefill path scores it (the draft span is causally
    self-masking — no extra mask term)."""
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    (q, kc, vc, pt, kv, li, dl, pos) = _ragged_setup(
        kv_lens=[25, 41], last_index=[3, 2],
        draft_lens=[3, 2], seed=59)
    out = paged_ragged_attention(q, kc, vc, pt, kv, li, dl,
                                 interpret=True)
    ref = paged_attention(q, kc, vc, pt, pos, kv)
    _assert_live_parity(out, ref, kv, li)


def test_ragged_kernel_draft_lens_invariance():
    """Attention is invariant to draft_lens (the descriptor rides the
    prefetch tuple for the contract; the span is self-masking)."""
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    (q, kc, vc, pt, kv, li, dl, _pos) = _ragged_setup(
        kv_lens=[25, 41], last_index=[3, 2],
        draft_lens=[3, 2], seed=61)
    with_dl = paged_ragged_attention(q, kc, vc, pt, kv, li, dl,
                                     interpret=True)
    without = paged_ragged_attention(q, kc, vc, pt, kv, li, None,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(with_dl),
                                  np.asarray(without))


def test_ragged_kernel_gqa_wide():
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    (q, kc, vc, pt, kv, li, dl, pos) = _ragged_setup(
        kv_lens=[20, 23, 30, 0], last_index=[0, 2, 5, 0],
        draft_lens=[0, 2, 0, 0], kv_heads=4, q_heads=16, w=16,
        seed=67)
    out = paged_ragged_attention(q, kc, vc, pt, kv, li, dl,
                                 interpret=True)
    ref = paged_attention(q, kc, vc, pt, pos, kv)
    _assert_live_parity(out, ref, kv, li)


def test_ragged_stacked_cache_layer_form():
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    (q, kc, vc, pt, kv, li, dl, _pos) = _ragged_setup(
        kv_lens=[20, 23, 30, 0], last_index=[0, 2, 5, 0],
        draft_lens=[0, 2, 0, 0], seed=71)
    L, layer = 3, 2
    rng = np.random.RandomState(73)
    k5 = jnp.asarray(rng.randn(L, *kc.shape).astype(np.float32))
    v5 = jnp.asarray(rng.randn(L, *vc.shape).astype(np.float32))
    out, k_thru, v_thru = paged_ragged_attention(
        q, k5, v5, pt, kv, li, dl, layer=layer, interpret=True)
    ref = paged_ragged_attention(
        q, k5[layer], v5[layer], pt, kv, li, dl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(k_thru), np.asarray(k5))
    np.testing.assert_array_equal(np.asarray(v_thru), np.asarray(v5))


def test_paged_ragged_attention_int8_parity():
    """int8 parity for paged_ragged_attention (kv-parity staticcheck
    contract): on the SAME quantized cache the fused kernel matches
    the XLA reference exactly over the live slots, and tracks the
    full-precision answer within the rounding budget."""
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    (q, kc, vc, pt, kv, li, dl, pos) = _ragged_setup(
        kv_lens=[20, 23, 13, 30, 0], last_index=[0, 3, 4, 7, 0],
        draft_lens=[0, 3, 0, 0, 0], seed=79)
    k8, v8 = _quantize_cache(kc), _quantize_cache(vc)
    out = paged_ragged_attention(q, k8, v8, pt, kv, li, dl,
                                 interpret=True)
    ref = paged_attention(q, k8, v8, pt, pos, kv)
    _assert_live_parity(out, ref, kv, li)
    full = paged_attention(q, kc, vc, pt, pos, kv)
    out, full = np.asarray(out), np.asarray(full)
    for i in range(out.shape[0]):
        if int(kv[i]) == 0:
            continue
        n = int(li[i]) + 1
        np.testing.assert_allclose(out[i, :n], full[i, :n],
                                   atol=0.15)


def test_ragged_int8_stacked_cache_layer_form():
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    (q, kc, vc, pt, kv, li, dl, _pos) = _ragged_setup(
        kv_lens=[20, 23, 30, 0], last_index=[0, 2, 5, 0],
        draft_lens=[0, 2, 0, 0], seed=83)
    L, layer = 3, 1
    rng = np.random.RandomState(89)
    k5 = _quantize_cache(jnp.asarray(
        rng.randn(L, *kc.shape).astype(np.float32)))
    v5 = _quantize_cache(jnp.asarray(
        rng.randn(L, *vc.shape).astype(np.float32)))
    out, k_thru, v_thru = paged_ragged_attention(
        q, k5, v5, pt, kv, li, dl, layer=layer, interpret=True)
    ref = paged_ragged_attention(
        q, k5[layer], v5[layer], pt, kv, li, dl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    for thru, src in ((k_thru, k5), (v_thru, v5)):
        np.testing.assert_array_equal(np.asarray(thru.data),
                                      np.asarray(src.data))
        np.testing.assert_array_equal(np.asarray(thru.scale),
                                      np.asarray(src.scale))


def test_prefill_int8_stacked_cache_layer_form():
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    (q, k_cache, v_cache, page_table, positions,
     kv_lens) = _prefill_setup(seed=37)
    L, layer = 3, 1
    rng = np.random.RandomState(41)
    k5 = _quantize_cache(jnp.asarray(
        rng.randn(L, *k_cache.shape).astype(np.float32)))
    v5 = _quantize_cache(jnp.asarray(
        rng.randn(L, *v_cache.shape).astype(np.float32)))
    out, k_thru, v_thru = paged_prefill_attention(
        q, k5, v5, page_table, positions, kv_lens, layer=layer,
        interpret=True
    )
    ref = paged_prefill_attention(
        q, k5[layer], v5[layer], page_table, positions, kv_lens,
        interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    for thru, src in ((k_thru, k5), (v_thru, v5)):
        np.testing.assert_array_equal(np.asarray(thru.data),
                                      np.asarray(src.data))
        np.testing.assert_array_equal(np.asarray(thru.scale),
                                      np.asarray(src.scale))
