"""Quantized int8 paged KV cache (docs/kv_quantization.md):
config gating + page-budget expansion, ops-level quantization error
bounds, XLA attention parity against full precision, engine-level logit
agreement int8 vs full precision through prefill and decode, greedy
stream stability on quantized pages (prefix-cache hits, speculative
decoding), executable-cache
stability, and /metrics exposition + router scrape of the KV gauges.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ParallelConfig,
    SchedulerConfig,
    tiny_model_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.ops.attention import (
    paged_attention,
    write_to_pages,
)
from production_stack_tpu.ops.quant_kv import (
    QuantKV,
    quant_cache_zeros,
    quantize_kv,
)


def _engine(kv_dtype="auto", num_pages=64, **sched_kw):
    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=num_pages,
                          kv_cache_dtype=kv_dtype),
        scheduler=SchedulerConfig(max_num_seqs=4,
                                  max_model_len=256,
                                  prefill_chunk_size=32,
                                  **sched_kw),
    )
    return LLMEngine(config)


def _prompts():
    rs = np.random.RandomState(3)
    return [
        [5, 6, 7] * 12,
        [9, 9, 9, 9, 9, 9, 9, 9],
        [11, 12, 13, 14] * 20,
        [int(x) for x in rs.randint(1, 500, size=23)],
    ]


def _greedy(engine, prompts, max_tokens=12):
    return [
        list(engine.generate(p, SamplingParams(
            temperature=0.0, max_tokens=max_tokens,
            ignore_eos=True)).output_token_ids)
        for p in prompts
    ]


# ---- config -----------------------------------------------------------------


def test_kv_dtype_validation():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        _engine(kv_dtype="fp8")
    # int8 composes with pipeline and context parallelism: the
    # shard_map boundaries carry congruent QuantKV pytree specs
    # (docs/parallelism.md), so these configs now construct cleanly.
    for parallel in (ParallelConfig(pipeline_parallel_size=2),
                     ParallelConfig(context_parallel_size=2)):
        cfg = EngineConfig(
            model=tiny_model_config("llama"),
            cache=CacheConfig(page_size=16, num_pages=64,
                              kv_cache_dtype="int8"),
            scheduler=SchedulerConfig(max_num_seqs=4,
                                      max_model_len=256),
            parallel=parallel,
        )
        assert cfg.cache.resolved_kv_dtype() == "int8"


def test_page_budget_expansion_and_idempotency():
    model = tiny_model_config("llama")
    model.dtype = "bfloat16"
    base = CacheConfig(page_size=16, num_pages=1024,
                       kv_cache_dtype="int8")
    config = EngineConfig(
        model=model, cache=base,
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256),
    )
    # bf16 slot = 2*d bytes; int8 slot = d + 4 (scale amortized over
    # the head row) -> ~1.88x more pages at the same byte budget for
    # d=32.
    ratio = config.cache.num_pages / 1024
    assert 1.7 <= ratio <= 2.0
    # Same HBM bytes, up to one slot of rounding.
    full_slot = model.head_dim * 2
    assert (config.cache.num_pages * (model.head_dim + 4)
            <= 1024 * full_slot)
    # dataclasses.replace reuses the already-expanded CacheConfig:
    # __post_init__ must not expand twice.
    replaced = dataclasses.replace(config)
    assert replaced.cache.num_pages == config.cache.num_pages

    # Full precision never expands.
    cfg2 = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256),
    )
    assert cfg2.cache.num_pages == 64
    assert cfg2.cache.resolved_kv_dtype() == "bf16"


def test_kv_bytes_accounting():
    model = tiny_model_config("llama")  # f32, d=32, 2L, 2kv
    cache = CacheConfig(page_size=16, num_pages=64,
                        kv_cache_dtype="int8")
    assert cache.kv_slot_bytes(model) == model.head_dim + 4
    assert cache.kv_bytes_per_token(model) == (
        2 * model.num_hidden_layers * model.num_key_value_heads
        * (model.head_dim + 4))
    full = CacheConfig(page_size=16, num_pages=64)
    assert full.kv_slot_bytes(model) == model.head_dim * 4  # f32


# ---- ops --------------------------------------------------------------------


def test_quantize_kv_roundtrip_bound():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 8, 2, 32).astype(np.float32))
    q, scale = quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    dq = q.astype(jnp.float32) * scale[..., None]
    # Symmetric rounding error is at most half a quantization step
    # per element, amax/127 per (token, head) row.
    step = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / 127.0
    assert np.all(np.abs(np.asarray(dq - x)) <= step * 0.5 + 1e-6)


def test_quantkv_pytree_and_indexing():
    kv = quant_cache_zeros((2, 2, 8, 16, 4))
    leaves, treedef = jax.tree_util.tree_flatten(kv)
    assert len(leaves) == 2
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(rebuilt, QuantKV)
    assert rebuilt.data.shape == (2, 2, 8, 16, 4)
    assert rebuilt.scale.shape == (2, 2, 8, 4)
    layer = kv[0]
    assert layer.data.shape == (2, 8, 16, 4)
    assert layer.scale.shape == (2, 8, 4)


def test_paged_attention_int8_parity_with_f32():
    """bf16-vs-int8 parity for paged_attention (the XLA impl): the
    quantized cache's output must track the full-precision one within
    the int8 rounding budget on identical inputs."""
    rs = np.random.RandomState(1)
    kv_heads, pages, d, ps, b, qh = 2, 9, 32, 16, 3, 4
    kf = jnp.asarray(rs.randn(kv_heads, pages, d, ps) * 0.5,
                     jnp.float32)
    vf = jnp.asarray(rs.randn(kv_heads, pages, d, ps) * 0.5,
                     jnp.float32)
    # Quantize the same cache content per (page, slot, head) row.
    kq, ks = quantize_kv(kf.transpose(1, 3, 0, 2))
    vq, vs = quantize_kv(vf.transpose(1, 3, 0, 2))
    k8 = QuantKV(kq.transpose(2, 0, 3, 1), ks.transpose(2, 0, 1))
    v8 = QuantKV(vq.transpose(2, 0, 3, 1), vs.transpose(2, 0, 1))
    q = jnp.asarray(rs.randn(b, 1, qh, d) * 0.5, jnp.float32)
    table = jnp.asarray(
        np.stack([rs.choice(pages - 1, 4, replace=False) + 1
                  for _ in range(b)]),
        jnp.int32)
    kv_lens = jnp.asarray([50, 17, 33], jnp.int32)
    q_pos = (kv_lens - 1)[:, None]
    ref = paged_attention(q, kf, vf, table, q_pos, kv_lens)
    got = paged_attention(q, k8, v8, table, q_pos, kv_lens)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=0.05)


def test_write_to_pages_quantized_matches_full_precision():
    rs = np.random.RandomState(2)
    kv_heads, pages, d, ps, b, t = 2, 6, 32, 16, 2, 5
    new_kv = jnp.asarray(rs.randn(b, t, kv_heads, d), jnp.float32)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    valid = jnp.ones((b, t), bool)
    full = write_to_pages(
        jnp.zeros((kv_heads, pages, d, ps)), new_kv, table,
        positions, valid)
    quant = write_to_pages(
        quant_cache_zeros((kv_heads, pages, d, ps)), new_kv, table,
        positions, valid)
    dq = (quant.data.astype(jnp.float32)
          * quant.scale[:, :, None, :])
    step = (jnp.max(jnp.abs(new_kv), axis=-1).max() / 127.0 + 1e-6)
    assert float(jnp.abs(dq - full).max()) <= float(step) * 0.5 + 1e-6
    # Stacked form with a static layer index scatters identically.
    stacked = write_to_pages(
        quant_cache_zeros((1, kv_heads, pages, d, ps)), new_kv,
        table, positions, valid, layer=0)
    np.testing.assert_array_equal(np.asarray(stacked.data[0]),
                                  np.asarray(quant.data))
    np.testing.assert_array_equal(np.asarray(stacked.scale[0]),
                                  np.asarray(quant.scale))


# ---- engine -----------------------------------------------------------------


def _teacher_forced_logits(kv_dtype, prompt, continuation):
    """Logits of the engine's own forward (its params, its cache form,
    its page table layout) for ``prompt`` prefilled in one block and
    ``continuation`` then decoded one token at a time through the
    cache: row 0 is the last prompt position, row i the i-th decode."""
    engine = _engine(kv_dtype)
    runner, model = engine.runner, engine.config.model
    page = engine.config.cache.page_size
    n_pages = -(-(len(prompt) + len(continuation)) // page)
    table = np.zeros((1, runner.max_pages_per_seq), np.int32)
    table[0, :n_pages] = np.arange(1, n_pages + 1)
    table = jnp.asarray(table)

    @jax.jit
    def forward(tokens, positions, kv_lens, k_cache, v_cache):
        return runner._forward(
            runner.params, model, tokens, positions, table, kv_lens,
            jnp.ones(tokens.shape, bool), k_cache, v_cache,
            lora=None, lora_ids=None)

    t = len(prompt)
    logits, k_cache, v_cache = forward(
        jnp.asarray([prompt], jnp.int32), jnp.arange(t)[None],
        jnp.asarray([t], jnp.int32), runner.k_cache, runner.v_cache)
    rows = [np.asarray(logits[0, -1])]
    for i, token in enumerate(continuation):
        logits, k_cache, v_cache = forward(
            jnp.asarray([[token]], jnp.int32),
            jnp.asarray([[t + i]], jnp.int32),
            jnp.asarray([t + i + 1], jnp.int32), k_cache, v_cache)
        rows.append(np.asarray(logits[0, 0]))
    return np.stack(rows)


def test_int8_logits_track_full_precision():
    """int8 KV pages against full-precision pages, on logits.

    Not on sampled tokens: with random weights the two largest logits
    of a row are often closer (1e-4 here) than the quantization moves
    them, so greedy streams diverge on rounding alone. The int8 cache
    rounds each K/V row to amax/127 steps (<= 0.4% of the row's amax);
    through this 2-layer f32 model that moves logits (std 0.22) by at
    most 0.006 on these prompts. The bound is 0.02 — under a tenth of
    the logit spread, so a cache that lost another bit or two of
    precision (error doubling per bit) fails it."""
    continuation = [17, 3, 250, 99, 41, 7, 300, 12]
    for prompt in _prompts():
        full = _teacher_forced_logits("auto", prompt, continuation)
        int8 = _teacher_forced_logits("int8", prompt, continuation)
        diff = np.abs(int8 - full).max()
        assert diff < 0.02, (len(prompt), diff)
        # ...and the quantized pages really were what attention read.
        assert diff > 1e-5, (len(prompt), diff)


def test_prefix_cache_hit_on_quantized_pages():
    engine = _engine("int8")
    prompt = list(range(2, 66))  # 4 full pages => 3 cacheable
    first = _greedy(engine, [prompt], max_tokens=8)
    hits0 = engine.cache_manager.prefix_hit_tokens
    second = _greedy(engine, [prompt], max_tokens=8)
    assert engine.cache_manager.prefix_hit_tokens > hits0
    assert second == first


def test_prefix_query_tokens_not_counted_when_disabled():
    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64,
                          enable_prefix_caching=False),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256),
    )
    engine = LLMEngine(config)
    _greedy(engine, [_prompts()[0]], max_tokens=4)
    assert engine.cache_manager.prefix_query_tokens == 0
    assert engine.cache_manager.prefix_hit_rate() == 0.0


def test_spec_decode_on_quantized_pages():
    # Draft-free speculation is lossless: spec-on int8 must emit the
    # same greedy stream as spec-off int8 (repetitive prompt so the
    # prompt-lookup proposer actually drafts).
    prompt = list(range(5, 25)) + list(range(5, 25))
    plain = _greedy(_engine("int8"), [prompt], max_tokens=16)
    spec = _engine("int8", speculative_k=3)
    got = _greedy(spec, [prompt], max_tokens=16)
    assert got == plain
    assert spec.metrics.spec_draft_tokens_total > 0


def test_no_per_step_recompiles_int8():
    engine = _engine("int8")
    _greedy(engine, _prompts()[:2], max_tokens=8)
    jit = engine.runner._step_jit
    if not hasattr(jit, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    before = jit._cache_size()
    _greedy(engine, _prompts()[2:], max_tokens=8)
    assert jit._cache_size() == before


# ---- telemetry --------------------------------------------------------------


def test_engine_stats_and_metrics_exposition():
    engine = _engine("int8", num_pages=64)
    st = engine.stats()
    assert st["engine_kv_cache_page_capacity"] == (
        engine.config.cache.num_pages - 1)
    assert st["engine_kv_bytes_per_decode_step"] == (
        4 * engine.config.cache.kv_bytes_per_token(
            engine.config.model))

    import asyncio

    from production_stack_tpu.engine.server import EngineServer
    server = EngineServer(engine, "tiny-llama")
    resp = asyncio.new_event_loop().run_until_complete(
        server.metrics(None))
    text = resp.text
    assert "vllm:engine_kv_cache_page_capacity" in text
    assert "vllm:engine_kv_bytes_per_decode_step" in text
    assert 'vllm:engine_kv_cache_dtype{kv_dtype="int8"} 1.0' in text

    from production_stack_tpu.router.stats.engine_stats import (
        EngineStats,
    )
    scraped = EngineStats.from_prometheus_text(text)
    assert scraped.engine_kv_cache_page_capacity == (
        engine.config.cache.num_pages - 1)
    assert scraped.engine_kv_bytes_per_decode_step == (
        st["engine_kv_bytes_per_decode_step"])
    assert scraped.engine_kv_cache_dtype == "int8"


def test_server_flag_threading():
    from production_stack_tpu.engine.server import parse_args
    args = parse_args(["--kv-cache-dtype", "int8"])
    assert args.kv_cache_dtype == "int8"
    assert parse_args([]).kv_cache_dtype == "auto"
