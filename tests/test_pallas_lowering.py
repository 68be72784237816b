"""Compiled Mosaic lowering checks for the Pallas attention kernels.

Round-2 lesson: ``interpret=True`` parity tests validate numerics but
none of Mosaic's tiling/layout rules — the prefill kernel passed every
interpret test and then failed to compile on the real chip (a (1, T)
int32 VMEM block violates the (8, 128) tiling rule). These tests cross-lower the kernels for the TPU
platform from the CPU host (no chip needed): the Pallas→Mosaic lowering
rules — including the BlockSpec tiling checks that failed on hardware —
run in Python during lowering, so the exact class of bug that slipped
through round 2 now fails in CI.

This validates lowering (tiling, layouts, scalar prefetch plumbing),
not Mosaic's final machine-code pass; the server's ``/version`` says
which impl actually served on the chip.

The latent (MLA) kernels, the drafting burst and its sampler's census
are in tests/test_pallas_lowering_latent.py (two files by kernel since
PR 46: ``--dist loadfile`` hands a worker one module at a time).
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402


def _lower_for_tpu(fn, *args):
    """Lower ``fn(*args)`` for the TPU platform from any host."""
    traced = jax.jit(fn).trace(*args)
    return traced.lower(lowering_platforms=("tpu",))


def _decode_args(b=8, num_pages=64, page_size=128, kv_heads=8,
                 q_heads=32, head_dim=64, max_pages=16):
    rng = np.random.RandomState(0)
    q = jnp.asarray(
        rng.randn(b, q_heads, head_dim), jnp.bfloat16)
    kc = jnp.asarray(
        rng.randn(kv_heads, num_pages, head_dim, page_size),
        jnp.bfloat16)
    vc = jnp.asarray(
        rng.randn(kv_heads, num_pages, head_dim, page_size),
        jnp.bfloat16)
    pt = jnp.zeros((b, max_pages), jnp.int32)
    kl = jnp.full((b,), 100, jnp.int32)
    return q, kc, vc, pt, kl


def _prefill_args(b=4, t=512, num_pages=64, page_size=128, kv_heads=8,
                  q_heads=32, head_dim=64, max_pages=64):
    rng = np.random.RandomState(0)
    q = jnp.asarray(
        rng.randn(b, t, q_heads, head_dim), jnp.bfloat16)
    kc = jnp.asarray(
        rng.randn(kv_heads, num_pages, head_dim, page_size),
        jnp.bfloat16)
    vc = jnp.asarray(
        rng.randn(kv_heads, num_pages, head_dim, page_size),
        jnp.bfloat16)
    pt = jnp.zeros((b, max_pages), jnp.int32)
    pos = jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    kl = jnp.full((b,), t, jnp.int32)
    return q, kc, vc, pt, pos, kl


def test_decode_kernel_lowers_for_tpu():
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
    )
    _lower_for_tpu(paged_decode_attention, *_decode_args())


def test_prefill_kernel_lowers_for_tpu():
    """The exact bench-shape prefill program (B=4, T=512) — the shape
    that failed Mosaic compilation in round 2."""
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    _lower_for_tpu(paged_prefill_attention, *_prefill_args())


# The half-width prefill shape (model_runner.prefill_shapes) of the
# cells that serve the Pallas prefill kernel (chipbench/configs/*.json):
# rows, tokens, query heads, kv heads, head_dim, pages, table width.
CELL_HALF_PREFILL = {
    "qwen2.5-3b": (4, 256, 16, 2, 128, 1408, 64),
    "qwen3-next-80b-a3b-ep4": (4, 256, 16, 2, 256, 2048, 64),
    "jamba2-3b": (4, 128, 20, 1, 128, 3072, 32),
    "lfm2-8b-a1b-ep4": (8, 128, 32, 8, 64, 4096, 32),
}


def _cell_half_prefill_shapes(cell, sharding=None):
    """(q, k plane, v plane, table, positions, kv_lens) of one
    layer's call in the cell's half-width prefill step, as shapes."""
    rows, t, q_heads, kv, d, pages, max_pages = CELL_HALF_PREFILL[cell]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    plane = shape((kv, pages, d, 128), jnp.bfloat16)
    return (shape((rows, t, q_heads, d), jnp.bfloat16), plane, plane,
            shape((rows, max_pages), jnp.int32),
            shape((rows, t), jnp.int32), shape((rows,), jnp.int32))


@pytest.mark.parametrize("case", [16, 64, 256]
                         + sorted(CELL_HALF_PREFILL))
def test_prefill_kernel_lowers_every_bucket(case):
    """All prefill shapes the model runner can emit must lower: every
    token bucket at the full width, and the cells' half widths at
    their own head shapes."""
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    args = (_prefill_args(t=case) if isinstance(case, int)
            else _cell_half_prefill_shapes(case))
    text = _lower_for_tpu(paged_prefill_attention, *args).as_text()
    assert "tpu_custom_call" in text


def test_decode_kernel_lowers_small_group():
    """GQA group 1 (MHA): the group axis pads to 8 sublanes."""
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
    )
    _lower_for_tpu(
        paged_decode_attention,
        *_decode_args(kv_heads=8, q_heads=8))


def test_full_model_step_lowers_for_tpu():
    """End-to-end: the llama forward with attention_impl=pallas (both
    kernels inside the layer scan) lowers for TPU."""
    from production_stack_tpu.engine.config import tiny_model_config
    from production_stack_tpu.models.llama import forward, init_params

    config = tiny_model_config("llama")
    config.attention_impl = "pallas"
    params = init_params(config, jax.random.PRNGKey(0))

    b, t = 2, 64
    page_size, num_pages, max_pages = 128, 32, 8
    tokens = jnp.zeros((b, t), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    page_table = jnp.zeros((b, max_pages), jnp.int32)
    kv_lens = jnp.full((b,), t, jnp.int32)
    valid = jnp.ones((b, t), bool)
    cache_shape = (config.num_hidden_layers,
                   config.num_key_value_heads, num_pages,
                   config.head_dim, page_size)
    k_cache = jnp.zeros(cache_shape, config.jax_dtype)
    v_cache = jnp.zeros(cache_shape, config.jax_dtype)

    def step(params, tokens, positions, page_table, kv_lens, valid,
             k_cache, v_cache):
        return forward(params, config, tokens, positions, page_table,
                       kv_lens, valid, k_cache, v_cache)

    _lower_for_tpu(step, params, tokens, positions, page_table,
                   kv_lens, valid, k_cache, v_cache)


def test_decode_burst_program_lowers_for_tpu():
    """The fused K-step decode burst (lax.scan over the pallas-decode
    forward, with donation-style carries, on-device budgets/stops)
    must lower for TPU as one program — kernel-level lowering alone
    misses scan/carry interactions."""
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config,
    )
    from production_stack_tpu.engine.model_runner import ModelRunner

    model = tiny_model_config("llama")
    model.attention_impl = "pallas"
    config = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=128, num_pages=32),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64,
                                  decode_steps=8),
    )
    runner = ModelRunner(config)
    b = 4
    args = (
        runner.params, runner.k_cache, runner.v_cache,
        jnp.zeros((b, 1), jnp.int32), jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b, runner.max_pages_per_seq), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
        jnp.zeros((b,), jnp.int32),
        jnp.full((b, 16), -1, jnp.int32),
        jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32),
        jnp.zeros((b,), jnp.int32), jax.random.PRNGKey(0),
        None, None,   # lora, lora_ids
        None, None,   # penalties, seeding
        None, None, None,  # bias, suppress, fsm
    )
    traced = jax.jit(
        runner._decode_burst_impl, static_argnames=("num_steps",)
    ).trace(*args, num_steps=8)
    traced.lower(lowering_platforms=("tpu",))


# The four cells' decode batches (chipbench/configs/*.json): rows,
# query heads, kv heads, head_dim, pages, table width (max-model-len
# over the page of 128).
CELL_DECODE = {
    "lfm2-8b-a1b-ep4": (256, 32, 8, 64, 4096, 32),
    "qwen2.5-3b": (64, 16, 2, 128, 1408, 64),
    "qwen3-next-80b-a3b-ep4": (128, 16, 2, 256, 2048, 64),
    "jamba2-3b": (128, 20, 1, 128, 3072, 32),
    # No cell: 8 KV heads of 128 (a page over the heads is 256 KB, so
    # a chunk is two pages) under a table of 32k tokens, the longest
    # static unroll ``auto`` serves unmeasured (ROADMAP S2).
    "kv8-d128-32k-table": (64, 32, 8, 128, 8192, 256),
}
cells = pytest.mark.parametrize("cell", sorted(CELL_DECODE))


def _cell_decode_shapes(cell, sharding=None):
    """(q, k plane, v plane, table, kv_lens, layer, k tail, v tail,
    q_positions) of one layer's call in the cell's deferred burst of
    32 steps, as shapes."""
    rows, q_heads, kv, d, pages, max_pages = CELL_DECODE[cell]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    plane = shape((kv, pages, d, 128), jnp.bfloat16)
    tail = shape((rows, 32, kv, d), jnp.bfloat16)
    rows_i32 = shape((rows,), jnp.int32)
    return (shape((rows, q_heads, d), jnp.bfloat16), plane, plane,
            shape((rows, max_pages), jnp.int32), rows_i32, None,
            tail, tail, rows_i32)


@cells
def test_decode_kernel_with_a_tail_lowers_at_the_cells_shapes(cell):
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
    )
    text = _lower_for_tpu(
        paged_decode_attention, *_cell_decode_shapes(cell)).as_text()
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described (not attached) v5e host: the TPU's own
    compiler, Mosaic's machine-code pass and the scoped-VMEM budget
    included, which the Python lowering rules do not run. The
    persistent cache is off meanwhile: such a compile cannot be read
    back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", sorted(CELL_HALF_PREFILL))
def test_prefill_kernel_compiles_for_a_v5e_at_the_half_width(
        cell, one_chip):
    """What ``auto`` probes at start-up on the chip for the shape the
    half-full steps run at, made here."""
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    compiled = jax.jit(paged_prefill_attention).lower(
        *_cell_half_prefill_shapes(cell, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@cells
def test_decode_kernel_with_a_tail_compiles_for_a_v5e(cell, one_chip):
    """What ``auto`` probes at start-up on the chip, made here."""
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
    )
    compiled = jax.jit(paged_decode_attention).lower(
        *_cell_decode_shapes(cell, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layout", ["per_layer", "stacked"])
def test_deferred_decode_burst_program_lowers_for_tpu(layout):
    """The deferred-write burst with the pallas decode form: the
    kernel reads the planes from outside the scan (nothing aliased,
    nothing threaded) and the tails ride the carry."""
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config,
    )
    from production_stack_tpu.engine.model_runner import ModelRunner

    model = tiny_model_config("llama")
    model.attention_impl = "pallas"
    config = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=128, num_pages=32,
                          cache_layout=layout),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64,
                                  decode_steps=8,
                                  deferred_kv_writes=True),
    )
    runner = ModelRunner(config)
    b = 4
    args = (
        runner.params, runner.k_cache, runner.v_cache,
        jnp.zeros((b, 1), jnp.int32), jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b, runner.max_pages_per_seq), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
        jnp.zeros((b,), jnp.int32),
        jnp.full((b, 16), -1, jnp.int32),
        jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32),
        jnp.zeros((b,), jnp.int32), jax.random.PRNGKey(0),
        None, None,   # lora, lora_ids
        None, None,   # penalties, seeding
        None, None, None,  # bias, suppress, fsm
    )
    traced = jax.jit(
        runner._decode_burst_deferred_impl,
        static_argnames=("num_steps",)
    ).trace(*args, num_steps=8)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def _ragged_args(r=8, w=512, num_pages=64, page_size=128, kv_heads=8,
                 q_heads=32, head_dim=64, max_pages=64):
    rng = np.random.RandomState(0)
    q = jnp.asarray(
        rng.randn(r, w, q_heads, head_dim), jnp.bfloat16)
    kc = jnp.asarray(
        rng.randn(kv_heads, num_pages, head_dim, page_size),
        jnp.bfloat16)
    vc = jnp.asarray(
        rng.randn(kv_heads, num_pages, head_dim, page_size),
        jnp.bfloat16)
    pt = jnp.zeros((r, max_pages), jnp.int32)
    kv = jnp.full((r,), w, jnp.int32)
    li = jnp.full((r,), w - 1, jnp.int32)
    dl = jnp.zeros((r,), jnp.int32)
    return q, kc, vc, pt, kv, li, dl


def test_ragged_kernel_lowers_for_tpu():
    """The fused unified-step kernel at a serving-shape [R, W]
    block."""
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    _lower_for_tpu(paged_ragged_attention, *_ragged_args())


@pytest.mark.parametrize("w", [16, 64, 256])
def test_ragged_kernel_lowers_every_width(w):
    """Every W bucket the mixed planner can emit must lower (the
    model runner's _ragged_lowering_error matrix)."""
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    _lower_for_tpu(paged_ragged_attention, *_ragged_args(w=w))


def test_ragged_kernel_lowers_small_head_thin_rows():
    """head_dim=64 with a thin row block: the q/o blocks are not
    naturally (8, 128)-divisible and must pad to true tile multiples
    — the class of shape that lowered cross-platform and then failed
    Mosaic's machine-code pass on chip."""
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    _lower_for_tpu(
        paged_ragged_attention,
        *_ragged_args(r=4, w=4, kv_heads=8, q_heads=8, head_dim=64))


def test_prefill_kernel_lowers_small_head_thin_rows():
    """The class that failed on chip for the prefill kernel: MHA
    (group 1) at a thin verify-style chunk with head_dim=64 — the
    whole-array block escape hatch the Python lowering rules allow is
    NOT honored by the machine-code pass, so the kernel now pads to
    true (8, 128) multiples; this shape is also in the model runner's
    probe matrix via the spec/unified probes."""
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    _lower_for_tpu(
        paged_prefill_attention,
        *_prefill_args(b=8, t=4, kv_heads=8, q_heads=8, head_dim=64))


def _quantize_lowering_cache(cache):
    from production_stack_tpu.ops.quant_kv import QuantKV, quantize_kv
    perm = (0, 1, 3, 2)
    q, scale = quantize_kv(jnp.transpose(cache, perm))
    return QuantKV(jnp.transpose(q, perm), scale)


def test_decode_kernel_int8_lowers_for_tpu():
    """paged_decode_attention over int8 QuantKV pages (extra scale
    DMAs + VMEM scratch) must pass the Mosaic lowering rules."""
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
    )
    q, kc, vc, pt, kl = _decode_args()
    _lower_for_tpu(
        paged_decode_attention, q,
        _quantize_lowering_cache(kc), _quantize_lowering_cache(vc),
        pt, kl)


def test_prefill_kernel_int8_lowers_for_tpu():
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    q, kc, vc, pt, pos, kl = _prefill_args()
    _lower_for_tpu(
        paged_prefill_attention, q,
        _quantize_lowering_cache(kc), _quantize_lowering_cache(vc),
        pt, pos, kl)


def test_ragged_kernel_int8_lowers_for_tpu():
    """paged_ragged_attention over int8 QuantKV pages (scale DMAs
    through the shared pipeline) must pass the Mosaic lowering
    rules."""
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    q, kc, vc, pt, kv, li, dl = _ragged_args()
    _lower_for_tpu(
        paged_ragged_attention, q,
        _quantize_lowering_cache(kc), _quantize_lowering_cache(vc),
        pt, kv, li, dl)


def test_decode_burst_program_int8_lowers_for_tpu():
    """The fused decode burst with --kv-cache-dtype int8 and pallas
    attention: quantize-on-commit + in-kernel dequant + QuantKV
    carries through lax.scan must lower as one TPU program."""
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config,
    )
    from production_stack_tpu.engine.model_runner import ModelRunner

    model = tiny_model_config("llama")
    model.attention_impl = "pallas"
    config = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=128, num_pages=32,
                          kv_cache_dtype="int8"),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64,
                                  decode_steps=8),
    )
    runner = ModelRunner(config)
    assert runner.kv_quantized
    b = 4
    args = (
        runner.params, runner.k_cache, runner.v_cache,
        jnp.zeros((b, 1), jnp.int32), jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b, runner.max_pages_per_seq), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
        jnp.zeros((b,), jnp.int32),
        jnp.full((b, 16), -1, jnp.int32),
        jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32),
        jnp.zeros((b,), jnp.int32), jax.random.PRNGKey(0),
        None, None,   # lora, lora_ids
        None, None,   # penalties, seeding
        None, None, None,  # bias, suppress, fsm
    )
    traced = jax.jit(
        runner._decode_burst_impl, static_argnames=("num_steps",)
    ).trace(*args, num_steps=8)
    traced.lower(lowering_platforms=("tpu",))
