"""Compiled Mosaic lowering checks for the latent (MLA) attention
kernels, the drafting burst and its sampler: the half of
tests/test_pallas_lowering.py that reads the latent families' cells
(its docstring says what such a check proves and what it does not).
Two files since PR 46, by kernel: ``--dist loadfile`` hands a worker one
module at a time, and the two together were 393 s of a lane whose
modules are held under 300 s.
"""

import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from test_pallas_lowering import _lower_for_tpu, one_chip  # noqa: E402,F401


# ---- the latent (MLA) decode kernel at the published sizes -----------------

# longcat-flash-omni-ep32's decode batch: rows, heads, the query head
# (128 + 64), the latent (512 + 64), the value head, pages, the table's
# width (max-model-len 4352 over the page of 128).
LATENT_CELL = (160, 64, 128, 64, 512, 128, 3328, 34)


def _latent_decode_shapes(steps=32, sharding=None):
    """(q, plane, table, kv_lens, w_uk, w_uv, tail, q_positions) of one
    sublayer's call in the cell's deferred burst, as shapes."""
    rows, n, dn, dr, rank, dv, pages, max_pages = LATENT_CELL

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    rows_i32 = shape((rows,), jnp.int32)
    return (shape((rows, n, dn + dr)), shape((1, pages, rank + dr, 128)),
            shape((rows, max_pages), jnp.int32), rows_i32,
            shape((n, dn, rank)), shape((n, rank, dv)),
            shape((rows, steps, 1, rank + dr)), rows_i32)


def _latent_decode(q, plane, table, lens, w_uk, w_uv, tail, positions):
    from production_stack_tpu.ops.mla_attention_pallas import (
        latent_paged_decode_attention,
    )
    return latent_paged_decode_attention(
        q, plane, table, lens, w_uk, w_uv, 192 ** -0.5, tail=tail,
        q_positions=positions)


def test_latent_decode_kernel_with_a_tail_lowers_at_the_cells_shapes():
    text = _lower_for_tpu(_latent_decode,
                          *_latent_decode_shapes()).as_text()
    assert "tpu_custom_call" in text


def test_latent_decode_kernel_with_a_tail_compiles_for_a_v5e(one_chip):
    """What ``auto`` probes at start-up on the chip, made here: a
    shape the compiler refuses is found on the CPU."""
    compiled = jax.jit(_latent_decode).lower(
        *_latent_decode_shapes(sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_latent_decode_kernel_without_a_tail_compiles_for_a_v5e(one_chip):
    """A single step (the harness's check requests decode a token at a
    time after their prefill): the same kernel with no tail's link, no
    tail block and no positions among its scalars."""
    from production_stack_tpu.ops.mla_attention_pallas import (
        latent_paged_decode_attention,
    )
    q, plane, table, lens, w_uk, w_uv, _, _ = _latent_decode_shapes(
        sharding=one_chip)
    compiled = jax.jit(
        lambda *a: latent_paged_decode_attention(*a, 192 ** -0.5)).lower(
        q, plane, table, lens, w_uk, w_uv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", ["longcat", "glm-verify", "glm-module"])
def test_the_latent_call_hands_no_softmax_state_through_hbm(shape, one_chip):
    """One sublayer's call of a deferred burst at each cell's shape
    (``benchmarks/latent_walk_iteration.py``), compiled for the
    described chip: the kernel's one result is the normalised weighted
    latents in bfloat16, and no float32 array of ``rows x heads x
    rank`` (the accumulator) or ``rows x heads x 128`` (the statistics'
    tile) is made anywhere between the custom call and the
    up-projection, as PR 44's form made eleven times a call (PERF.md
    section 6, PR 45); nothing of the plane's size appears either."""
    from benchmarks.latent_walk_iteration import (
        PAGE,
        ROWS,
        SHAPES,
        TABLE_PAGES,
        array_census,
        make_case,
        sublayer,
    )
    from production_stack_tpu.ops import mla_attention_pallas
    heads, positions, _, _, rank = SHAPES[shape][:5]
    args, _ = make_case(SHAPES[shape], ROWS, PAGE, TABLE_PAGES, 1,
                        jax.random.PRNGKey(0), as_shapes=one_chip)
    text = jax.jit(sublayer(mla_attention_pallas, SHAPES[shape], False)
                   ).lower(*args).compile().as_text()
    plane = args[1].shape
    census = array_census(text, ROWS, heads * positions, rank,
                          plane[1] * plane[2] * plane[3])
    assert census == {"float32_state": {}, "plane_sized": {}}
    query_rows = -(-heads * positions // 16) * 16
    call = [line for line in text.splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(call) == 1
    assert f"= bf16[{ROWS},{query_rows},{rank}]" in call[0]


def test_that_census_sees_a_state_on_its_way_through_hbm():
    """The control: lines as PR 44's compiled call had them."""
    from benchmarks.latent_walk_iteration import array_census
    text = "\n".join([
        "  %call.1 = (f32[160,64,512]{2,1,0:T(8,128)}, f32[160,64,128]"
        "{2,1,0:T(8,128)}) custom-call(%pad.0, %plane.1), "
        "custom_call_target=\"tpu_custom_call\"",
        "  %divide.1 = f32[160,64,512]{2,1,0:T(8,128)} divide(%a, %b)",
        "  %slice.3 = f32[160,40,512]{2,1,0:T(8,128)} slice(%call.2)",
        "  %plane.1 = bf16[1,100,576,128]{3,2,1,0} parameter(1)",
        "  %copy.9 = bf16[1,100,576,128]{3,2,1,0} copy(%plane.1)",
        "  %q.1 = bf16[160,64,576]{2,1,0} parameter(0)"])
    assert array_census(text, 160, 64, 512, 100 * 576 * 128) == {
        "float32_state": {"f32[160,64,512] custom-call": 1,
                          "f32[160,64,128] custom-call": 1,
                          "f32[160,64,512] divide": 1},
        "plane_sized": {"bf16[1,100,576,128] copy": 1}}
    assert array_census(text, 160, 40, 512, 1)["float32_state"] == {
        "f32[160,40,512] slice": 1}


def test_the_latent_decode_step_never_expands_cached_tokens_to_heads():
    """One decode step of the whole model at the published widths,
    lowered for the TPU in the form the cell's burst runs (the Pallas
    latent kernel, the grouped expert product, tails of 24 steps so
    that the tail's axis is no other's): no array anywhere holds
    per-head keys or values (64 heads of 192 or 128) for cached tokens,
    the tail's 24 or the plane's 3328 pages: materialised, the tail
    alone would be [160, 24, 64, 128] and the pages 35 times their
    bytes, and every test of the numbers would pass."""
    import json
    import os
    import re

    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models import longcat_flash
    from production_stack_tpu.models.registry import init_hybrid_cache

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs",
        "longcat-flash-omni-ep32.json")
    with open(path) as f:
        hf = json.load(f)
    hf.pop("chipbench")
    config = ModelConfig.from_hf_config(hf)
    config.attention_impl = "pallas"
    params = jax.eval_shape(
        lambda key: longcat_flash.init_params(config, key),
        jax.random.PRNGKey(0))
    k_cache, v_cache = jax.eval_shape(
        lambda: init_hybrid_cache(config, 3328, 128, 0))
    rows, steps = 160, 24
    tails = tuple(jax.ShapeDtypeStruct((rows, steps, 1, 576), jnp.bfloat16)
                  for _ in range(8)) + (k_cache[-1],)

    def i32(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.int32)

    def step(params, k_cache, tails, tokens, positions, table, lens, valid):
        return longcat_flash.forward(
            params, config, tokens, positions, table, lens, valid, k_cache,
            v_cache, kv_tail=(tails, v_cache))

    real = longcat_flash.hybrid_kernel_impl
    longcat_flash.hybrid_kernel_impl = lambda c: "pallas"
    try:
        text = jax.jit(step).trace(
            params, k_cache, tails, i32(rows, 1), i32(rows, 1),
            i32(rows, 34), i32(rows),
            jax.ShapeDtypeStruct((rows, 1), jnp.bool_)).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        longcat_flash.hybrid_kernel_impl = real
    # The latent kernel and the grouped product's two, each a function
    # of the module that the sublayers and the branches call.
    assert text.count("tpu_custom_call") >= 3
    shapes = {tuple(int(d) for d in dims.split("x"))
              for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)
              for dims in [dims.rstrip("x")]}
    assert (rows, steps, 1, 576) in shapes       # the latent tail
    assert (1, 3328, 576, 128) in shapes         # the plane
    assert (rows, 64, 192) in shapes             # a row's own query
    expanded = [s for s in shapes
                if 64 in s and (192 in s or 128 in s)
                and (steps in s or 3328 in s or 34 in s)]
    assert not expanded, expanded


# ---- the latent kernel's verify form and the drafting burst ----------------

# glm-4.7-flash-pp8's decode batch: rows, heads, the query head (192 +
# 64), the latent (512 + 64), the value head, pages, the table's width,
# and the two positions a row a burst iteration verifies.
VERIFY_CELL = (160, 20, 192, 64, 512, 256, 3328, 34, 2)


def _latent_verify_shapes(steps=32, sharding=None):
    """(q, plane, table, kv_lens, w_uk, w_uv, tail, q_positions) of one
    entry's call in the cell's drafting burst, as shapes: tails of two
    slots an iteration."""
    rows, n, dn, dr, rank, dv, pages, max_pages, t = VERIFY_CELL

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    return (shape((rows, t, n, dn + dr)), shape((1, pages, rank + dr, 128)),
            shape((rows, max_pages), jnp.int32), shape((rows,), jnp.int32),
            shape((n, dn, rank)), shape((n, rank, dv)),
            shape((rows, t * steps, 1, rank + dr)),
            shape((rows, t), jnp.int32))


def _latent_verify(q, plane, table, lens, w_uk, w_uv, tail, positions):
    from production_stack_tpu.ops.mla_attention_pallas import (
        latent_paged_verify_attention,
    )
    return latent_paged_verify_attention(
        q, plane, table, lens, w_uk, w_uv, 256 ** -0.5, tail=tail,
        q_positions=positions)


def test_latent_verify_kernel_lowers_at_the_cells_shapes():
    """2 x 20 heads are the kernel's 40 rows, padded to 48."""
    text = _lower_for_tpu(_latent_verify,
                          *_latent_verify_shapes()).as_text()
    assert "tpu_custom_call" in text
    assert "160x48x576xbf16" in text


def test_latent_verify_kernel_compiles_for_a_v5e(one_chip):
    """What ``auto`` probes at start-up on the chip where the burst
    drafts, made here."""
    compiled = jax.jit(_latent_verify).lower(
        *_latent_verify_shapes(sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_the_drafting_burst_and_its_prefill_step_lower_for_tpu():
    """The whole burst of a family that drafts (two positions a row
    through the Pallas latent kernel's verify form, the verify rule
    with the module's distribution, the module on what was committed,
    tails of two slots an iteration) and the prefill step that fills
    the module's cache entry, as TPU programs; the burst's named
    scopes are in its text."""
    from production_stack_tpu.engine import config as cfg
    from production_stack_tpu.engine.model_runner import ModelRunner

    rows, steps, chunk = 4, 8, 64
    model = cfg.tiny_glm4_moe_lite_config()
    model.attention_impl, model.dtype = "pallas", "bfloat16"
    runner = ModelRunner(cfg.EngineConfig(
        model=model,
        cache=cfg.CacheConfig(page_size=128, num_pages=32),
        scheduler=cfg.SchedulerConfig(
            max_num_seqs=rows, max_model_len=256, prefill_chunk_size=chunk,
            decode_steps=steps, deferred_kv_writes=True,
            draft_module=True)))

    def i32(*dims):
        return jnp.zeros(dims, jnp.int32)

    sampling = (jnp.zeros((rows,), jnp.float32),
                jnp.ones((rows,), jnp.float32), i32(rows),
                jax.random.PRNGKey(0)) + (None,) * 7
    burst = jax.jit(runner._decode_burst_draft_impl,
                    static_argnames=("num_steps",)).trace(
        runner.params, runner.k_cache, runner.v_cache, i32(rows, 1),
        i32(rows, 1), i32(rows, runner.max_pages_per_seq), i32(rows),
        jnp.zeros((rows,), bool), i32(rows),
        jnp.full((rows, 16), -1, jnp.int32), *sampling, num_steps=steps,
        draft_rows=jnp.ones((rows,), bool))
    text = burst.lower(lowering_platforms=("tpu",)).as_text(
        debug_info=True)
    assert "tpu_custom_call" in text
    for scope in ("mtp_draft", "mtp_verify", "mla_decode", "moe_experts",
                  "dense_ffn"):
        assert scope in text, scope
    assert f"{rows}x{2 * steps}x1x32xbf16" in text      # the tails
    step = jax.jit(runner._step_impl, static_argnames=(
        "sample_index_mode", "want_logprobs")).trace(
        runner.params, runner.k_cache, runner.v_cache, i32(rows, chunk),
        i32(rows, chunk), i32(rows, runner.max_pages_per_seq), i32(rows),
        jnp.zeros((rows, chunk), bool), i32(rows), *sampling,
        sample_index_mode="last", next_tokens=i32(rows))
    assert "mtp_draft" in step.lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


def _sampler_census(form, rows, vocab, one_chip):
    from benchmarks.mtp_sampler_iteration import (
        FORMS,
        plane_census,
        step_shapes,
    )
    text = jax.jit(FORMS[form][0]).lower(
        *step_shapes(form, rows, vocab, one_chip)).compile().as_text()
    return text, plane_census(text, rows, vocab)


def test_the_drafting_iterations_sampler_reads_dense_planes(one_chip):
    """The drafting iteration's sampler (``verify_proposal`` on the two
    positions' logits, ``draw_proposal`` on the module's) at the GLM
    cell's 160 rows and vocabulary of 154880, compiled for the
    described chip: the only float32 array of ``B x 2 x V`` elements in
    its text is the one the head wrote, positions OUTERMOST under dense
    (8, 128) tiles, and nothing reshapes, transposes, copies, pads or
    gathers one; outside the branches that sort the vocabulary at most
    fourteen instructions touch a plane at all (twelve as written: an
    argmax a position, the proposal's max, the three log-sum-exps in
    one fusion, the last pass, the draw, the all-greedy draw, one
    async copy that parks a plane in VMEM, four gathers of an element
    a row). PR 43's form kept the
    positions in a minor axis, under (2, 128) tiles, and spent 8.6 ms
    an iteration there (PERF.md section 6, PR 44); the control below
    shows this census sees that form."""
    rows, vocab = VERIFY_CELL[0], 154880
    text, census = _sampler_census("planes", rows, vocab, one_chip)
    assert census["pair_arrays"], "the census found no logits at all"
    for found in census["pair_arrays"]:
        shape, opcode = found.split(" ")
        assert shape == f"f32[2,{rows},{vocab}]{{2,1,0:T(8,128)}}", found
        assert opcode in ("parameter", "get-tuple-element", "tuple",
                          "bitcast"), found
    assert f"f32[{rows},2,{vocab}]" not in text
    passes = [p for name, found in census["plane_passes"].items()
              if "sorts" not in name for p in found
              if not p.startswith("conditional")]
    assert 6 <= len(passes) <= 14, passes


def test_that_census_sees_the_position_minor_form(one_chip):
    """The control: PR 43's form (kept in
    ``benchmarks/mtp_sampler_iteration.py``) holds ``[B, 2, V]`` under
    (2, 128) tiles and relayouts of it; at a small vocabulary, which
    shows the same."""
    rows, vocab = VERIFY_CELL[0], 2048
    _, census = _sampler_census("parent", rows, vocab, one_chip)
    minor = [f for f in census["pair_arrays"]
             if f.startswith(f"f32[{rows},2,{vocab}]")]
    assert any("T(2,128)" in f for f in minor), census["pair_arrays"]
    assert {f.split(" ")[1] for f in census["pair_arrays"]} & {
        "reshape", "transpose", "copy", "pad", "gather"}


def _unmask_census(form, rows, vocab, one_chip):
    from benchmarks.unmask_iteration import (
        FORMS,
        pass_shapes,
        plane_census,
    )
    text = jax.jit(FORMS[form]).lower(
        *pass_shapes(rows, 4, vocab, one_chip)).compile().as_text()
    return [found for name, found in plane_census(text, rows,
                                                  vocab).items()
            if "sorts" not in name]


def test_the_block_samplers_plain_draw_reads_a_plane_twice(one_chip):
    """``unmask_block`` at the SDAR cell's 256 rows, four places and
    vocabulary of 151936, compiled for the described chip: outside the
    branches that sort the vocabulary each place's branch READS its
    plane in two reductions (the argmax, the blocks' sums), gathers
    from it twice (the maximum a row, a block a row) and WRITES none:
    no copy of a plane in another layout for the blocks' sake, no slice
    of ``[T, B, V]`` written out for the gather's, no reduction over
    the plane for the whole sum's (PERF.md section 6, PR 57). The
    control: PR 56's form (kept in ``benchmarks/unmask_iteration.py``)
    writes a plane (the scaled logits) and reads it three times more,
    which this census sees."""
    rows, vocab = 256, 151936
    branches = _unmask_census("two_pass", rows, vocab, one_chip)
    assert len(branches) == 4, branches
    for found in branches:
        assert not found["writes"], found
        assert (len(found["reads"]), len(found["gathers"])) == (2, 2), found
    parent = _unmask_census("parent", rows, 2048, one_chip)
    assert len(parent) == 4, parent
    assert all(found["writes"] and len(found["reads"]) >= 3
               for found in parent), parent
