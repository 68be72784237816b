"""Attention over the paged KV cache — XLA reference implementation.

One unified primitive serves prefill chunks and decode steps: queries at
absolute positions attend to everything already written to their
sequence's pages, with causal masking. Decode is the T=1 special case, so
there is exactly one numerics path to test. A Pallas kernel
(ops/paged_attention_pallas.py) implements the same contract for the
decode hot loop; this module is the ground truth it is tested against.

Replaces: vLLM's PagedAttention CUDA kernels (external to the reference
repo; provisioned via helm/templates/deployment-vllm-multi.yaml engine
image) — re-designed for TPU: gather whole pages (contiguous HBM reads),
mask in-register, let XLA tile the batched matmuls onto the MXU.

What is gathered: the first pages of every row's table, a block of
BLOCK_TOKENS at a time, as many blocks as the call's longest row
holds. The program counts them from ``kv_lens`` on the device (one
loop with that trip count per call, an online softmax across the
blocks), so a batch of short rows under a large ``--max-model-len``
does not read, write and contract max_pages pages a row only to mask
them; the table itself stays [B, max_pages] and the host never
chooses a shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from production_stack_tpu.ops.quant_kv import QuantKV, quantize_kv

NEG_INF = -1e30

# Pages are gathered this many tokens at a time (8 pages of 128). On
# a v5e a block of 64 rows is 33 MB a KV plane, which the re-layout
# for the contraction keeps in the fast memory; a finer block would
# follow the lengths closer at more loop turns a layer.
BLOCK_TOKENS = 1024

# Every attention implementation with a paged-KV read path. The
# quantized-coverage lint (tests/test_kv_parity_coverage_lint.py)
# requires a bf16-vs-int8 parity test naming each function here, so a
# new kernel cannot silently skip int8 coverage.
ATTENTION_IMPLS = {
    "xla": ("production_stack_tpu.ops.attention", "paged_attention"),
    "pallas_decode": ("production_stack_tpu.ops.paged_attention_pallas",
                      "paged_decode_attention"),
    "pallas_prefill": ("production_stack_tpu.ops.prefill_attention_pallas",
                       "paged_prefill_attention"),
    "pallas_ragged": ("production_stack_tpu.ops.ragged_attention_pallas",
                      "paged_ragged_attention"),
}


def block_pages(max_pages: int, page_size: int) -> int:
    """Pages ``paged_attention`` gathers at a time: BLOCK_TOKENS'
    worth, or the whole table where it is narrower."""
    return min(max_pages, -(-BLOCK_TOKENS // page_size))


def gathered_blocks(max_len, max_pages: int, page_size: int):
    """How many blocks a call whose longest row holds ``max_len``
    tokens gathers: those that hold it, at least one, at most the
    table's. One rule for the program, where ``max_len`` is traced,
    and for the host's step record (``attn_pages``), where it is an
    int."""
    block = block_pages(max_pages, page_size)
    need = (max_len + block * page_size - 1) // (block * page_size)
    most = -(-max_pages // block)
    if isinstance(need, int):
        return min(max(need, 1), most)
    return jnp.clip(need, 1, most)


def gather_pages(cache_layer: jnp.ndarray,
                 page_table: jnp.ndarray) -> jnp.ndarray:
    """[kv, num_pages, d, page] gathered to [kv, B, P, d, page], P the
    table's width as passed: ``paged_attention`` hands over one block
    of the [B, max_pages] table at a time.

    Cache layout (shared with the Pallas kernels): kv-head axis major
    so TP shards a leading axis, and each page stored *token-minor*
    ([head_dim, page_size]) so a page slice's last two dims are
    (d, 128)-tile-aligned for direct HBM->VMEM DMA and arrive
    pre-transposed for the MXU's ``q @ k^T`` contraction.

    The gather output keeps the cache's native axis order: an explicit
    transpose here gets hoisted by XLA's algebraic simplifier onto the
    gather *operand* — materializing a transposed copy of the ENTIRE
    cache per layer (seen in compiled HLO as [L,kv,pages,d,p]
    transposes). Consumers contract it via einsum in native order
    instead.
    """
    return cache_layer[:, page_table]  # [kv, B, P, d, page]


def write_to_pages(cache: jnp.ndarray, new_kv: jnp.ndarray,
                   page_table: jnp.ndarray, positions: jnp.ndarray,
                   valid: jnp.ndarray,
                   layer: "int | None" = None) -> jnp.ndarray:
    """Scatter new KV entries into their pages, a token at a time.

    Page 0 is the engine's trash page (the allocator never hands it out),
    so padded slots write there harmlessly instead of needing predication.

    The scatter's index lies in the plane's minor dimension, so a TPU
    compiles it on ``[pages x page_size, kv, d]`` and copies the whole
    plane to that layout and back at every call. It serves what is no
    run of tokens into a plain plane: one eager decode token a row, int8
    pages, the stacked cache, a ring (ops/window_attention.py). A run
    (a prefill chunk, a deferred burst's tail at its flush) into a
    layer's plain plane goes through ``write_run_to_pages``.

    With ``layer`` (a static int), ``cache`` is the full stacked
    [L, kv_heads, num_pages, head_dim, page_size] cache and the scatter
    lands at that layer IN PLACE. Model forwards must use this form
    inside their (statically unrolled) layer loop: threading per-layer
    cache slices through ``lax.scan`` xs/ys makes XLA copy the whole
    layer cache in and out every step (~20 ms/step measured on v5e for
    a 1B config vs ~1.3 ms for the chained in-place form).

    Args:
      cache:       [kv_heads, num_pages, head_dim, page_size], or the
                   stacked [L, ...] form when ``layer`` is given
      new_kv:      [B, T, kv_heads, head_dim]
      page_table:  [B, max_pages] int32 physical page ids
      positions:   [B, T] absolute token positions
      valid:       [B, T] bool; False entries are redirected to page 0
    """
    if (cache.ndim == 5) != (layer is not None):
        raise ValueError(
            "layer index and cache rank must agree: pass a stacked "
            "[L, ...] cache WITH layer, or a per-layer [kv, ...] "
            f"cache WITHOUT (got ndim={cache.ndim}, layer={layer!r})")
    page_size = cache.shape[-1]
    b, t = positions.shape
    logical_page = positions // page_size  # [B, T]
    offset = positions % page_size  # [B, T]
    physical_page = jnp.take_along_axis(
        page_table, logical_page, axis=1
    )  # [B, T]
    physical_page = jnp.where(valid, physical_page, 0)
    flat_pages = physical_page.reshape(-1)
    flat_offsets = offset.reshape(-1)
    if isinstance(cache, QuantKV):
        # Quantize-on-write: one symmetric int8 scale per (token,
        # kv_head) row lands in the scale tensor's matching page slot,
        # so incremental writes never rescale a neighbour.
        q8, kv_scale = quantize_kv(new_kv)  # [B,T,kv,d] i8 / [B,T,kv]
        flat_q8 = q8.reshape(b * t, *q8.shape[2:])
        flat_scale = kv_scale.reshape(b * t, kv_scale.shape[2])
        if layer is None:
            data = cache.data.at[:, flat_pages, :, flat_offsets].set(
                flat_q8)
            # Adjacent advanced indices (page, slot) keep the result
            # in place — updates are [kv, B*T], hence the transpose.
            scale = cache.scale.at[:, flat_pages, flat_offsets].set(
                flat_scale.T)
        else:
            data = cache.data.at[
                layer, :, flat_pages, :, flat_offsets].set(flat_q8)
            # The static layer index makes the advanced indices
            # non-adjacent again: updates broadcast to the front as
            # [B*T, kv].
            scale = cache.scale.at[
                layer, :, flat_pages, flat_offsets].set(flat_scale)
        return QuantKV(data, scale)
    # Advanced indices on the page and token-slot dims broadcast to
    # the front: the updates shape is [B*T, kv, d].
    flat_kv = new_kv.reshape(b * t, *new_kv.shape[2:])
    if layer is None:
        return cache.at[:, flat_pages, :, flat_offsets].set(flat_kv)
    return cache.at[layer, :, flat_pages, :, flat_offsets].set(flat_kv)


def write_run_to_pages(cache, new_kv, page_table: jnp.ndarray,
                       start: jnp.ndarray, count: jnp.ndarray):
    """A run of tokens a row into its pages, a page at a time, in the
    plane's own layout.

    Row ``r``'s real tokens are the first ``count[r]`` of ``new_kv[r]``
    and sit at the contiguous positions ``start[r] + s``: a prefill
    chunk (``start = positions[:, 0]``, ``count = sum(valid)``) or a
    deferred burst's tail at its flush (``start`` the row's length
    before the burst, ``count`` the tokens it emitted). Each of the
    pages a row's run can touch wherever it starts (``ceil(T /
    page_size) + 1``; one fewer where T is one over a whole number of
    pages) is read, takes the run's tokens on their lanes under a
    select and goes back whole, by its page index alone: all rows'
    pages in one gather and one scatter whose index is the plane's
    second-major dimension, which the compiler does on ``[kv, pages,
    d, page_size]`` as it lies and, the plane donated, in place. The
    program's text does not grow with the rows. A page that gets none
    of the row's tokens (every page of a pad row) is the trash page 0,
    written back as it was read; no two rows own a page, so only page
    0 is written twice. The result is the scatter's of
    ``write_to_pages``, bit for bit, outside page 0.

    ``write_to_pages``' scatter has its index in the plane's minor
    dimension; a TPU compiles it on ``[pages x page_size, kv, d]`` and
    copies the whole plane to that layout and back at every call
    (PERF.md section 6, PR 52). Who calls which: the run writer serves
    ``models/llama.py`` ``cached_attention``'s per-layer branch at
    more than a token a row (every prefill step of llama, lfm2_moe,
    jamba, granitemoehybrid, qwen3_next and exaone_moe's full layers),
    the latent plane's chunk in ``models/longcat_flash.py`` ``mla``
    (LongCat, GLM) and the flush of both deferred bursts
    (``engine/model_runner.py`` ``_burst_tails``); the scatter keeps
    what is not a run into a plain plane: the stacked ``[L, ...]``
    cache (``layer=``: pipeline and context serving), int8 pages
    (``QuantKV`` writes a scale beside the data), one eager token a
    row, and the ring's ``positions % window``
    (ops/window_attention.py ``write_to_ring``).

    Why no loop over the rows with a ``dynamic_update_slice`` a page,
    which alone on the chip took half this form's time (PERF.md
    section 6, PR 52): the compiler is free to give the planes such a
    loop carries the layout of its small update, pages before heads.
    At two KV heads it did, and copied all 72 planes of that cell's
    burst; held to the row-major layout by a layout constraint it
    copied none in one spelling of the body and twenty-two planes of
    another cell's burst INSIDE the loop in the next.

    cache [kv, pages, d, page_size] (one layer's plain plane, or the
    latent's [1, pages, 576, page_size]) and new_kv [B, T, kv, d], or
    a tuple of planes and a tuple of runs under the one table (a
    layer's K and V, a flush's every plane), whose page indices and
    lane masks are then worked out once; page_table [B, max_pages];
    start, count [B].
    """
    planes, runs = ((cache, new_kv) if isinstance(cache, tuple)
                    else ((cache,), (new_kv,)))
    if any(isinstance(c, QuantKV) or c.ndim != 4 for c in planes):
        raise ValueError("write_run_to_pages writes plain [kv, pages, d, "
                         "page_size] planes, a layer's each: int8 pages "
                         "and the stacked [L, ...] cache keep "
                         "write_to_pages")
    page_size = planes[0].shape[-1]
    b, t = runs[0].shape[:2]
    p = (t + page_size - 2) // page_size + 1
    # Lane l of a row's j-th page holds the run's token
    # j * page_size + l - start % page_size, where that is one.
    token = (jnp.arange(p * page_size, dtype=start.dtype)[None]
             - (start % page_size)[:, None])  # [B, P * page_size]
    take = (token >= 0) & (token < count[:, None])
    logical = start[:, None] // page_size + jnp.arange(p, dtype=start.dtype)
    pages = jnp.where(
        jnp.any(take.reshape(b, p, page_size), axis=-1),
        jnp.take_along_axis(
            page_table, jnp.minimum(logical, page_table.shape[1] - 1),
            axis=1), 0).reshape(b * p)
    source = jnp.clip(token, 0, t - 1)[:, :, None, None]
    take = take.reshape(1, b * p, 1, page_size)
    written = []
    for plane, run in zip(planes, runs):
        heads, _, width, _ = plane.shape
        # The shift to the pages' lanes is a gather of whole tokens
        # (rows of heads x width), then the pages' token-minor order.
        new = jnp.take_along_axis(run, source, axis=1).reshape(
            b, p, page_size, heads, width).transpose(
            3, 0, 1, 4, 2).reshape(heads, b * p, width, page_size)
        written.append(plane.at[:, pages].set(
            jnp.where(take, new, plane[:, pages])))
    return tuple(written) if isinstance(cache, tuple) else written[0]


def write_to_tail(tail: jnp.ndarray, new_kv: jnp.ndarray,
                  slot: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """One decode token into its burst-tail slot (deferred KV write).

    A decode ablation (builder-captured 2026-07-31, not measured by
    the driver) put the per-step paged scatters at ~5.1 of 11.1 ms —
    for ~1 MB of writes. Deferred mode appends each step's K/V to a small
    dense [B, S, kv, d] tail instead (a one-hot select over S<=32
    slots — no scatter), and the runner flushes the tails to the pages
    once at burst end: every plane's tail page-wise and in place in one
    pass (``write_run_to_pages``; int8 pages and the stacked cache by
    one ``write_to_pages`` a layer).

    Args:
      tail:   [B, S, kv_heads, head_dim]
      new_kv: [B, 1, kv_heads, head_dim] — this step's K or V
      slot:   [B] int32 — tail slot per row (q_pos - frozen kv_len)
      active: [B] bool — rows decoding this step; a frozen row's hit
              mask is all-False, so its tail is untouched (its stale
              slots stay masked out of attention positionally and out
              of the flush by the emitted count)
    """
    s = tail.shape[1]
    hit = (jnp.arange(s)[None, :] == slot[:, None]) & active[:, None]
    return jnp.where(hit[..., None, None], new_kv, tail)


def write_block_to_tail(tail: jnp.ndarray, new_kv: jnp.ndarray,
                        slot: jnp.ndarray, active: jnp.ndarray
                        ) -> jnp.ndarray:
    """A block's K or V into its tail slots (block-diffusion burst).

    The rows of such a burst go block by block in lockstep, so every
    row's block lies at the same slots ``slot .. slot + T - 1``
    (``slot`` a scalar): one dynamic slice of the tail is replaced,
    for the active rows. A denoising pass writes a block provisionally
    and a later pass, at last the store pass, overwrites it in place.

    Args:
      tail:   [B, S, kv_heads, head_dim]
      new_kv: [B, T, kv_heads, head_dim] this pass's K or V
      slot:   scalar int32, the block's first tail slot
      active: [B] bool
    """
    t = new_kv.shape[1]
    old = jax.lax.dynamic_slice_in_dim(tail, slot, t, axis=1)
    new = jnp.where(active[:, None, None, None], new_kv, old)
    return jax.lax.dynamic_update_slice_in_dim(tail, new, slot, axis=1)


def fold_block_queries(q: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """``[B, T, q_heads, d]`` to ``[B, kv * T * group, d]``: the T
    queries of a block that all see the same keys, as ``T * group``
    query heads of each KV head (kv-major, so that either decode form's
    ``reshape(kv, group', d)`` finds them)."""
    b, t, num_q_heads, d = q.shape
    group = num_q_heads // num_kv_heads
    return (q.reshape(b, t, num_kv_heads, group, d)
            .transpose(0, 2, 1, 3, 4)
            .reshape(b, num_kv_heads * t * group, d))


def unfold_block_queries(out: jnp.ndarray, t: int,
                         num_kv_heads: int) -> jnp.ndarray:
    """``fold_block_queries``' inverse on the attention's output:
    ``[B, kv * T * group, d]`` to ``[B, T, q_heads, d]``."""
    b, folded, d = out.shape
    group = folded // (num_kv_heads * t)
    return (out.reshape(b, num_kv_heads, t, group, d)
            .transpose(0, 2, 1, 3, 4)
            .reshape(b, t, num_kv_heads * group, d))


def tail_softmax_state(qg: jnp.ndarray, k_tail: jnp.ndarray,
                       v_tail: jnp.ndarray, q_positions: jnp.ndarray,
                       kv_lens: jnp.ndarray):
    """The softmax's running state over a burst tail alone.

    ``qg`` is the grouped query [B, T, kv, group, d]; the tail's S
    un-flushed tokens sit at positions ``kv_lens + s`` and stay full
    precision. Returns the running maximum and sum [B, kv, group, T]
    and the weighted values [B, kv, group, T, d], float32: what
    ``paged_attention`` starts its blocks from, and what the Pallas
    decode form (ops/paged_attention_pallas.py) merges its pages'
    state with."""
    head_dim = qg.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, dtype=jnp.float32))
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
    m = t_scores.max(axis=-1)
    t_probs = jnp.exp(t_scores - m[..., None])
    return (m, t_probs.sum(axis=-1), jnp.einsum(
        "bkgts,bskd->bkgtd", t_probs.astype(v_tail.dtype), v_tail,
        preferred_element_type=jnp.float32))


def paged_attention(q: jnp.ndarray, k_cache_layer: jnp.ndarray,
                    v_cache_layer: jnp.ndarray, page_table: jnp.ndarray,
                    q_positions: jnp.ndarray,
                    kv_lens: jnp.ndarray,
                    layer: "int | None" = None,
                    k_tail: "jnp.ndarray | None" = None,
                    v_tail: "jnp.ndarray | None" = None) -> jnp.ndarray:
    """Causal attention of q against a sequence's cached pages.

    Gathers and contracts the table a block of ``block_pages`` at a
    time, ``gathered_blocks(max(kv_lens))`` of them: a loop whose trip
    count the device reads from ``kv_lens``, with the softmax carried
    across blocks as a running maximum, sum and weighted values (the
    tail, where given, starts it). A block past every row's length
    changes nothing (its positions are masked to NEG_INF and weigh
    exactly 0), so the result does not depend on how many blocks the
    rest of the batch asks for. Pad rows (``kv_lens`` 0) ask for
    nothing. ``kv_lens`` may be traced (a burst's carry): the count
    follows it step by step.

    Args:
      q:           [B, T, num_q_heads, head_dim]
      k/v_cache_layer: [num_kv_heads, num_pages, head_dim, page_size],
                   or the stacked [L, ...] cache when ``layer`` (a
                   static int) is given — the static slice fuses into
                   the page gather instead of materializing
      page_table:  [B, max_pages]
      q_positions: [B, T] absolute positions of the queries
      kv_lens:     [B] number of valid cached tokens (>= max position + 1)
      k_tail/v_tail: optional [B, S, kv_heads, head_dim] deferred-write
                   burst tails holding tokens NOT yet flushed to the
                   pages: tail slot s is absolute position
                   ``kv_lens + s`` (kv_lens frozen for the burst), and
                   masking is purely positional — unwritten slots sit
                   at positions > every query and never attend.

    Returns [B, T, num_q_heads, head_dim].
    """
    if (k_cache_layer.ndim == 5) != (layer is not None):
        raise ValueError(
            "layer index and cache rank must agree: pass a stacked "
            "[L, ...] cache WITH layer, or a per-layer [kv, ...] "
            f"cache WITHOUT (got ndim={k_cache_layer.ndim}, "
            f"layer={layer!r})")
    if layer is not None:
        k_cache_layer = k_cache_layer[layer]
        v_cache_layer = v_cache_layer[layer]
    b, t, num_q_heads, head_dim = q.shape
    num_kv_heads = k_cache_layer.shape[0]
    group = num_q_heads // num_kv_heads
    page = k_cache_layer.shape[-1]
    max_pages = page_table.shape[1]
    block = block_pages(max_pages, page)
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, dtype=jnp.float32))
    qg = q.reshape(b, t, num_kv_heads, group, head_dim)
    quantized = isinstance(k_cache_layer, QuantKV)
    # A table that is no whole number of blocks ends in the trash
    # page, at positions past every length.
    page_table = jnp.pad(page_table, ((0, 0), (0, -max_pages % block)))

    def add_block(i, carry):
        """Fold block ``i`` of the table into the running softmax."""
        m, denom, acc = carry
        table = jax.lax.dynamic_slice_in_dim(page_table, i * block,
                                             block, axis=1)
        k = gather_pages(k_cache_layer, table)  # [kv, B, P, d, page]
        v = gather_pages(v_cache_layer, table)
        if quantized:
            # int8 pages: keep the matmul operands int8 (dequant
            # BEFORE the gather would materialize the whole cache in
            # f32, the same hazard as the convert-hoist note below)
            # and fold the per-slot scales in afterwards — exact,
            # because each scale varies only over non-contracted
            # score axes. Broadcast shape [B, kv, 1(group), 1(T), P,
            # page].
            k_scale_b = k.scale.transpose(1, 0, 2, 3)[:, :, None, None]
            v_scale_b = v.scale.transpose(1, 0, 2, 3)[:, :, None, None]
            k, v = k.data, v.data
        # scores: [B, kv, group, T, P, page], contracted in the
        # cache's NATIVE axis order. Two deliberate choices, both
        # HBM-traffic driven (this runs once per layer per step):
        # - operands stay in the cache dtype with an f32 accumulator
        #   (the MXU's native bf16xbf16->f32 form): upcasting k/v
        #   first makes XLA hoist the convert above the page gather
        #   and materialize the ENTIRE cache in f32,
        # - no reshape/transpose of the gathered pages: an explicit
        #   transpose gets hoisted onto the gather operand as a
        #   whole-cache transposed copy (see gather_pages).
        scores = jnp.einsum(
            "btkgd,kbpdc->bkgtpc", qg, k,
            preferred_element_type=jnp.float32,
        ) * scale
        if quantized:
            scores = scores * k_scale_b  # fold k dequant into the logits
        token_pos = ((i * block + jnp.arange(block))[:, None] * page
                     + jnp.arange(page)[None, :])  # [P, page]
        causal = (token_pos[None, None]
                  <= q_positions[:, :, None, None])  # [B, T, P, page]
        in_len = token_pos[None] < kv_lens[:, None, None]  # [B, P, page]
        mask = causal & in_len[:, None]  # [B, T, P, page]
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=(-2, -1)))
        keep = jnp.exp(m - m_new)
        probs = jnp.exp(scores - m_new[..., None, None])  # f32
        denom = denom * keep + probs.sum(axis=(-2, -1))
        if quantized:
            # v dequant folds into the weights (f32 — casting to the
            # cache dtype would truncate to int8).
            probs = probs * v_scale_b
        else:
            probs = probs.astype(v.dtype)
        acc = acc * keep[..., None] + jnp.einsum(
            "bkgtpc,kbpdc->bkgtd", probs, v,
            preferred_element_type=jnp.float32)
        return m_new, denom, acc

    stat = (b, num_kv_heads, group, t)
    if k_tail is not None:
        carry = tail_softmax_state(qg, k_tail, v_tail, q_positions,
                                   kv_lens)
    else:
        carry = (jnp.full(stat, NEG_INF, jnp.float32),
                 jnp.zeros(stat, jnp.float32),
                 jnp.zeros((*stat, head_dim), jnp.float32))

    if max_pages <= block:
        carry = add_block(0, carry)
    else:
        carry = jax.lax.fori_loop(
            0, gathered_blocks(jnp.max(kv_lens), max_pages, page),
            add_block, carry)
    _, denom, acc = carry
    out = (acc / denom[..., None]).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, t, num_q_heads, head_dim).astype(q.dtype)
