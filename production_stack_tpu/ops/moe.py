"""A routed expert layer that is told which experts it holds.

The router scores every expert of the model (``router_w`` is as wide
as the published count) and a token keeps its ``top_k`` choices
(``route``: softmax over all experts; ``route_sigmoid``: a sigmoid an
expert, chosen with a learned bias and weighted without it, in the two
published forms of its sum's epsilon and its scale;
``route_softmax_bias``: softmax scores chosen with a learned bias,
scaled and not renormalised, over routed and zero-compute experts,
whose part of the sum is ``identity_weight``). This
engine holds one contiguous block of the experts,
``[first_expert, first_expert + E)``, and computes for each token the
part of the weighted sum that its held choices give; what experts held
elsewhere would add is left out, and the partial sum is what goes on
(on one chip of an expert-parallel group the exchange that would
complete it is simply absent). With every expert held the sum is
whole.

Work follows the HELD choices, inside the kernel and around it: the
(token, choice) pairs are sorted by held expert (the sort and the count
an expert are over all N x k keys: they are what finds the held pairs),
each expert's rows go through its three matrices as one group of a
grouped matrix product, so FLOPs are tokens x held choices x one
expert, and an expert nobody chose is never read. Where only part of
the router's experts is held, every pass around the two products (the
gather of ``x``, both float32 outputs and their masks, the activation,
the weighting, the sum back to tokens) is over ``room`` rows and not
N x k: ``expert_room`` finds that many whole tiles from the call's
shapes (the held pairs to expect, times ``_ROOM_MARGIN``), the sorted
order's held rows go through in chunks of ``room`` (one chunk unless a
step's routing leans on the held block: ``count_step`` counts those
steps), and with every expert held all rows go through as one. On a TPU
the grouped product is the Pallas ``megablox`` kernel that ships with
JAX (group sizes reach it through scalar prefetch; it visits only tiles
that hold rows) under tiles that follow each product's shape
(``expert_tiles``: a k tile that divides k, a few grid steps a visit);
elsewhere it is ``jax.lax.ragged_dot``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# The grouped product's tiles follow the product's shape
# (``expert_tiles``). 128 rows a tile: a decode step of 128 rows x 10
# choices leaves ~2.5 rows an expert, so a tile is mostly one expert's
# few rows and the product is bound by reading the expert (6.3 MB at
# the published widths), not by the matrix unit.
_TILE_ROWS = 128
# The most one right-hand tile [tk, tn] may hold. For every (m tile,
# expert) pair that holds rows the kernel walks tiles_n x tiles_k grid
# steps, each fetching one such tile, and a step costs 0.11-0.3 us
# beside its bytes (more where it splits k: the partial products are
# summed over steps). Measured on a v5e at the five expert cells'
# shapes (benchmarks/grouped_product_tiles.py, PERF.md section 6,
# PR 49): 4 MiB is the fastest of 2, 3 and 4 on four shapes and 0.3%
# behind 2 on the fifth; 6 MiB gains 2-3% more on two and leaves no
# room in the scoped VMEM; 8 is refused.
_RHS_TILE_BYTES = 4 << 20
# A v5e's default scoped VMEM is 16 MiB and the kernel asks for no
# more: two right-hand tiles, two left-hand tiles [128, tk], two
# output tiles and the accumulator [128, tn] (float32) stay under
# three quarters of it.
_TILE_BUFFER_BYTES = 12 << 20
# The constant tile every product had before the tiles followed its
# shape, kept where no tile that divides k fits: the kernel then masks
# the last k tile of both operands (in float32, on every such step:
# 0.6 us a step, a quarter of the down product at k 1536).
_CONSTANT_TK, _CONSTANT_TN = 1024, 512


def grid_steps(tiles, k: int, n: int) -> int:
    """Grid steps the kernel walks under ``tiles`` for one (m tile,
    expert) pair that holds rows: one a (k tile, n tile)."""
    _, tk, tn = tiles
    return -(-k // tk) * -(-n // tn)


def expert_tiles(k: int, n: int, itemsize: int):
    """(tm, tk, tn) of the grouped product ``[m, k] x [E, k, n]`` whose
    operands hold ``itemsize`` bytes an element.

    ``tk`` is ``k`` or a divisor of it that is a multiple of 128, so
    the kernel never builds its masked branch; ``tn`` is a divisor of
    ``n`` that is a multiple of 128 (where ``n`` has none, the constant
    tile's). Of the pairs that fit (``_RHS_TILE_BYTES``,
    ``_TILE_BUFFER_BYTES``) the one of the fewest grid steps a visit,
    and of those the one with the largest ``tk``: with ``k`` whole
    nothing is summed over grid steps. Where no pair fits (a ``k`` over
    what one tile holds that no multiple of 128 divides) the constant
    tile stands."""
    tm = _TILE_ROWS
    tks = [d for d in range(k, 0, -1)
           if k % d == 0 and (d == k or d % 128 == 0)]
    tns = ([d for d in range(n, 0, -1) if n % d == 0 and d % 128 == 0]
           or [min(_CONSTANT_TN, n)])
    fitting = [
        (tk, tn) for tk in tks for tn in tns
        if tk * tn * itemsize <= _RHS_TILE_BYTES
        and (2 * (tk * tn + tm * tk) * itemsize + 3 * tm * tn * 4
             <= _TILE_BUFFER_BYTES)]
    if not fitting:
        return tm, min(_CONSTANT_TK, k), min(_CONSTANT_TN, n)
    return min(((tm, tk, tn) for tk, tn in fitting),
               key=lambda tiles: (grid_steps(tiles, k, n), -tiles[1]))


def expert_layer_tiles(hidden: int, width: int, itemsize: int) -> dict:
    """What one routed expert layer's two products run under, as
    ``/version`` states it: the tiles of gate|up ``[H, 2F]`` and of
    down ``[F, H]``, and the grid steps the kernel walks for an
    (m tile, expert) pair that holds rows, over both."""
    gate_up = expert_tiles(hidden, 2 * width, itemsize)
    down = expert_tiles(width, hidden, itemsize)
    return {"gate_up": list(gate_up), "down": list(down),
            "steps_per_visit": grid_steps(gate_up, hidden, 2 * width)
            + grid_steps(down, width, hidden)}


def route(x: jnp.ndarray, router_w: jnp.ndarray, top_k: int,
          norm_topk: bool):
    """Softmax over ALL experts in float32, the ``top_k`` largest, and
    (``norm_topk``) their weights divided by their sum.

    x [N, H], router_w [H, E_all] -> (weights [N, k] f32, ids [N, k]).
    """
    probs = jax.nn.softmax(
        jnp.dot(x, router_w, preferred_element_type=jnp.float32),
        axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids


def route_sigmoid(x: jnp.ndarray, router_w: jnp.ndarray,
                  expert_bias: jnp.ndarray, top_k: int,
                  scale: float = 1.0, eps: float = 1e-6):
    """``route`` for a router that scores each expert alone: a sigmoid
    over ALL experts in float32; the ``top_k`` largest of score +
    ``expert_bias`` (learned, [E_all] float32) are chosen, and their
    weights are the scores WITHOUT the bias, divided by their sum +
    ``eps``. Two published forms: ``lfm2_moe`` (eps 1e-6, no scale)
    and the DeepSeek-V3 kind that ``glm4_moe_lite`` follows (eps
    1e-20, and the normalised weights times ``scale``, its
    ``routed_scaling_factor``).

    x [N, H], router_w [H, E_all] -> (weights [N, k] f32, ids [N, k]).
    """
    scores = jax.nn.sigmoid(
        jnp.dot(x, router_w, preferred_element_type=jnp.float32))
    _, ids = jax.lax.top_k(scores + expert_bias, top_k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return (weights if scale == 1.0 else weights * scale), ids


def route_softmax_bias(x: jnp.ndarray, router_w: jnp.ndarray,
                       expert_bias: jnp.ndarray, top_k: int,
                       scale: float):
    """``route`` for a router whose choice is biased and whose weights
    are not renormalised: softmax over ALL the router's outputs in
    float32 (zero-compute experts among them); the ``top_k`` largest
    of score + ``expert_bias`` (learned, [E_all] float32) are chosen,
    and their weights are ``scale`` times the scores WITHOUT the bias.

    x [N, H], router_w [H, E_all] -> (weights [N, k] f32, ids [N, k]).
    """
    scores = jax.nn.softmax(
        jnp.dot(x, router_w, preferred_element_type=jnp.float32),
        axis=-1)
    _, ids = jax.lax.top_k(scores + expert_bias, top_k)
    return scale * jnp.take_along_axis(scores, ids, axis=-1), ids


def identity_weight(weights: jnp.ndarray, ids: jnp.ndarray,
                    first_zero_expert: int):
    """What a token's choices of zero-compute (identity) experts, the
    ids from ``first_zero_expert`` on, weigh together: ``[N]`` float32,
    the factor of the token's own input in the routed sum; and how
    many such choices each token made, ``[N]`` int32."""
    zero = ids >= first_zero_expert
    return (jnp.sum(jnp.where(zero, weights, 0.0), axis=-1),
            jnp.sum(zero, axis=-1, dtype=jnp.int32))


def _grouped_dot(lhs, rhs, group_sizes, impl: str):
    """lhs [M, K] rows sorted by group, rhs [E, K, N] -> [M, N] f32;
    rows past the groups' total come back zero."""
    m, k = lhs.shape
    # Neither product defines the rows that belong to no group.
    grouped = (jnp.arange(m) < jnp.sum(group_sizes))[:, None]
    if impl == "xla":
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                 preferred_element_type=jnp.float32)
        return jnp.where(grouped, out, 0.0)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    tiling = expert_tiles(k, rhs.shape[-1], rhs.dtype.itemsize)
    pad = (-m) % tiling[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, group_sizes,
              preferred_element_type=jnp.float32, tiling=tiling,
              interpret=impl == "pallas-interpret")
    return jnp.where(grouped, out[:m], 0.0)


# Rows a call makes room for, over the held pairs it expects
# (``expert_room``). A step whose held choices pass its room is exact
# and pays one more chunk, so the constant buys speed against the share
# of such steps, which ``count_step`` counts. Measured on a v5e (PERF.md
# section 6, PR 54): at 1.5 the bursts' rooms are 128 rows (LongCat,
# K-EXAONE), 384 (LFM2) and 512 (Granite, Qwen3-Next), and the cell
# nearest its room, LFM2 (280 held pairs a layer-step of 384), passed
# it in 0.64% of a window's 94 336 layer-steps, LongCat in none of
# 13 696; a second chunk costs a call a quarter to a half more
# (0.34 -> 0.50 ms at three chunks), so 0.2% of the calls' time, where
# room for twice the pairs costs every call 0.3 to 1.5% in a burst and
# 4 to 9% in a prefill step (0.996 -> 1.083 ms on LFM2's).
_ROOM_MARGIN = 1.5


def expert_room(n: int, top_k: int, held: int, router_width: int):
    """Rows of the sorted (token, choice) order that ``held_experts``
    sends through the held experts at a time, from the call's shapes
    alone: the held pairs to expect of ``n`` tokens x ``top_k``
    choices over a router ``router_width`` wide of which ``held``
    experts are here, times ``_ROOM_MARGIN``, in whole tiles of
    ``_TILE_ROWS``. None where that reaches every pair (all experts
    held, or too few rows for a tile to be less): every row then goes
    through as one."""
    pairs = n * top_k
    expected = pairs * held / router_width
    room = -(-math.ceil(_ROOM_MARGIN * expected) // _TILE_ROWS) * _TILE_ROWS
    return None if room >= pairs else room


def held_experts(x: jnp.ndarray, weights: jnp.ndarray, ids: jnp.ndarray,
                 w_gate_up: jnp.ndarray, w_down: jnp.ndarray,
                 first_expert: int, valid: jnp.ndarray = None,
                 impl: str = "xla", router_width: "int | None" = None):
    """The held experts' part of the routed sum.

    Args:
      x:         [N, H] normalised hidden states
      weights:   [N, k] routing weights (float32), ids [N, k] expert ids
      w_gate_up: [E, H, 2F] held experts' gate and up matrices, side by
                 side; w_down [E, F, H]
      first_expert: id of the first held expert
      valid:     [N] bool; a token that is not real chooses nothing
      impl:      "xla" (``ragged_dot``), "pallas" (the ``megablox``
                 kernel, on a TPU, under ``expert_tiles(H, 2F)`` and
                 ``expert_tiles(F, H)``: both follow the operands'
                 shapes and dtype, nothing else) or "pallas-interpret"
      router_width: outputs the router chose among (static). With it
                 the rows that go around and through the products are
                 ``expert_room``'s, a chunk at a time; without it, or
                 where the room would be every row, all N * k go
                 through as one. Exact either way: only the order of a
                 float32 sum differs

    Returns (y [N, H] in x's dtype, load [E] int32: real tokens that
    chose each held expert).
    """
    with jax.named_scope("moe_experts"):
        n, top_k = ids.shape
        e, _, f2 = w_gate_up.shape
        f = f2 // 2
        local = ids - first_expert
        held = (local >= 0) & (local < e)
        if valid is not None:
            held = held & valid[:, None]
        # Choices that fall elsewhere sort behind every held expert
        # and belong to no group, so the product never touches them.
        key = jnp.where(held, local, e).reshape(-1)
        order = jnp.argsort(key, stable=True)
        load = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]

        def through(rows, sizes):
            """The sorted rows ``rows`` through their experts (``sizes``
            of them each), weighted: [len(rows), H] in x's dtype, zero
            past the groups' total."""
            hidden = _grouped_dot(x[rows // top_k], w_gate_up, sizes, impl)
            act = (jax.nn.silu(hidden[:, :f])
                   * hidden[:, f:]).astype(x.dtype)
            out = _grouped_dot(act, w_down, sizes, impl)   # f32
            return (out * weights.reshape(-1)[rows][:, None]).astype(
                x.dtype)

        room = (None if router_width is None
                else expert_room(n, top_k, e, router_width))
        if room is None:
            out = through(order, load)
            # Back to (token, choice) order, then the sum over choices.
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype))
            return jnp.sum(out[inverse].reshape(n, top_k, -1), axis=1,
                           dtype=jnp.float32).astype(x.dtype), load

        ends = jnp.cumsum(load)
        starts = ends - load
        # Whole chunks: the rows added lie past every held pair.
        chunked = jnp.pad(order, (0, -order.shape[0] % room))

        def chunk(i, total):
            """Adds to the tokens' float32 sums what rows [i * room,
            (i + 1) * room) of the order give: each expert's part of
            them is cut from ``load``'s running sum."""
            lo = i * room
            rows = jax.lax.dynamic_slice(chunked, (lo,), (room,))
            sizes = jnp.maximum(jnp.minimum(ends, lo + room)
                                - jnp.maximum(starts, lo), 0)
            out = through(rows, sizes)
            # Back to tokens by one product with the 0/1 matrix of
            # which row is whose: exact in float32, no scatter, and at
            # every expert cell's burst and prefill shape faster on a
            # v5e than gathering each (token, choice)'s row by its
            # place in the order (0.34 against 0.39 ms a call in
            # LFM2's burst, 1.00 against 1.09 and 2.6 against 6.0 in
            # LFM2's and LongCat's prefill steps; the gather is the
            # faster only past some 2048 rows of room a choice, four
            # times LFM2's step: PERF.md section 6, PR 54).
            whose = (rows // top_k)[None, :] == jnp.arange(n)[:, None]
            return total + jnp.dot(whose.astype(x.dtype), out,
                                   preferred_element_type=jnp.float32)

        y = jax.lax.fori_loop(0, -(-ends[-1] // room), chunk,
                              jnp.zeros(x.shape, jnp.float32))
        return y.astype(x.dtype), load


def count_step(stats: jnp.ndarray, top_k: int, load: jnp.ndarray,
               valid: jnp.ndarray,
               router_width: "int | None" = None) -> jnp.ndarray:
    """Add one decode step of one expert layer to the first six of a
    family's float32 counters (``models/registry.py``: layer_steps,
    choices, held_choices, max_load, experts_hit, room_overflows); what
    the family keeps after them stays. ``load [E]`` is ``held_experts``';
    ``valid`` marks the real rows; ``router_width`` is what that call
    was given: the step counts under ``room_overflows`` if its held
    choices took more than one chunk of that call's room."""
    rows = jnp.sum(valid).astype(jnp.float32)
    room = (None if router_width is None else expert_room(
        valid.size, top_k, load.shape[0], router_width))
    step = jnp.stack([
        jnp.float32(1.0),
        rows * top_k,
        jnp.sum(load).astype(jnp.float32),
        jnp.max(load).astype(jnp.float32),
        jnp.sum(load > 0).astype(jnp.float32),
        (jnp.float32(0.0) if room is None
         else (jnp.sum(load) > room).astype(jnp.float32)),
    ])
    return stats.at[:step.shape[0]].add(step)


def swiglu(x, w_gate_up, w_down):
    """A dense SwiGLU expert: gate and up side by side."""
    f = w_gate_up.shape[-1] // 2
    hidden = x @ w_gate_up
    return (jax.nn.silu(hidden[..., :f]) * hidden[..., f:]) @ w_down
