"""The rows that go around and through the routed experts' two grouped
products (``ops/moe.py`` ``expert_room``, ``held_experts`` given the
router's width, ``count_step``'s sixth counter).

The rule at the eight cells' burst and prefill shapes (the files of
chipbench/configs are read); then tiny float32 operands, seeded, on the
CPU: the call in chunks of its room against the call over every row,
for the three routers, where the held choices fit, where they take two
and three chunks, where none is held, under ``valid``; all experts held
against the call as it stood before there was a room, bit for bit; the
``megablox`` kernel in interpret mode against ``ragged_dot`` under a
room; and the counter.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.model_runner import prefill_shapes
from production_stack_tpu.models import registry
from production_stack_tpu.ops import moe

CONFIGS = pathlib.Path(__file__).resolve().parent.parent / "chipbench/configs"
# cell -> the rows a burst step and the widest prefill step go through
# under a room (None: every row x choice), as the rule gives them at
# the cell's published widths and served shapes.
CELLS = {
    "longcat-flash-omni-ep32": (128, 768),
    "k-exaone-236b-a23b-ep16": (128, 1536),
    "lfm2-8b-a1b-ep4": (384, 3072),
    "granite-4.0-h-small-ep4": (512, 3840),
    "qwen3-next-80b-a3b-ep4": (512, 7680),
    "glm-4.7-flash-pp8": (None, None),
    "qwen2.5-3b": (None, None),
    "jamba2-3b": (None, None),
}
FLOAT32 = 2e-5


def served(cell):
    """(model, burst tokens, every prefill shape's tokens) of a cell."""
    hf = json.loads((CONFIGS / f"{cell}.json").read_text())
    flags = hf["chipbench"]["server_flags"]
    model = ModelConfig.from_hf_config(hf)
    positions = 2 if model.has_draft_module else 1
    return (model, flags["max-num-seqs"] * positions,
            [rows * tokens for rows, tokens in prefill_shapes(
                flags["prefill-batch-size"], flags["prefill-chunk-size"])])


def room_of(model, n):
    return moe.expert_room(n, model.num_experts_per_tok, model.num_experts,
                           model.router_width)


@pytest.mark.parametrize("step", ["burst", "prefill"])
@pytest.mark.parametrize("cell", CELLS)
def test_the_room_at_the_cells_shapes(cell, step):
    """Whole tiles of 128 under N x k where part of the router's experts
    is held, and at least the pairs to expect; no room where all are
    (GLM) or the model routes nothing (the two dense cells)."""
    model, burst, prefills = served(cell)
    want = CELLS[cell][step == "prefill"]
    if not model.num_experts:
        assert want is None
        return
    for n in [burst] if step == "burst" else prefills:
        room = room_of(model, n)
        pairs = n * model.num_experts_per_tok
        if model.num_experts == model.router_width:
            assert room is None
            continue
        expected = pairs * model.num_experts / model.router_width
        if room is not None:
            assert room % 128 == 0 and expected <= room < pairs
            assert room - 128 < moe._ROOM_MARGIN * expected
        else:       # a low token bucket: a tile would be every pair
            assert moe._ROOM_MARGIN * expected > pairs - 128
    widest = burst if step == "burst" else max(prefills)
    assert room_of(model, widest) == want


HELD = (4, 8)       # the block held of a router 32 wide


def routed(router, key, n, width, top_k, leaning=0, lean=0.0):
    """(x, weights, ids) of ``router`` over ``width`` experts; the
    first ``leaning`` of the held block's get ``lean`` on their
    scores (all of a token's choices where it is large, none where it
    is as far below zero)."""
    k_x, k_w, k_b = jax.random.split(key, 3)
    h = 32
    x = jnp.abs(jax.random.normal(k_x, (n, h), jnp.float32))
    cols = slice(HELD[0], HELD[0] + leaning)
    router_w = 0.3 * jax.random.normal(k_w, (h, width), jnp.float32)
    bias = 0.1 * jax.random.normal(k_b, (width,), jnp.float32)
    if router == "route":       # no bias term: x is positive
        weights, ids = moe.route(x, router_w.at[:, cols].add(lean), top_k,
                                 norm_topk=True)
    elif router == "route_sigmoid":
        weights, ids = moe.route_sigmoid(
            x, router_w, bias.at[cols].add(lean), top_k)
    else:
        weights, ids = moe.route_softmax_bias(
            x, router_w, bias.at[cols].add(lean), top_k, 2.5)
    return x, weights, ids


def experts(key, e=4, h=32, f=16):
    k_up, k_down = jax.random.split(key)
    return (0.2 * jax.random.normal(k_up, (e, h, 2 * f), jnp.float32),
            0.2 * jax.random.normal(k_down, (e, f, h), jnp.float32))


# 256 tokens x 4 choices over 32 experts of which 4 are held: 128 held
# pairs to expect and a room of 256. name -> (held experts leant on,
# lean, chunks of the room the held choices of the 228 real tokens take).
N, WIDTH, TOP_K, ROOM = 256, 32, 4, 256
LEANS = {"fit": (0, 0.0, 1), "two": (2, 30.0, 2), "three": (3, 30.0, 3),
         "none": (4, -30.0, 0)}


def case(router, lean, seed, **widths):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    leaning, by, chunks = LEANS[lean]
    x, weights, ids = routed(router, keys[0], N, WIDTH, TOP_K, leaning, by)
    assert moe.expert_room(N, TOP_K, HELD[1] - HELD[0], WIDTH) == ROOM
    return ((x, weights, ids) + experts(keys[1], **widths),
            jnp.arange(N) % 9 != 8, chunks)


@pytest.mark.parametrize("lean", LEANS)
@pytest.mark.parametrize("router", ["route", "route_sigmoid",
                                    "route_softmax_bias"])
def test_chunks_of_the_room_give_every_rows_sum(router, lean):
    """The held choices fit their room, take two and three chunks of it
    (a router that leans on the held block), or are none: the sum is
    the sum over every row to float32's rounding, ``load`` the same,
    and a row that is not real gets nothing."""
    operands, valid, chunks = case(router, lean, 11)
    want, want_load = moe.held_experts(*operands, HELD[0], valid)
    got, load = jax.jit(lambda *a: moe.held_experts(
        *a, HELD[0], valid, router_width=WIDTH))(*operands)
    assert -(-int(want_load.sum()) // ROOM) == chunks
    assert load.tolist() == want_load.tolist()
    assert np.all(np.asarray(got)[~np.asarray(valid)] == 0)
    assert (np.abs(np.asarray(want)).max() > 0.1) == bool(chunks)
    assert np.abs(np.asarray(got - want)).max() < FLOAT32


def every_row_as_it_stood(x, weights, ids, w_gate_up, w_down, first_expert,
                          valid):
    """``held_experts`` before there was a room (PR 53's tree), written
    out: what all experts held still runs."""
    n, top_k = ids.shape
    e, _, f2 = w_gate_up.shape
    f = f2 // 2
    local = ids - first_expert
    held = (local >= 0) & (local < e) & valid[:, None]
    key = jnp.where(held, local, e).reshape(-1)
    order = jnp.argsort(key, stable=True)
    load = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
    hidden = moe._grouped_dot(x[order // top_k], w_gate_up, load, "xla")
    act = (jax.nn.silu(hidden[:, :f]) * hidden[:, f:]).astype(x.dtype)
    out = moe._grouped_dot(act, w_down, load, "xla")
    out = (out * weights.reshape(-1)[order][:, None]).astype(x.dtype)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    return jnp.sum(out[inverse].reshape(n, top_k, -1), axis=1,
                   dtype=jnp.float32).astype(x.dtype), load


@pytest.mark.parametrize("router", ["route", "route_sigmoid",
                                    "route_softmax_bias"])
def test_all_experts_held_is_the_call_as_it_stood(router):
    """A router as wide as the held block: no room, and the result is
    the parent's bit for bit, with the width given and without."""
    n, width, top_k = 96, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    x, weights, ids = routed(router, keys[0], n, width, top_k)
    w_gate_up, w_down = experts(keys[1], e=width)
    valid = jnp.arange(n) < 90
    assert moe.expert_room(n, top_k, width, width) is None
    want, want_load = jax.jit(every_row_as_it_stood, static_argnums=5)(
        x, weights, ids, w_gate_up, w_down, 0, valid)
    for given in (width, None):
        got, load = jax.jit(lambda *a: moe.held_experts(
            *a, 0, valid, router_width=given))(x, weights, ids, w_gate_up,
                                               w_down)
        assert load.tolist() == want_load.tolist()
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(want)).max() > 0.1


@pytest.mark.parametrize("lean", ["fit", "two"])
def test_the_kernel_in_interpret_mode_under_a_room(lean):
    operands, valid, chunks = case("route_sigmoid", lean, 23, f=128)
    got, load = moe.held_experts(*operands, HELD[0], valid,
                                 impl="pallas-interpret", router_width=WIDTH)
    want, want_load = moe.held_experts(*operands, HELD[0], valid,
                                       impl="xla", router_width=WIDTH)
    assert -(-int(load.sum()) // ROOM) == chunks
    assert load.tolist() == want_load.tolist()
    assert np.abs(np.asarray(want)).max() > 0.1
    assert np.abs(np.asarray(got - want)).max() < FLOAT32


@pytest.mark.parametrize("held,overflows", [
    ([[60, 60], [100, 28], [100, 29], [0, 0], [128, 128]], 2),
    ([[1, 2]], 0),
    ([[129, 0], [0, 129]], 2)])
def test_the_sixth_counter_counts_the_steps_that_pass_their_room(
        held, overflows):
    """128 rows x 4 choices over 16 experts, 2 held: a room of 128. A
    step counts where its held choices are more than that, and a call
    without the router's width, or with every expert held, counts
    none."""
    names = registry.family("lfm2_moe").counters
    assert names.index("room_overflows") == 5
    valid = jnp.ones((128, 1), bool)
    assert moe.expert_room(128, 4, 2, 16) == 128
    stats = no_width = all_held = jnp.zeros((len(names) + 2,), jnp.float32)
    for load in held:
        load = jnp.asarray(load, jnp.int32)
        stats = count_a_step(stats, load, valid, 16)
        no_width = count_a_step(no_width, load, valid, None)
        all_held = count_a_step(all_held, load, valid, 2)
    assert float(stats[5]) == overflows
    assert float(no_width[5]) == float(all_held[5]) == 0
    # The five before it as they were; what a family keeps after them
    # stays.
    assert stats[:5].tolist() == no_width[:5].tolist() == [
        len(held), 128 * 4 * len(held), sum(map(sum, held)),
        sum(map(max, held)), sum(sum(x > 0 for x in load) for load in held)]
    assert stats[6:].tolist() == [0, 0]


def count_a_step(stats, load, valid, width):
    return jax.jit(lambda s, l, v: moe.count_step(s, 4, l, v, width))(
        stats, load, valid)
