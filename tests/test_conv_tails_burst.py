"""The convolution's tails ride the deferred burst: a recurrent layer's
K-1 held inputs are gathered from the slot pool once a burst, shifted
dense in the scan's carry and scattered back once (both hybrid
families; the burst's K/V side: tests/test_qwen3_next_deferred.py).

Tiny widths, float32, on the CPU. Three bursts are compared on the same
requests. ``carried`` is what the engine serves with deferred writes.
``pool`` is the same deferred burst with the family's ``conv_tail``
switched off, so that every step gathers from the tail pool and
scatters back, which is the program before the tails were carried:
tokens, log-probabilities and every cache entry agree with it bit for
bit. ``eager`` writes K/V as it goes: the deferred burst sums the
softmax tail first, then blocks, so what follows the first attention
layer differs from it in the last bits of float32 (``ORDER`` 1e-5, as
in tests/test_qwen3_next_deferred.py; what precedes it is compared bit
for bit), and the tokens are the same.
"""

import asyncio
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

import test_jamba_engine
import test_lfm2_moe_engine
import test_qwen3_next_engine
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models import registry
from production_stack_tpu.ops import gated_delta
from test_qwen3_next_deferred import burst_jaxpr, burst_scan

ORDER = 1e-5
EXCESS_OFF = "--xla_allow_excess_precision=false"
HYBRIDS = {"jamba": test_jamba_engine, "lfm2_moe": test_lfm2_moe_engine,
           "qwen3_next": test_qwen3_next_engine}
prompt_of = test_jamba_engine.prompt_of


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("num_valid", [(0, 0, 0), (1, 1, 1), (1, 0, 1)])
def test_the_decode_step_form_equals_the_chunk_form(num_valid, dtype):
    """At T = 1 ``causal_conv_step`` is ``causal_conv`` to the bit: the
    output, and the tail shifted where the row has a token and held
    where it has none."""
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (3, 1, 24), jnp.float32).astype(dtype)
    tail = jax.random.normal(keys[1], (3, 3, 24), jnp.float32).astype(dtype)
    w = jax.random.normal(keys[2], (4, 24), jnp.float32).astype(dtype)
    n = jnp.array(num_valid, jnp.int32)
    want_y, want_tail = jax.jit(gated_delta.causal_conv)(x, tail, w, n)
    got_y, got_tail = jax.jit(gated_delta.causal_conv_step)(
        x[:, 0], tuple(tail[:, j] for j in range(3)), w, n > 0)
    np.testing.assert_array_equal(np.asarray(got_y, np.float32),
                                  np.asarray(want_y[:, 0], np.float32))
    np.testing.assert_array_equal(
        np.asarray(jnp.stack(got_tail, axis=1), np.float32),
        np.asarray(want_tail, np.float32))
    assert got_y.dtype == want_y.dtype
    assert {t.dtype for t in got_tail} == {want_tail.dtype}


def keep_tails_in_the_pool(monkeypatch, *families):
    """Switches the families' ``conv_tail`` off: the deferred burst as
    it was before the tails were carried."""
    for name in families:
        monkeypatch.setitem(registry.FAMILIES, name, dataclasses.replace(
            registry.family(name), conv_tail=False))


def requests(case):
    """(requests as (prompt, sampling keywords), scheduler keywords)."""
    if case == "a row stops inside the burst":
        # Both run out of budget inside the one burst of eight, one at
        # its third token and one at its fifth (a stop on a token:
        # tests/test_qwen3_next_deferred.py; tiny Jamba repeats itself).
        return [(prompt_of(19, seed=3), dict(max_tokens=3)),
                (prompt_of(27, seed=4), dict(max_tokens=5))], dict(
                    decode_steps=8)
    if case == "padded rows on the trash slot":
        return [(prompt_of(n, seed=n), dict(max_tokens=5))
                for n in (20, 14, 9)], {}
    if case == "a prompt of two chunks just before":
        return [(prompt_of(45, seed=45), dict(max_tokens=5)),
                (prompt_of(20, seed=20), dict(max_tokens=5))], {}
    assert case == "two bursts in a row"
    return [(prompt_of(20, seed=1), dict(max_tokens=9)),
            (prompt_of(14, seed=2), dict(max_tokens=9))], {}


def serve(family, case, deferred, **model):
    """Runs the case to its end: (runner, per request its tokens and
    the log-probabilities served with them)."""
    reqs, scheduler = requests(case)
    engine = LLMEngine(HYBRIDS[family].engine_config(
        HYBRIDS[family].model_config(**model),
        deferred_kv_writes=deferred, **scheduler))
    ids = [engine.add_request(prompt, SamplingParams(
        temperature=0.0, ignore_eos=True, logprobs=True, top_logprobs=3,
        **keywords)) for prompt, keywords in reqs]
    seqs = [engine.sequences[i] for i in ids]
    served = {i: [] for i in ids}
    while any(s.state.name not in ("FINISHED", "ABORTED") for s in seqs):
        for out in engine.step():
            if out.new_token is not None:
                served[out.seq_id].append(out.logprobs)
    assert engine.cache_manager.num_used_state_slots == 0
    return engine.runner, [(s.output_token_ids, served[s.seq_id])
                           for s in seqs]


def flat(logprobs):
    """One request's served log-probabilities as one float array."""
    return np.array([[entry[0]] + [lp for _, lp in entry[1]]
                     for entry in logprobs], np.float64)


@pytest.mark.parametrize("case", [
    "a row stops inside the burst", "padded rows on the trash slot",
    "a prompt of two chunks just before", "two bursts in a row"])
@pytest.mark.parametrize("family", sorted(HYBRIDS))
def test_carried_tails_leave_tokens_and_pools_as_the_pool_path(
        family, case, monkeypatch):
    reqs, _ = requests(case)
    carried, got = serve(family, case, True)
    eager, want_eager = serve(family, case, False)
    keep_tails_in_the_pool(monkeypatch, family)
    pool, want = serve(family, case, True)

    assert [len(tokens) for tokens, _ in got] == [
        k["max_tokens"] for _, k in reqs]
    for (tokens, lps), (pool_tokens, pool_lps), (eager_tokens, eager_lps) \
            in zip(got, want, want_eager):
        assert tokens == pool_tokens == eager_tokens
        np.testing.assert_array_equal(flat(lps), flat(pool_lps))
        np.testing.assert_allclose(flat(lps), flat(eager_lps), rtol=0,
                                   atol=ORDER)
    linear = carried.config.model.layer_is_linear
    first_attention = linear.index(False)
    for name in ("k_cache", "v_cache"):
        for layer, is_linear in enumerate(linear):
            if getattr(carried, name)[layer] is None:
                # A family that declares the tail alone: no k entry on
                # any path.
                assert getattr(pool, name)[layer] is None
                assert getattr(eager, name)[layer] is None
                continue
            have = np.asarray(getattr(carried, name)[layer])
            # Slot by slot, the trash slot and the trash page too: the
            # two deferred bursts send the same rows there.
            np.testing.assert_array_equal(
                have, np.asarray(getattr(pool, name)[layer]))
            other = np.asarray(getattr(eager, name)[layer])
            if not is_linear:
                # Page 0 is the trash page: the eager burst sends a
                # frozen row's steps there, the flush its unused slots.
                have, other = have[:, 1:], other[:, 1:]
            assert np.abs(have).max() > 0
            if layer <= first_attention:
                np.testing.assert_array_equal(have, other)
            else:
                np.testing.assert_allclose(have, other, rtol=0, atol=ORDER)


@pytest.mark.parametrize("family", sorted(HYBRIDS))
def test_carried_tails_at_bfloat16_are_the_pool_paths_to_the_bit(
        family, monkeypatch):
    """In the dtype the cells serve: the same tokens, log-probabilities,
    pools and planes from the two deferred bursts (on the CPU; what a
    TPU's fusions round is its compiler's: PERF.md section 6, PR 35).

    ``lfm2_moe`` convolves a product of two bfloat16 arrays (``B * x``),
    and XLA keeps such a product in float32 where it flows straight
    into float32 arithmetic (``xla_allow_excess_precision``, on by
    default): the carried path's current input then skips one bfloat16
    rounding that the pool path's concatenation makes. With that flag
    off the two agree to the bit (the case after this one runs it so);
    with it on the family is held to a bfloat16 step on the
    log-probabilities and to equal tokens."""
    exact = (family != "lfm2_moe"
             or EXCESS_OFF in os.environ.get("XLA_FLAGS", ""))
    case = "two bursts in a row"
    carried, got = serve(family, case, True, dtype="bfloat16")
    keep_tails_in_the_pool(monkeypatch, family)
    pool, want = serve(family, case, True, dtype="bfloat16")
    assert carried.v_cache[0].dtype == jnp.bfloat16
    for (tokens, lps), (pool_tokens, pool_lps) in zip(got, want):
        assert tokens == pool_tokens
        np.testing.assert_allclose(flat(lps), flat(pool_lps), rtol=0,
                                   atol=0 if exact else 2 ** -7)
    for name in ("k_cache", "v_cache"):
        for have, other in zip(getattr(carried, name), getattr(pool, name)):
            assert (have is None) == (other is None)
            if have is not None:
                np.testing.assert_allclose(
                    np.asarray(have, np.float32),
                    np.asarray(other, np.float32), rtol=0,
                    atol=0 if exact else 2 ** -5)


def test_without_excess_precision_lfm2s_carried_tails_are_exact_too():
    """The room given to ``lfm2_moe`` above is XLA's excess precision
    and nothing else: the same case in a child whose compiler may not
    keep a bfloat16 product in float32 asks for equal bits, and
    passes. (The flag is read when the backend starts, hence a
    child.)"""
    flags = (os.environ.get("XLA_FLAGS", "") + " " + EXCESS_OFF).strip()
    done = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.abspath(__file__), "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly", "-k",
         "bfloat16_are_the_pool_paths_to_the_bit and lfm2_moe"],
        env=dict(os.environ, XLA_FLAGS=flags), capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    assert "1 passed" in done.stdout and "failed" not in done.stdout


@pytest.mark.parametrize("family", sorted(HYBRIDS))
def test_the_tails_ride_the_scan_dense_and_their_pool_does_not(
        family, monkeypatch):
    """In the burst's scan a recurrent layer's tail pool is neither a
    constant nor a carry operand: what is carried is K-1 arrays
    ``[B, channels]`` a layer; the state pool rides as before, where
    the family has one (one that declares the tail alone has nothing
    of a slot pool in the scan at all). With the family's ``conv_tail``
    off the pool rides, so the guard can tell the two apart."""
    runner = LLMEngine(HYBRIDS[family].engine_config(
        deferred_kv_writes=True)).runner
    model = runner.config.model
    layer = model.layer_is_linear.index(True)
    layers = model.layer_is_linear.count(True)
    tails = runner.v_cache[layer].shape
    row = (4, tails[2])                      # burst_scan's batch of 4
    consts, carry = burst_scan(runner, deferred=True)
    assert tails not in carry and tails not in consts
    assert carry.count(row) == layers * tails[1]
    if runner.k_cache[layer] is None:
        assert family == "lfm2_moe"
        assert not [shape for shape in consts + carry
                    if shape[:1] == tails[:1]]
    else:
        assert carry.count(runner.k_cache[layer].shape) == layers
    keep_tails_in_the_pool(monkeypatch, family)
    consts, carry = burst_scan(runner, deferred=True)
    assert carry.count(tails) == layers and row not in carry


@pytest.mark.parametrize("layout", ["per_layer", "stacked"])
def test_the_llama_familys_deferred_burst_is_untouched(layout, monkeypatch):
    """A family with no recurrent layer carries L K/V tails a cache and
    nothing of a convolution: no gather and no scatter before its scan,
    and the same jaxpr whether or not any family's ``conv_tail`` path
    can be reached."""
    from test_deferred_kv import _engine
    runner = _engine(decode_steps=4, deferred=True,
                     cache_layout=layout).runner
    model = runner.config.model
    assert not model.family.conv_tail and not any(model.layer_is_linear)
    jaxpr = burst_jaxpr(runner, deferred=True)
    scan, = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    consts = scan.params["num_consts"]
    carry = [v.aval.shape for v in scan.invars[
        consts:consts + scan.params["num_carry"]]]
    tail = (4, 4, model.num_key_value_heads, model.head_dim)
    # tokens, positions, active, emitted (the zero-size placeholders of
    # counts and fsm are no operands), then the tails.
    assert carry == [(4, 1), (4, 1), (4,), (4,)] + [tail] * (
        2 * model.num_hidden_layers)
    before = [e.primitive.name for e in jaxpr.eqns[:jaxpr.eqns.index(scan)]]
    assert "gather" not in before and "scatter" not in before
    text = str(jaxpr)
    keep_tails_in_the_pool(monkeypatch, *HYBRIDS)
    assert str(burst_jaxpr(runner, deferred=True)) == text


@pytest.mark.parametrize("family,deferred,want", [
    ("jamba", True, "burst"), ("jamba", False, "step"),
    ("qwen3_next", True, "burst"), ("lfm2_moe", True, "burst"),
    ("llama", True, None)])
def test_version_says_where_the_convolution_tails_are_kept(
        family, deferred, want):
    body = version_of(family, deferred)
    assert body["kv_writes"] == ("deferred" if deferred else "eager")
    assert body.get("conv_tails") == want
    assert ("conv_tails" in body) == (want is not None)


def version_of(family, deferred=True) -> dict:
    """``/version`` of a tiny engine of ``family``."""
    from production_stack_tpu.engine.server import EngineServer
    if family == "llama":
        from test_deferred_kv import _engine
        engine = _engine(decode_steps=4, deferred=deferred)
    else:
        engine = LLMEngine(HYBRIDS[family].engine_config(
            deferred_kv_writes=deferred))

    async def version():
        client = TestClient(TestServer(
            EngineServer(engine, "tiny").build_app()))
        await client.start_server()
        try:
            return await (await client.get("/version")).json()
        finally:
            await client.close()

    return asyncio.run(version())


@pytest.mark.parametrize("family,routed", [
    ("qwen3_next", True), ("lfm2_moe", True), ("jamba", False),
    ("llama", False)])
def test_version_states_the_expert_products_tiles(family, routed):
    """A family that serves routed experts says which tiles its two
    grouped products take and how many grid steps a visit is; a dense
    family says nothing of them."""
    from production_stack_tpu.ops.moe import expert_tiles
    body = version_of(family)
    assert ("expert_tiles" in body) == routed
    if routed:
        model = HYBRIDS[family].engine_config().model
        hidden, width = model.hidden_size, model.moe_intermediate_size
        itemsize = jnp.dtype(model.jax_dtype).itemsize
        tiles = body["expert_tiles"]
        assert tiles["gate_up"] == list(
            expert_tiles(hidden, 2 * width, itemsize))
        assert tiles["down"] == list(expert_tiles(width, hidden, itemsize))
        # Tiny widths: each product is one tile.
        assert tiles["steps_per_visit"] == 2


@pytest.mark.parametrize("family,routed", [
    ("qwen3_next", True), ("lfm2_moe", True), ("jamba", False),
    ("llama", False)])
def test_version_states_the_expert_room(family, routed):
    """Beside the tiles, the rows that go around and through the
    products a chunk, for the burst and for each shape a prefill step
    is compiled at (``ops/moe.py`` ``expert_room``); ``null`` at these
    tiny shapes, where a tile of 128 is every row."""
    from production_stack_tpu.engine.model_runner import prefill_shapes
    from production_stack_tpu.ops.moe import expert_room
    body = version_of(family)
    assert ("expert_room" in body) == routed
    if routed:
        config = HYBRIDS[family].engine_config()
        model, scheduler = config.model, config.scheduler
        shapes = prefill_shapes(scheduler.prefill_batch_size,
                                scheduler.prefill_chunk_size)
        assert body["expert_room"] == {
            "burst": expert_room(
                scheduler.max_num_seqs, model.num_experts_per_tok,
                model.num_experts, model.router_width),
            "prefill": {f"{rows}x{tokens}": expert_room(
                rows * tokens, model.num_experts_per_tok,
                model.num_experts, model.router_width)
                for rows, tokens in shapes}}
        assert len(shapes) >= 2
