"""Deferred K/V writes for a model that keeps a recurrent state beside
its pages (Qwen3-Next): the burst keeps each step's K/V of the
full-attention layers in a tail and flushes once, while the linear
layers' state pools and the expert counters go on being read and
written every step (the Llama family's cases: tests/test_deferred_kv.py;
the convolution tails, carried dense: tests/test_conv_tails_burst.py).

Tiny widths, float32, on the CPU, in the forms the model's kernels
take: plain XLA; the model's own Pallas kernels in interpret mode
beside XLA decode attention; and those with the Pallas paged decode
kernel too, which is what ``auto`` resolves on the chip (the deferred
burst attends through ``models/llama.py`` ``deferred_attention``).

``ORDER`` 1e-5: the deferred burst sums the softmax tail first, then
blocks, so whatever follows the first full-attention layer differs
from the eager burst in the last bits of float32 (readings under
1e-6); what precedes it is compared bit for bit.
"""

import asyncio
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.model_runner import deferred_kv_eligible
from production_stack_tpu.engine.sequence import SamplingParams
from test_qwen3_next_engine import (
    engine_config,
    finish,
    greedy,
    model_config,
    prompt_of,
)

ORDER = 1e-5
FORMS = {
    "xla": dict(attention_impl="xla"),
    "pallas-interpret": dict(attention_impl="pallas-interpret",
                             attention_impl_decode="xla"),
    "pallas-interpret-decode": dict(attention_impl="pallas-interpret"),
}
forms = pytest.mark.parametrize("form", sorted(FORMS))


def hybrid_engine(form, deferred, **scheduler):
    return LLMEngine(engine_config(
        model=model_config(**FORMS[form]), deferred_kv_writes=deferred,
        **scheduler))


@pytest.fixture(scope="module")
def hybrid_engines():
    """``engines(form, deferred)``: ``hybrid_engine(form, deferred)``,
    built once a module (docs/source/dev_guide/testing.md) for the
    cases that run greedy requests to their end on it or only read it.
    A hybrid takes no prefix hit and a recycled slot needs no clearing
    (tests/test_qwen3_next_engine.py), so greedy tokens do not depend
    on what ran before; a case that compares whole planes or pools
    builds its own."""
    built = {}

    def engines(form, deferred):
        if (form, deferred) not in built:
            built[form, deferred] = hybrid_engine(form, deferred)
        return built[form, deferred]

    yield engines
    built.clear()


@forms
def test_deferred_tokens_equal_the_eager_bursts(form, hybrid_engines):
    """Four bursts of four steps, rows that cross a page boundary
    inside a burst, and a prompt of two chunks (45 of 32), whose state
    is carried between the chunks before the first burst reads it."""
    prompts = [prompt_of(n, seed=n) for n in (45, 20, 14, 33)]
    eager, deferred = (
        [s.output_token_ids
         for s in greedy(hybrid_engines(form, d), prompts, max_tokens=15)]
        for d in (False, True))
    assert deferred == eager
    assert all(len(t) == 15 for t in deferred)


@forms
def test_a_burst_leaves_the_planes_and_the_state_as_the_eager_one(
        form, hybrid_engines):
    """One row stops on a token in the middle of a burst, one runs out
    of budget there: the flush takes what each emitted and no more, and
    a frozen row's pools are left as they were."""
    prompts = [prompt_of(19, seed=3), prompt_of(27, seed=4)]
    free = greedy(hybrid_engines(form, False), prompts, max_tokens=8)
    stop = free[0].output_token_ids[1]

    def run(deferred):
        engine = hybrid_engine(form, deferred, decode_steps=8)
        ids = [engine.add_request(prompts[0], SamplingParams(
                   temperature=0.0, max_tokens=8, stop_token_ids=[stop])),
               engine.add_request(prompts[1], SamplingParams(
                   temperature=0.0, max_tokens=5, ignore_eos=True))]
        seqs = [engine.sequences[i] for i in ids]
        finish(engine, seqs)
        assert [len(s.output_token_ids) for s in seqs] == [2, 5]
        return engine.runner

    eager, deferred = run(False), run(True)
    linear = eager.config.model.layer_is_linear
    first_full = linear.index(False)
    for name in ("k_cache", "v_cache"):
        for layer, is_linear in enumerate(linear):
            want = np.asarray(getattr(eager, name)[layer])
            got = np.asarray(getattr(deferred, name)[layer])
            if not is_linear:
                # Page 0 is the trash page: the eager burst sends a
                # frozen row's steps there, the flush its unused slots.
                want, got = want[:, 1:], got[:, 1:]
                assert np.abs(want).max() > 0
            if layer <= first_full:
                np.testing.assert_array_equal(got, want)
            else:
                np.testing.assert_allclose(got, want, rtol=0, atol=ORDER)


@forms
def test_the_expert_counters_ride_the_deferred_burst(
        form, hybrid_engines, monkeypatch):
    engine = hybrid_engines(form, True)
    read, seen = engine.runner.read_moe_stats, []

    def record():
        seen.append(read())
        return seen[-1]

    monkeypatch.setattr(engine.runner, "read_moe_stats", record)
    greedy(engine, [prompt_of(20, seed=1), prompt_of(11, seed=2)],
           max_tokens=9)
    bursts = [s for s in seen if s]
    layers = engine.config.model.num_hidden_layers
    steps = engine.config.scheduler.decode_steps
    assert len(bursts) == 2                 # 1 from prefill + 4 + 4
    for stats in bursts:
        assert stats["layer_steps"] == steps * layers
        assert stats["choices"] == 2 * steps * layers * 4  # top-4, 2 rows
        assert stats["held_choices"] == stats["choices"]   # all held
    assert engine.runner.read_moe_stats() is None          # zeroed


def burst_jaxpr(runner, deferred):
    """The runner's burst program, four rows and four steps, traced on
    the runner's own caches."""
    b, steps = 4, 4
    pages = runner.config.scheduler.max_model_len // \
        runner.config.cache.page_size
    row = functools.partial(jnp.zeros, (b,))
    impl = (runner._decode_burst_deferred_impl if deferred
            else runner._decode_burst_impl)
    state = ({"state_slots": row(jnp.int32)}
             if runner.config.model.has_recurrent_state else {})
    return jax.make_jaxpr(functools.partial(
        impl, num_steps=steps, **state))(
        runner.params, runner.k_cache, runner.v_cache,
        jnp.zeros((b, 1), jnp.int32), jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b, pages), jnp.int32), row(jnp.int32), row(bool),
        row(jnp.int32), jnp.full((b, 1), -1, jnp.int32),
        row(jnp.float32), row(jnp.float32), row(jnp.int32),
        jax.random.PRNGKey(0), None, None, None, None, None, None, None)


def burst_scan(runner, deferred):
    """The scan of that program: (shapes of its constants, shapes of
    its carry)."""
    jaxpr = burst_jaxpr(runner, deferred)
    scan, = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    consts = scan.params["num_consts"]
    carry = scan.params["num_carry"]
    shapes = [v.aval.shape for v in scan.invars]
    return shapes[:consts], shapes[consts:consts + carry]


@pytest.mark.parametrize("family", ["qwen3_next", "jamba", "lfm2_moe",
                                    "llama"])
def test_no_page_plane_rides_the_deferred_scan(family, hybrid_engines):
    """The planes are constants of the scan and not its carry, which
    is what keeps XLA from copying them around the block loop; of a
    hybrid model's caches the state pools and the counters are
    carried."""
    if family == "qwen3_next":
        runner = hybrid_engines("xla", True).runner
    elif family in ("jamba", "lfm2_moe"):
        import importlib
        runner = LLMEngine(importlib.import_module(
            f"test_{family}_engine").engine_config(
            deferred_kv_writes=True)).runner
    else:
        from test_deferred_kv import _engine
        runner = _engine(decode_steps=4, deferred=True).runner
    planes = 2 * runner.config.model.num_kv_layers
    plane = runner.k_cache[
        runner.config.model.layer_is_linear.index(False)].shape
    consts, carry = burst_scan(runner, deferred=True)
    assert plane not in carry
    assert consts.count(plane) == planes
    if family != "llama":
        linear = runner.config.model.layer_is_linear.index(True)
        # The last entry: the expert counters, a Mamba layer's pool;
        # a layer that keeps the tail alone has no k entry to ride.
        for pool in (runner.k_cache[linear], runner.k_cache[-1]):
            if pool is not None:
                assert pool.shape in carry and pool.shape not in consts
        # The convolution tails' pool is read before the scan and
        # written after it (tests/test_conv_tails_burst.py).
        tails = runner.v_cache[linear].shape
        assert tails not in carry and tails not in consts
    # The eager burst carries them: the guard can tell the two apart.
    consts, carry = burst_scan(runner, deferred=False)
    assert carry.count(plane) == planes and plane not in consts


@pytest.mark.parametrize("architecture,file", [
    ("qwen3_next", "qwen3-next-80b-a3b-ep4.json"),
    ("jamba", "jamba2-3b.json"),
    ("lfm2_moe", "lfm2-8b-a1b-ep4.json")])
def test_auto_resolves_deferred_writes_on_for_the_hybrid_cell(
        architecture, file):
    from production_stack_tpu.engine.server import (
        _resolve_deferred_kv,
        parse_args,
    )
    assert deferred_kv_eligible(architecture, 32)
    assert not deferred_kv_eligible(architecture, 1)
    assert not deferred_kv_eligible("mixtral", 32)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs", file)
    with open(path) as f:
        hf = json.load(f)
    flags = hf["chipbench"]["server_flags"]
    assert "deferred-kv-writes" not in flags
    argv = ["--model", "x", "--random-weights"]
    for name, value in flags.items():
        argv += [f"--{name}", str(value)]
    args = parse_args(argv)
    config = ModelConfig.from_hf_config(hf)
    assert config.architecture == architecture
    assert args.deferred_kv_writes == "auto"
    assert _resolve_deferred_kv(args, config) is True
    args.deferred_kv_writes = "off"
    assert _resolve_deferred_kv(args, config) is False


def test_version_names_the_write_mode_the_hybrid_is_served_with(
        hybrid_engines):
    from production_stack_tpu.engine.server import EngineServer

    async def kv_writes(deferred):
        server = EngineServer(hybrid_engines("xla", deferred),
                              "tiny-qwen3-next")
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            return (await (await client.get("/version")).json())[
                "kv_writes"]
        finally:
            await client.close()

    assert asyncio.run(kv_writes(True)) == "deferred"
    assert asyncio.run(kv_writes(False)) == "eager"

