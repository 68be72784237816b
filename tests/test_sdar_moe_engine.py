"""SDAR-MoE through the engine: a prefill of whole blocks that yields
no token, the prompt's remainder entering the first block as given
places, the block burst over pages, tails and store passes, the
hand-over of 0..4 tokens a row a block in position order, budgets and
stop tokens that cut inside a block, preemption between blocks, the
counters, the request fields, and what start-up and a request refuse
(the model and its ops alone: tests/test_sdar_moe.py).

Tiny widths, float32, seeded, on the CPU. The oracle is the plain
reference of the family (chipbench/reference/sdar_family.py): its
``generate`` is the published loop, its ``log_probs`` what the
benchmark's check asks. ``FLOAT32`` 2e-5 on log-probabilities (both
sides float32 on one CPU with the same weights; the readings are under
2e-6), ``INTERPRET`` 2e-4 where a Pallas kernel in interpret mode sums
in another order, ``LEFT_OUT`` 3e-4 for a term turned in the reference.
"""

import asyncio
import json

import numpy as np
import pytest
from test_sdar_moe import (FLOAT32, INTERPRET, LEFT_OUT, model_config,
                           prompt_of)

from chipbench.reference import sdar_family as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    LoRAConfig,
    OffloadConfig,
    ParallelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams

# Every remainder of a prompt over a block of 4, one shorter than a
# block, one of several chunks of 32, with answers that end on and off a
# block's edge.
REQUESTS = ((37, 9), (3, 5), (64, 1), (18, 2), (70, 11), (32, 7),
            (35, 5), (2, 1))


def engine_config(model=None, pages=64, **scheduler):
    sched = dict(max_num_seqs=4, max_model_len=256, prefill_chunk_size=32,
                 prefill_batch_size=2, decode_steps=6,
                 deferred_kv_writes=True)
    sched.update(scheduler)
    return EngineConfig(
        model=model or model_config(),
        cache=CacheConfig(page_size=16, num_pages=pages),
        scheduler=SchedulerConfig(**sched))


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(engine_config())


@pytest.fixture(scope="module")
def oracle(engine):
    return reference.model_of(engine.config.model, engine.runner.params)


def serve(engine, requests, **sampling):
    """[(prompt, sequence, [log-probability entry a token])]."""
    sampling = dict(dict(temperature=0.0, ignore_eos=True, logprobs=True,
                         top_logprobs=5), **sampling)
    rows = []
    for n, (length, answers) in enumerate(requests):
        prompt = prompt_of(length, seed=100 + n)
        sid = engine.add_request(prompt, SamplingParams(
            max_tokens=answers, **sampling))
        rows.append((prompt, engine.sequences[sid], []))
    by_id = {seq.seq_id: got for _, seq, got in rows}
    while any(seq.state.name not in ("FINISHED", "ABORTED")
              for _, seq, _ in rows):
        for out in engine.step():
            if out.new_token is not None:
                by_id[out.seq_id].append((out.new_token, out.logprobs))
    return rows


def worst_difference(got, want):
    return max(abs(lp - want[j, tid])
               for j, (_, entry) in enumerate(got) for tid, lp in entry[1])


@pytest.mark.parametrize("steps", [2, 3])
@pytest.mark.parametrize("strategy", reference.STRATEGIES)
def test_blocks_agree_with_the_published_loop(engine, oracle, strategy,
                                              steps):
    """Eight requests over four rows (rows wait, finish inside a burst
    and are replaced): tokens, and the top log-probabilities of every
    token at the pass that committed it, against the reference's own
    run of the published loop under the same rule; the dynamic rule at
    a threshold that random weights reach now and then."""
    rows = serve(engine, REQUESTS, remasking_strategy=strategy,
                 denoising_steps=steps, confidence_threshold=0.02)
    for prompt, seq, got in rows:
        tokens, want = reference.generate(
            oracle, prompt, seq.sampling.max_tokens, steps=steps,
            strategy=strategy, threshold=0.02)
        assert seq.output_token_ids == tokens == [t for t, _ in got]
        assert seq.finish_reason.value == "length"
        assert worst_difference(got, want) < FLOAT32


def test_the_checks_reading_of_a_sequential_answer(engine, oracle):
    """What chipbench/reference/check.py does: prompt and answers
    alone, under the configuration's own rule and steps."""
    rows = serve(engine, REQUESTS)
    for prompt, seq, got in rows:
        answers = len(seq.output_token_ids)
        first = len(prompt) - 1
        want = np.asarray(reference.log_probs(
            oracle, prompt + seq.output_token_ids,
            list(range(first, first + answers))))
        assert seq.output_token_ids == [int(t) for t in want.argmax(-1)]
        assert worst_difference(got, want) < FLOAT32


@pytest.mark.parametrize("lever", [
    dict(head_norms=False), dict(own_block=False),
    dict(causal_prefill=True), dict(head_shift=1), dict(norm_topk=False),
    dict(stale_blocks=True)])
def test_a_term_turned_in_the_reference_fails_the_check(engine, lever):
    """Left out or put in, each moves the served log-probabilities by
    over three times the tolerance: a head norm, the block's own keys,
    causal in place of block sight over the prompt, a shifted head,
    ``norm_topk_prob``, and a cache that keeps a denoising pass's K/V
    (the store pass skipped)."""
    rows = serve(engine, ((37, 9), (70, 11)))
    turned = reference.model_of(engine.config.model, engine.runner.params,
                                **lever)
    for prompt, seq, got in rows:
        first = len(prompt) - 1
        want = np.asarray(reference.log_probs(
            turned, prompt + seq.output_token_ids,
            list(range(first, first + len(got)))))
        assert worst_difference(got, want) > LEFT_OUT


def test_the_pallas_forms_serve_the_same_blocks():
    served = LLMEngine(engine_config(
        model_config(attention_impl="pallas-interpret")))
    oracle = reference.model_of(served.config.model, served.runner.params)
    rows = serve(served, ((37, 9), (3, 5), (64, 6)),
                 remasking_strategy="low_confidence_static")
    for prompt, seq, got in rows:
        tokens, want = reference.generate(
            oracle, prompt, seq.sampling.max_tokens,
            strategy="low_confidence_static")
        assert seq.output_token_ids == tokens
        assert worst_difference(got, want) < INTERPRET


def test_a_stop_token_inside_a_block_ends_the_row_there(engine):
    """The first answer of eight whose fifth token (the second place of
    its second whole block) is new to it: asked again with that token
    as a stop id, the row ends on it, inside the block."""
    for seed in range(8):
        prompt = prompt_of(36, seed=200 + seed)
        sid = engine.add_request(prompt, SamplingParams(
            temperature=0.0, max_tokens=9, ignore_eos=True))
        seq = engine.sequences[sid]
        while seq.state.name != "FINISHED":
            engine.step()
        free = seq.output_token_ids
        if free[5] not in free[:5]:
            break
    else:
        pytest.fail("no answer with a new token at its sixth place")
    sid = engine.add_request(prompt, SamplingParams(
        temperature=0.0, max_tokens=9, stop_token_ids=[free[5]]))
    seq = engine.sequences[sid]
    # The tokenizer's own end id stays out of the way.
    seq.sampling.stop_token_ids = [free[5]]
    outs = []
    while seq.state.name != "FINISHED":
        outs += [o for o in engine.step() if o.seq_id == sid]
    assert seq.output_token_ids == free[:6]
    assert seq.finish_reason.value == "stop"
    assert [o.new_token for o in outs if o.new_token is not None] == free[:6]
    assert not seq.pages


def test_a_preempted_row_resumes_at_a_blocks_edge(engine):
    """Preempted between blocks: the pages go, prompt and answer so far
    are prefilled again under sight by block (what the store passes
    wrote), and the answer is the undisturbed one."""
    requests = ((37, 21), (18, 14))
    free = [seq.output_token_ids for _, seq, _ in
            serve(engine, requests, logprobs=False)]
    ids = [engine.add_request(prompt_of(n, seed=100 + i), SamplingParams(
        temperature=0.0, max_tokens=m, ignore_eos=True))
        for i, (n, m) in enumerate(requests)]
    seqs = [engine.sequences[i] for i in ids]
    while len(seqs[0].output_token_ids) < 7:
        engine.step()
    assert seqs[0].total_len % 4 == 0       # a block's edge
    before = engine.scheduler.num_preemptions
    engine.scheduler._preempt(seqs[0])
    assert not seqs[0].pages and seqs[0].state.name == "WAITING"
    while any(s.state.name != "FINISHED" for s in seqs):
        engine.step()
    assert engine.scheduler.num_preemptions == before + 1
    assert seqs[0].all_token_ids[37:] == free[0]
    assert seqs[1].output_token_ids == free[1]


def pages_of(engine, seq, tokens):
    """K and V of ``seq``'s first ``tokens`` positions, every layer:
    [layers, 2, kv, d, tokens]."""
    layers = engine.config.model.num_hidden_layers
    out = []
    for layer in range(layers):
        planes = [np.concatenate(
            [np.asarray(cache[layer][:, page]) for page in seq.pages], -1)
            for cache in (engine.runner.k_cache, engine.runner.v_cache)]
        out.append(np.stack(planes)[..., :tokens])
    return np.stack(out)


def test_the_store_passes_write_what_a_prefill_writes(engine):
    """K/V of a generated text in the pages (written by the store
    passes, flushed from the tails) against the pages a fresh engine
    fills by prefilling prompt + that text under sight by block: the
    same, so a page of generated text is as good a prefix as any."""
    prompt = prompt_of(30, seed=40)
    sid = engine.add_request(prompt, SamplingParams(
        temperature=0.0, max_tokens=60, ignore_eos=True))
    seq = engine.sequences[sid]
    while len(seq.output_token_ids) < 18:
        engine.step()
    stored = seq.total_len
    assert stored % 4 == 0 and stored >= 48
    wrote = pages_of(engine, seq, stored)
    fresh = LLMEngine(engine_config())
    sid = fresh.add_request(seq.all_token_ids, SamplingParams(
        temperature=0.0, max_tokens=4, ignore_eos=True))
    again = fresh.sequences[sid]
    while again.state.name != "RUNNING":
        fresh.step()
    assert not again.output_token_ids
    assert np.abs(wrote - pages_of(fresh, again, stored)).max() < FLOAT32
    engine.abort_request(seq.seq_id)
    while engine.has_work():
        engine.step()


def test_the_step_record_and_the_counters_count_passes_and_tokens():
    from production_stack_tpu.engine.tracing import EngineTracer
    engine = LLMEngine(engine_config())
    engine.tracer = EngineTracer(ring_size=256)
    rows = serve(engine, ((16, 8), (17, 7)), logprobs=False,
                 temperature=1.0)
    bursts = [s for s in engine.tracer.recent_steps(limit=256)
              if s.get("kind") == "decode"]
    # One burst: two blocks of two denoising passes and a store pass;
    # window is the passes that ran, not the flag.
    assert len(bursts) == 1
    assert (bursts[0]["denoise_passes"], bursts[0]["store_passes"],
            bursts[0]["window"], bursts[0]["decode_rows"],
            bursts[0]["blocks"], bursts[0]["committed"]) == (
        4, 1, 5, 2, 4, 15)
    m = engine.metrics
    assert (m.diffusion_denoise_passes_total,
            m.diffusion_store_passes_total, m.diffusion_blocks_total,
            m.diffusion_committed_tokens_total) == (4, 1, 4, 15)
    assert sum(len(seq.output_token_ids) for _, seq, _ in rows) == 15
    text = "\n".join(m.render())
    for name in ("denoise_passes", "store_passes", "blocks",
                 "committed_tokens"):
        assert f"vllm:diffusion_{name}_total" in text
    # A prefill step of this family samples nothing and reads nothing.
    prefills = [s for s in engine.tracer.recent_steps(limit=256)
                if s.get("kind") == "prefill"]
    assert prefills
    assert bursts[0]["moe_layer_steps"] == 5 * 2   # passes x layers


@pytest.mark.parametrize("top_k", (0, 3))
def test_sorted_passes_counts_the_passes_that_sorted_the_vocabulary(top_k):
    """A burst one of whose rows carries a top-k sorts the vocabulary in
    every denoising pass and says so; a burst without sorts in none."""
    from production_stack_tpu.engine.tracing import EngineTracer
    engine = LLMEngine(engine_config())
    engine.tracer = EngineTracer(ring_size=256)
    seqs = []
    for n, (length, answers, k) in enumerate(((16, 8, 0), (17, 7, top_k))):
        sid = engine.add_request(
            prompt_of(length, seed=100 + n), SamplingParams(
                temperature=1.0, top_k=k, max_tokens=answers,
                ignore_eos=True))
        seqs.append(engine.sequences[sid])
    while engine.has_work():
        engine.step()
    bursts = [s for s in engine.tracer.recent_steps(limit=256)
              if s.get("kind") == "decode"]
    assert len(bursts) == 1 and bursts[0]["denoise_passes"] == 4
    sorted_passes = 4 if top_k else 0
    assert bursts[0]["sorted_passes"] == sorted_passes
    assert bursts[0]["window"] == 5           # the passes, as before
    assert engine.metrics.diffusion_sorted_passes_total == sorted_passes
    assert (f"vllm:diffusion_sorted_passes_total {sorted_passes}"
            in engine.metrics.render())
    assert sum(len(seq.output_token_ids) for seq in seqs) == 15


def test_a_burst_reserves_pages_by_blocks():
    engine = LLMEngine(engine_config(decode_steps=30))
    assert engine.runner.burst_blocks == 10
    assert engine.scheduler._window_tokens(30) == 40
    sid = engine.add_request(prompt_of(18, seed=1), SamplingParams(
        temperature=0.0, max_tokens=60, ignore_eos=True))
    seq = engine.sequences[sid]
    while not seq.output_token_ids:
        engine.step()
    # 16 prefilled + the block of the remainder and 9 more, all stored.
    assert len(seq.output_token_ids) == 38 and seq.total_len == 56
    assert len(seq.pages) >= 4


# ---- what a request may carry ----------------------------------------------------


def test_request_fields_take_the_models_defaults_and_are_checked(engine):
    sampling = SamplingParams(temperature=0.0, max_tokens=4)
    engine.check_sampling(sampling)
    assert (sampling.denoising_steps, sampling.remasking_strategy,
            sampling.confidence_threshold) == (2, "sequential", 0.9)
    for bad, said in (
            (dict(denoising_steps=5), "1 to 4"),
            (dict(denoising_steps=0), "1 to 4"),
            (dict(remasking_strategy="random"), "one of sequential"),
            (dict(guided="json"), "its automaton walks left to right"),
            (dict(presence_penalty=0.5), "penalties"),
            (dict(logit_bias={3: 1.0}), "logit_bias"),
            (dict(min_tokens=2), "min_tokens"),
            (dict(seed=7), "seed")):
        with pytest.raises(ValueError, match=said):
            engine.check_sampling(SamplingParams(max_tokens=4, **bad))
    from production_stack_tpu.engine.config import tiny_model_config
    llama = LLMEngine(EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=32),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=64)))
    with pytest.raises(ValueError, match="generates left to right"):
        llama.check_sampling(SamplingParams(denoising_steps=2))


def test_the_server_streams_a_blocks_tokens_in_order_with_usage():
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer
    engine = LLMEngine(engine_config())
    server = EngineServer(engine, "tiny-sdar")

    async def run():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            body = {"model": "tiny-sdar", "prompt": "abcdefghij",
                    "max_tokens": 7, "temperature": 0, "ignore_eos": True,
                    "logprobs": 2, "remasking_strategy":
                    "low_confidence_static", "denoising_steps": 2}
            whole = await (await client.post("/v1/completions",
                                             json=body)).json()
            assert whole["usage"]["completion_tokens"] == 7
            assert whole["choices"][0]["finish_reason"] == "length"
            lp = whole["choices"][0]["logprobs"]
            assert len(lp["tokens"]) == len(lp["token_logprobs"]) == 7
            resp = await client.post("/v1/completions", json={
                **body, "stream": True,
                "stream_options": {"include_usage": True}})
            events = []
            async for line in resp.content:
                line = line.decode().strip()
                if line.startswith("data: ") and line != "data: [DONE]":
                    events.append(json.loads(line[6:]))
            text = "".join(e["choices"][0]["text"] for e in events
                           if e.get("choices"))
            assert text == whole["choices"][0]["text"]
            usage = [e["usage"] for e in events if e.get("usage")]
            assert usage and usage[-1]["completion_tokens"] == 7
            for bad in ({"denoising_steps": 9},
                        {"response_format": {"type": "json_object"}},
                        {"presence_penalty": 1.0}):
                refused = await client.post(
                    "/v1/completions", json={**body, **bad})
                assert refused.status == 400, bad
            version = await (await client.get("/version")).json()
            assert version["family"] == "sdar_moe"
            assert version["kv_writes"] == "deferred"
            assert version["block_diffusion"] == {
                "block_length": 4, "mask_token_id": 511,
                "denoising_steps": 2, "remasking_strategy": "sequential",
                "confidence_threshold": 0.9, "burst_passes": 6,
                "burst_blocks": 2}
            metrics = await (await client.get("/metrics")).text()
            assert "vllm:diffusion_store_passes_total" in metrics
            assert "vllm:diffusion_sorted_passes_total 0" in metrics
        finally:
            await client.close()

    asyncio.run(run())


# ---- what start-up refuses ---------------------------------------------------------


@pytest.mark.parametrize("over, said", [
    (dict(scheduler=dict(speculative_k=2, deferred_kv_writes=False)),
     "speculative decoding by prompt lookup"),
    (dict(scheduler=dict(unified_step=True)), "the unified ragged step"),
    (dict(scheduler=dict(async_scheduling=True)), "async scheduling"),
    (dict(scheduler=dict(decode_steps=2)),
     "a burst is at least 3 forward passes"),
    (dict(scheduler=dict(deferred_kv_writes=False)),
     "a burst is at least 3 forward passes"),
    (dict(scheduler=dict(prefill_chunk_size=30)),
     "a whole number of blocks of 4"),
    (dict(parallel=ParallelConfig(tensor_parallel_size=2)),
     "tensor parallelism"),
    (dict(parallel=ParallelConfig(pipeline_parallel_size=2)),
     "pipeline-parallel serving"),
    (dict(parallel=ParallelConfig(context_parallel_size=2)),
     "context-parallel prefill"),
    (dict(engine_role="prefill"), "this prefill yields none"),
    (dict(offload=OffloadConfig(enable=True)), "KV offload"),
    (dict(checkpoint_interval_tokens=8), "mid-stream checkpoint"),
    (dict(lora=LoRAConfig(enable=True)), "no LoRA targets"),
    (dict(cache=CacheConfig(page_size=16, num_pages=64,
                            kv_cache_dtype="int8")), "int8 KV pages"),
    (dict(cache=CacheConfig(page_size=16, num_pages=64,
                            cache_layout="stacked")), "cache_layout"),
])
def test_start_up_refuses_in_a_sentence_that_says_why(over, said):
    over = dict(over)
    scheduler = dict(max_num_seqs=4, max_model_len=256,
                     prefill_chunk_size=32, prefill_batch_size=2,
                     decode_steps=6, deferred_kv_writes=True)
    scheduler.update(over.pop("scheduler", {}))
    fields = dict(model=model_config(),
                  cache=CacheConfig(page_size=16, num_pages=64),
                  scheduler=SchedulerConfig(**scheduler))
    fields.update(over)
    with pytest.raises(ValueError, match=said) as refused:
        EngineConfig(**fields)
    assert "generates by diffusion over blocks of 4" in str(refused.value)


def test_a_draft_module_and_a_quantized_model_are_refused_too():
    with pytest.raises(ValueError, match="draft_module needs a family"):
        engine_config(draft_module=True)
    with pytest.raises(ValueError, match="weight quantization"):
        engine_config(model_config(quantization="int8"))


def test_the_server_resolves_its_switches_for_the_family():
    import argparse

    from production_stack_tpu.engine.model_runner import (
        deferred_kv_eligible,
    )
    from production_stack_tpu.engine.server import _resolve_unified_step
    assert _resolve_unified_step(argparse.Namespace(
        unified_step="auto", distributed=False), model_config()) is False
    assert deferred_kv_eligible("sdar_moe", 30)
    assert engine_config().cache.cache_layout == "per_layer"
    assert (engine_config().scheduler.block_length,
            engine_config().scheduler.block_steps) == (4, 2)


def test_a_checkpoint_is_refused_and_the_runner_names_no_model(tmp_path):
    import inspect

    from production_stack_tpu.engine import model_runner, scheduler
    from production_stack_tpu.engine.weights import load_weights
    with pytest.raises(NotImplementedError, match="--random-weights"):
        load_weights(str(tmp_path), model_config())
    for module in (model_runner, scheduler):
        assert "sdar" not in inspect.getsource(module).lower()
