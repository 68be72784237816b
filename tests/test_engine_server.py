"""HTTP-level tests of the engine server (OpenAI surface + /metrics).

Test model: the reference's fake-openai-server-based e2e rig
(src/tests/perftest + router-e2e-test.yml), but against the REAL engine
with a tiny model — no TPU required.
"""

import asyncio
import json

import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.server import EngineServer


def make_server() -> EngineServer:
    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=128),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64),
    )
    engine = LLMEngine(config)
    return EngineServer(engine, "tiny-llama")


async def _with_client(fn):
    server = make_server()
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    try:
        await fn(client)
    finally:
        await client.close()


def test_models_health_version():
    async def run(client):
        resp = await client.get("/v1/models")
        assert resp.status == 200
        data = await resp.json()
        assert data["data"][0]["id"] == "tiny-llama"
        assert (await client.get("/health")).status == 200
        resp = await client.get("/version")
        assert resp.status == 200
        version = await resp.json()
        # The process that holds the device names it, and says which
        # attention impl each phase resolved to.
        assert version["platform"] == "cpu"
        assert version["device_kind"] and version["num_devices"] >= 1
        assert version["attention_impl"]["decode"] == "xla"
        assert version["attention_impl"]["prefill"] == "xla"
        # Single-step decode writes K/V as it goes.
        assert version["kv_writes"] == "eager"
    asyncio.run(_with_client(run))


def test_chat_completion_non_streaming():
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 8,
            "temperature": 0,
            "ignore_eos": True,
        })
        assert resp.status == 200
        data = await resp.json()
        assert data["object"] == "chat.completion"
        assert data["choices"][0]["finish_reason"] == "length"
        assert data["usage"]["completion_tokens"] == 8
        assert isinstance(
            data["choices"][0]["message"]["content"], str
        )
    asyncio.run(_with_client(run))


def test_chat_completion_streaming():
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 6,
            "temperature": 0,
            "ignore_eos": True,
            "stream": True,
        })
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith(
            "text/event-stream"
        )
        events = []
        async for line in resp.content:
            line = line.decode().strip()
            if line.startswith("data: "):
                events.append(line[len("data: "):])
        assert events[-1] == "[DONE]"
        first = json.loads(events[0])
        assert first["choices"][0]["delta"].get("role") == "assistant"
        finishes = [json.loads(e)["choices"][0]["finish_reason"]
                    for e in events[:-1]]
        assert finishes[-1] == "length"
    asyncio.run(_with_client(run))


def test_completions_endpoint():
    async def run(client):
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama",
            "prompt": "abc",
            "max_tokens": 4,
            "temperature": 0,
            "ignore_eos": True,
        })
        assert resp.status == 200
        data = await resp.json()
        assert data["object"] == "text_completion"
        assert data["usage"]["completion_tokens"] == 4
    asyncio.run(_with_client(run))


def test_metrics_exposition_names():
    async def run(client):
        # Generate some load first.
        await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 2, "temperature": 0, "ignore_eos": True,
        })
        resp = await client.get("/metrics")
        text = await resp.text()
        # The names the router scrapes (engine_stats.py contract).
        for name in (
            "vllm:num_requests_running",
            "vllm:num_requests_waiting",
            "vllm:gpu_cache_usage_perc",
            "vllm:gpu_prefix_cache_hit_rate",
        ):
            assert name in text, f"missing {name}"
    asyncio.run(_with_client(run))


def test_concurrent_requests_batched():
    async def run(client):
        async def one(i):
            resp = await client.post("/v1/chat/completions", json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": f"req {i}"}],
                "max_tokens": 5, "temperature": 0, "ignore_eos": True,
            })
            assert resp.status == 200
            data = await resp.json()
            assert data["usage"]["completion_tokens"] == 5
        await asyncio.gather(*(one(i) for i in range(6)))
    asyncio.run(_with_client(run))


def test_oversized_prompt_rejected_with_400():
    async def run(client):
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama",
            "prompt": list(range(1, 400)),  # > max_model_len=256
            "max_tokens": 4,
        })
        assert resp.status == 400
        data = await resp.json()
        assert "max_model_len" in data["error"]["message"]
    asyncio.run(_with_client(run))


def test_malformed_json_rejected_with_400():
    async def run(client):
        resp = await client.post(
            "/v1/chat/completions", data=b"{nope",
            headers={"content-type": "application/json"},
        )
        assert resp.status == 400
    asyncio.run(_with_client(run))


def test_null_sampling_params_use_openai_defaults():
    from production_stack_tpu.engine.server import _sampling_from_body
    sp = _sampling_from_body(
        {"temperature": None, "top_p": None, "max_tokens": 4}, 256
    )
    assert sp.temperature == 1.0
    assert sp.top_p == 1.0
    sp = _sampling_from_body({"temperature": 0, "max_tokens": 4}, 256)
    assert sp.temperature == 0.0


def test_engine_latency_histograms_after_traffic():
    """/metrics exposes vLLM-parity TTFT/ITL/e2e histograms and token
    counters once requests have completed."""
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 6,
        })
        assert resp.status == 200
        await resp.json()
        text = await (await client.get("/metrics")).text()
        assert 'vllm:time_to_first_token_seconds_count 1' in text
        assert 'vllm:e2e_request_latency_seconds_count 1' in text
        # TTFT decomposition: queue wait vs prefill compute.
        assert 'vllm:request_queue_time_seconds_count 1' in text
        assert 'vllm:request_prefill_time_seconds_count 1' in text
        assert 'vllm:time_per_output_token_seconds_bucket' in text
        assert 'vllm:generation_tokens_total 6' in text
        assert 'vllm:request_success_total{finished_reason="length"} 1' \
            in text
    asyncio.run(_with_client(run))


def test_chat_template_override():
    """--chat-template Jinja source takes priority over the default
    role-tagged rendering (reference chart's chatTemplate knob)."""
    from production_stack_tpu.engine.tokenizer import (
        ByteTokenizer,
        render_chat_prompt,
    )
    tok = ByteTokenizer()
    messages = [{"role": "user", "content": "hi"}]
    tpl = "{% for m in messages %}[{{ m.role }}]{{ m.content }}{% endfor %}>>"
    ids = render_chat_prompt(tok, messages, chat_template=tpl)
    assert tok.decode(ids) == "[user]hi>>"
    # A broken template falls back to the default rendering (loudly).
    bad = render_chat_prompt(tok, messages,
                             chat_template="{{ undefined_fn() }}")
    default = render_chat_prompt(tok, messages, chat_template=None)
    assert bad == default and tok.decode(bad) != ""


def test_bench_tokenizer_full_vocab_decode():
    """BenchTokenizer: every id >= 258 decodes to one printable char —
    a random-weights bench server must stream a non-empty delta per
    generated token (the ByteTokenizer dropped ids >= 256, so the
    round-5 QPS sweep saw zero TTFT signal and gen_tokens == 0)."""
    from production_stack_tpu.engine.tokenizer import (
        BenchTokenizer,
        get_tokenizer,
    )
    tok = get_tokenizer("bench")
    assert isinstance(tok, BenchTokenizer)
    # Byte-range behavior identical to ByteTokenizer.
    assert tok.encode("hi") == [tok.BOS, 104, 105]
    assert tok.decode([104, 105]) == "hi"
    # Specials stay invisible; everything else is one printable char.
    assert tok.decode([tok.BOS, tok.EOS]) == ""
    for tid in (258, 1000, 32127):
        s = tok.decode([tid])
        assert len(s) == 1 and s.isprintable(), (tid, s)
    # Mixed byte-range + high ids interleave in order.
    assert tok.decode([104, 5000, 105]) == (
        "h" + chr(33 + (5000 - 258) % 94) + "i")


def test_n_choices_non_streaming():
    """n > 1 returns n independent choices with summed usage."""
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 6, "temperature": 0.0, "n": 3,
        })
        assert resp.status == 200
        data = await resp.json()
        assert [c["index"] for c in data["choices"]] == [0, 1, 2]
        # Greedy: all choices identical (and thus provably complete).
        texts = {c["message"]["content"] for c in data["choices"]}
        assert len(texts) == 1
        assert data["usage"]["completion_tokens"] == 18
    asyncio.run(_with_client(run))


def test_n_rejected_out_of_range():
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "x"}],
            "n": 0,
        })
        assert resp.status == 400
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "x"}],
            "n": "many",
        })
        assert resp.status == 400
    asyncio.run(_with_client(run))


def test_n_choices_streaming_indexes_chunks():
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 4, "temperature": 0.0, "n": 2,
            "stream": True,
        })
        assert resp.status == 200
        raw = (await resp.read()).decode()
        assert raw.strip().endswith("data: [DONE]")
        finishes = set()
        for line in raw.splitlines():
            if line.startswith("data: {"):
                payload = json.loads(line[len("data: "):])
                choice = payload["choices"][0]
                if choice.get("finish_reason"):
                    finishes.add(choice["index"])
        assert finishes == {0, 1}
    asyncio.run(_with_client(run))


def test_stop_string_truncates_and_aborts():
    """A stop sequence ends generation early and is not returned."""
    async def run(client):
        # Learn the greedy continuation first.
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 12, "temperature": 0.0,
        })
        full = (await resp.json())["choices"][0]["message"]["content"]
        # Use a mid-text fragment as the stop string.
        assert len(full) > 4
        stop = full[2:4]
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 12, "temperature": 0.0, "stop": stop,
        })
        data = await resp.json()
        text = data["choices"][0]["message"]["content"]
        assert stop not in text
        assert text == full[:full.find(stop)]
        assert data["choices"][0]["finish_reason"] == "stop"
    asyncio.run(_with_client(run))


def test_stop_string_streaming_holds_back_partial_match():
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 12, "temperature": 0.0,
        })
        full = (await resp.json())["choices"][0]["message"]["content"]
        stop = full[2:4]
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 12, "temperature": 0.0, "stop": stop,
            "stream": True,
        })
        raw = (await resp.read()).decode()
        text = ""
        for line in raw.splitlines():
            if line.startswith("data: {"):
                payload = json.loads(line[len("data: "):])
                text += payload["choices"][0]["delta"].get(
                    "content", "")
        assert stop not in text
        assert text == full[:full.find(stop)]
    asyncio.run(_with_client(run))


def test_penalties_change_sampling():
    """A strong presence penalty must change greedy output whenever
    the unpenalized continuation repeats a token."""
    async def run(client):
        body = {
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 16, "temperature": 0.0,
        }
        r1 = await (await client.post(
            "/v1/chat/completions", json=body)).json()
        body2 = dict(body, presence_penalty=2.0,
                     frequency_penalty=1.5)
        r2 = await (await client.post(
            "/v1/chat/completions", json=body2)).json()
        assert r2["choices"][0]["finish_reason"] in ("stop", "length")
        # Both runs completed; the penalty request exercised the
        # penalized compiled path end to end (output may or may not
        # differ depending on whether greedy repeats tokens).
        assert r1["usage"]["completion_tokens"] == 16
        assert r2["usage"]["completion_tokens"] >= 1
    asyncio.run(_with_client(run))


def test_chat_logprobs():
    """logprobs + top_logprobs return per-token entries whose sampled
    logprob appears among the tops for greedy decoding."""
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 5, "temperature": 0.0,
            "logprobs": True, "top_logprobs": 3,
        })
        assert resp.status == 200
        data = await resp.json()
        content = data["choices"][0]["logprobs"]["content"]
        assert len(content) == 5
        for entry in content:
            assert entry["logprob"] <= 0.0
            assert len(entry["top_logprobs"]) == 3
            # Greedy: the sampled token IS the top-1 alternative.
            assert entry["top_logprobs"][0]["token"] == entry["token"]
            assert (abs(entry["top_logprobs"][0]["logprob"]
                        - entry["logprob"]) < 1e-4)


    asyncio.run(_with_client(run))


def test_completions_legacy_logprobs():
    async def run(client):
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "hello world",
            "max_tokens": 4, "temperature": 0.0, "logprobs": 2,
        })
        assert resp.status == 200
        lp = (await resp.json())["choices"][0]["logprobs"]
        assert len(lp["tokens"]) == 4
        assert len(lp["token_logprobs"]) == 4
        # Text-keyed dicts may collapse ids that decode identically
        # (byte-fallback chars in the tiny vocab).
        assert all(1 <= len(t) <= 2 for t in lp["top_logprobs"])
    asyncio.run(_with_client(run))


def test_logprobs_streaming_chunks():
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 4, "temperature": 0.0,
            "logprobs": True, "top_logprobs": 2, "stream": True,
        })
        raw = (await resp.read()).decode()
        entries = []
        for line in raw.splitlines():
            if line.startswith("data: {"):
                payload = json.loads(line[len("data: "):])
                lp = payload["choices"][0].get("logprobs")
                if lp:
                    entries.extend(lp["content"])
        assert len(entries) == 4
    asyncio.run(_with_client(run))


def test_stop_string_drops_truncated_logprob_entries():
    """logprobs.content must align with the truncated text when a stop
    string hits: entries for held-back/truncated tokens are dropped."""
    async def run(client):
        base = {
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 10, "temperature": 0.0,
            "logprobs": True, "top_logprobs": 1,
        }
        full = await (await client.post(
            "/v1/chat/completions", json=base)).json()
        full_text = full["choices"][0]["message"]["content"]
        full_entries = full["choices"][0]["logprobs"]["content"]
        assert len(full_entries) == 10
        stop = full_text[3:6]
        resp = await (await client.post(
            "/v1/chat/completions",
            json=dict(base, stop=stop))).json()
        text = resp["choices"][0]["message"]["content"]
        entries = resp["choices"][0]["logprobs"]["content"]
        assert stop not in text
        # Released entries' token texts reassemble exactly the
        # returned (truncated) text — no phantom trailing entries.
        assert "".join(e["token"] for e in entries) == text
    asyncio.run(_with_client(run))


def test_top_logprobs_without_logprobs_rejected():
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "x"}],
            "logprobs": False, "top_logprobs": 2,
        })
        assert resp.status == 400
    asyncio.run(_with_client(run))


def test_best_of_returns_top_n():
    """best_of generates extra candidates and returns the n best by
    mean token logprob, without leaking internal logprobs."""
    async def run(client):
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "hello world",
            "max_tokens": 6, "temperature": 0.9, "seed": 11,
            "n": 2, "best_of": 4,
        })
        assert resp.status == 200
        data = await resp.json()
        assert [c["index"] for c in data["choices"]] == [0, 1]
        assert all(c["logprobs"] is None for c in data["choices"])
        # All 4 candidates' tokens count toward usage.
        assert data["usage"]["completion_tokens"] == 24

        # Legacy integer logprobs:0 ("sampled logprob, no
        # alternatives") must survive best_of's internal forcing.
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "hello world",
            "max_tokens": 4, "temperature": 0.9, "seed": 3,
            "n": 1, "best_of": 2, "logprobs": 0,
        })
        data = await resp.json()
        lp = data["choices"][0]["logprobs"]
        assert lp is not None and len(lp["token_logprobs"]) == 4

        # Streaming with best_of > n is rejected.
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "x", "n": 1,
            "best_of": 2, "stream": True,
        })
        assert resp.status == 400
        # best_of < n is rejected.
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "x", "n": 3,
            "best_of": 2,
        })
        assert resp.status == 400
    asyncio.run(_with_client(run))


def test_completions_echo_and_suffix():
    async def run(client):
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "hello world",
            "max_tokens": 3, "temperature": 0.0, "echo": True,
        })
        data = await resp.json()
        text = data["choices"][0]["text"]
        prompt_text = "hello world"
        # Echo prepends the (detokenized) prompt; round-tripping the
        # tiny tokenizer reproduces the input string exactly.
        assert text.startswith(prompt_text)
        assert len(text) > len(prompt_text)

        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "x", "suffix": "tail",
        })
        assert resp.status == 400
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "x", "echo": True,
            "logprobs": 1,
        })
        assert resp.status == 400
    asyncio.run(_with_client(run))


def test_completions_echo_streaming():
    async def run(client):
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "hello world",
            "max_tokens": 3, "temperature": 0.0, "echo": True,
            "stream": True, "n": 2,
        })
        assert resp.status == 200
        raw = (await resp.read()).decode()
        texts = {0: "", 1: ""}
        for line in raw.splitlines():
            if line.startswith("data: {"):
                payload = json.loads(line[len("data: "):])
                c = payload["choices"][0]
                texts[c["index"]] += c.get("text", "")
        assert texts[0].startswith("hello world")
        assert texts[1].startswith("hello world")
        assert len(texts[0]) > len("hello world")
    asyncio.run(_with_client(run))


def test_a_bursts_frames_share_one_write():
    """A decode burst hands a row's tokens over together; the stream
    keeps one frame a token and puts the frames of one hand-over on
    the wire in one write (a write a frame was over half of the event
    loop's cost a token: PERF.md, PR 38)."""
    from aiohttp import web

    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=128),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64, decode_steps=8),
    )
    from production_stack_tpu.engine.tokenizer import BenchTokenizer
    # An id from 258 up decodes to one visible character.
    server = EngineServer(
        LLMEngine(config, tokenizer=BenchTokenizer(
            config.model.vocab_size)), "tiny-llama")
    writes = []
    real_write = web.StreamResponse.write

    async def counting_write(self, data):
        writes.append(bytes(data))
        return await real_write(self, data)

    async def run():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            web.StreamResponse.write = counting_write
            resp = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "abc", "stream": True,
                "max_tokens": 33, "temperature": 0, "ignore_eos": True,
            })
            body = (await resp.read()).decode()
        finally:
            web.StreamResponse.write = real_write
            await client.close()
        frames = [line for line in body.splitlines()
                  if line.startswith("data: ")]
        assert frames[-1] == "data: [DONE]"
        chunks = [json.loads(f[len("data: "):]) for f in frames[:-1]]
        assert chunks[-1]["choices"][0]["finish_reason"] == "length"
        # One frame a token that has text of its own (an id under 258
        # is a byte the detokenizer may hold back), then the finish.
        assert 16 <= len(chunks) <= 34
        # 33 tokens are the prefill's and four bursts of eight: their
        # frames take a write a hand-over, not a write a frame.
        with_frames = [w for w in writes if w.startswith(b"data: {")]
        assert b"".join(writes).decode() == body
        assert len(with_frames) <= 7 < len(chunks)
        assert max(w.count(b"data: {") for w in with_frames) >= 4

    asyncio.run(run())


def test_stream_options_include_usage():
    """OpenAI stream_options.include_usage: a final pre-[DONE] chunk
    with empty choices and aggregate usage."""
    async def run(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama", "stream": True,
            "stream_options": {"include_usage": True},
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4, "ignore_eos": True,
        })
        assert resp.status == 200
        chunks = []
        async for line in resp.content:
            line = line.decode().strip()
            if line.startswith("data: ") and line != "data: [DONE]":
                chunks.append(json.loads(line[len("data: "):]))
        usage_chunks = [c for c in chunks if c.get("usage")]
        assert len(usage_chunks) == 1
        assert usage_chunks[0]["choices"] == []
        u = usage_chunks[0]["usage"]
        assert u["completion_tokens"] == 4
        assert u["total_tokens"] == u["prompt_tokens"] + 4
        # Usage chunk is the LAST data chunk before [DONE].
        assert chunks[-1].get("usage")
    asyncio.run(_with_client(run))
