"""Multi-host serving tests: 2 jax.distributed CPU processes execute
the same engine steps via the MultihostStepBridge broadcast.

This is the distributed-without-cluster test the reference gets from
envtest/kind (SURVEY.md §4); here the real jax.distributed runtime runs
as local processes, so the broadcast protocol and global-mesh dispatch
are exercised without TPU pods.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


HELPER = os.path.join(os.path.dirname(__file__), "multihost_helper.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_two_process_bridge_generation():
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("XLA_FLAGS", None)  # helper sets its own device count
    procs = [
        subprocess.Popen(
            [sys.executable, HELPER, coordinator, "2", str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env, cwd=REPO,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=420)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    for code, out, err in outs:
        assert code == 0, f"proc failed:\n{out}\n{err}"
    token_line = [ln for ln in outs[0][1].splitlines()
                  if ln.startswith("TOKENS=")]
    assert token_line, outs[0][1]
    tokens = json.loads(token_line[0][len("TOKENS="):])
    assert len(tokens) == 6
    assert "WORKER_DONE" in outs[1][1]

    # The coordinator's greedy output must match a plain single-process
    # run of the same config/seed (the bridge must not perturb numerics).
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config,
    )
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sequence import SamplingParams
    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=128,
                                  prefill_chunk_size=32,
                                  decode_steps=4),
    )
    ref_engine = LLMEngine(config)
    ref = ref_engine.generate(
        list(range(1, 20)),
        SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True),
    )
    assert ref.output_token_ids == tokens

    # The embed bridge leg (KIND_EMBED) must also have run and matched
    # a single-process embed of the same inputs.
    embed_line = [ln for ln in outs[0][1].splitlines()
                  if ln.startswith("EMBED=")]
    assert embed_line, outs[0][1]
    embed_first_dims = json.loads(embed_line[0][len("EMBED="):])
    from production_stack_tpu.engine.embeddings import Embedder
    embedder = Embedder(config.model, ref_engine.runner.params,
                        max_len=config.scheduler.max_model_len)
    ref_vecs = embedder.embed_batch([[1, 2, 3], [4, 5, 6, 7]])
    np.testing.assert_allclose(embed_first_dims, ref_vecs[:, 0],
                               atol=1e-4)


def test_bridge_template_matches_real_payloads():
    """The worker-side payload template must structurally match what
    host 0 actually publishes for every optional-input combination —
    template/payload drift desyncs the broadcast and hangs the slice."""
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config,
    )
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sequence import SamplingParams
    from production_stack_tpu.parallel.distributed import (
        MultihostStepBridge,
    )

    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=128),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64,
                                  decode_steps=4),
    )
    engine = LLMEngine(config)
    bridge = MultihostStepBridge(engine.runner)

    published = []

    def fake_publish(kind, t, payload):
        flags = bridge.payload_flags(kind, payload)
        arrays = {k: v for k, v in payload.items()
                  if k != "want_logprobs"}
        published.append((kind, t, flags, arrays))

    engine.runner.bridge = bridge
    bridge.publish = fake_publish

    engine.generate(list(range(1, 40)), SamplingParams(
        max_tokens=6, temperature=0.7, seed=7,
        presence_penalty=0.5, logprobs=True, top_logprobs=2,
        logit_bias={9: -1.5}, min_tokens=4, guided="json",
    ))

    assert published, "bridge.publish never called"
    for kind, t, flags, arrays in published:
        template = bridge._payload_template(kind, t, flags)
        assert set(template) == set(arrays), (
            f"kind={kind} t={t} flags={flags}: template keys "
            f"{sorted(template)} != payload keys {sorted(arrays)}")
        for k in template:
            assert template[k].shape == np.asarray(arrays[k]).shape, (
                f"{k}: {template[k].shape} != "
                f"{np.asarray(arrays[k]).shape}")
            assert template[k].dtype == np.asarray(arrays[k]).dtype, (
                f"{k}: {template[k].dtype} != "
                f"{np.asarray(arrays[k]).dtype}")
