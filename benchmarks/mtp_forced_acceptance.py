"""The accepted path of a drafting burst, on a sequence whose every
draft is the token that follows: what a greedy row with random weights
reaches once in a vocabulary's worth of drafts.

    python3 benchmarks/mtp_forced_acceptance.py <config.json> \
        <sequences.json> <out.json> [--pallas] [--jit]

``served_log_probs`` calls the family's ``forward`` and ``draft`` as the
runner does (engine/model_runner.py ``_step_impl`` and
``_decode_burst_draft_impl``): the prompt in chunks through the latent
pages, each chunk also filling the prediction module's cache entry with
the next ids; then bursts of verify iterations, each running two
positions a row (the last committed token and, as its draft, the
sequence's next token) against the pages and the burst's tails, the
module on both committed positions, and the tails flushed to the pages
by count at each burst's end. It returns the main model's
log-probabilities after every position and the module's for the token
two after every position; tests/test_glm4_moe_lite.py compares them
with the plain reference at a tiny size, and ``main`` does at a
configuration's published widths on whatever device JAX holds (chip
run (4) of ISSUE 43): the float32 reference runs on the host's CPU
beside it.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def served_log_probs(config, params, tokens, prompt_len: int, chunk: int,
                     page_size: int = 16, burst: int = 3,
                     fill_module: bool = True, module_entry=None,
                     module_tokens_shift: int = 1, jit: bool = False):
    """Row 1 of two (row 0 is padding). ``tokens`` is the whole
    sequence; the first ``prompt_len`` are the prompt, the rest are
    committed two an iteration (the second as an accepted draft),
    ``burst`` iterations a burst, while two more remain. Returns
    ``(main, module, positions)``: ``main[i]`` the log-softmax after
    position ``i`` and ``module[i]`` the module's for token ``i + 2``,
    for ``i < positions``. The levers are the tests' wrong programs:
    ``fill_module`` False leaves the module's cache unfilled over the
    prompt, ``module_entry`` serves another entry's planes and tails to
    it, ``module_tokens_shift`` 0 feeds it ``t_i`` for ``t_{i+1}``.
    ``jit`` compiles each call of ``forward`` and ``draft`` (as the
    runner's programs are compiled) where the default runs them
    operation by operation."""
    from production_stack_tpu.models.registry import (
        get_draft,
        get_model,
        init_hybrid_cache,
    )
    from production_stack_tpu.ops.attention import write_to_pages

    c = config
    _, forward = get_model(c)
    draft = get_draft(c)
    if jit:
        forward, draft = _jitted(forward, draft)
    tokens = np.asarray(tokens, np.int32)
    total = len(tokens)
    pages_a_row = -(-total // page_size) + 1
    k_cache, v_cache = init_hybrid_cache(c, 1 + 2 * pages_a_row,
                                         page_size, 0)
    table = jnp.asarray(
        [[0] * pages_a_row,
         list(range(1, 1 + pages_a_row))], jnp.int32)
    entry = c.num_hidden_layers
    served = entry if module_entry is None else module_entry
    main_rows, module_rows = [], []

    def to_module(k):
        # What the module reads and writes as its own entry.
        if served == entry:
            return k
        return k[:entry] + (k[served],) + k[entry + 1:]

    def after(nxt, ids):
        # The module's input token at each position.
        return jnp.asarray(nxt if module_tokens_shift else ids)

    start = 0
    while start < prompt_len:
        n = min(chunk, prompt_len - start)
        ids = np.zeros((2, chunk), np.int32)
        ids[1, :n] = tokens[start:start + n]
        nxt = np.zeros((2, chunk), np.int32)
        nxt[1, :n] = tokens[start + 1:start + n + 1]
        pos = np.zeros((2, chunk), np.int32)
        pos[1, :n] = np.arange(start, start + n)
        valid = np.zeros((2, chunk), bool)
        valid[1, :n] = True
        kv = jnp.asarray([0, start + n], jnp.int32)
        logits, hidden, k_cache, v_cache = forward(
            params, c, jnp.asarray(ids), jnp.asarray(pos), table, kv,
            jnp.asarray(valid), k_cache, v_cache, return_hidden=True)
        main_rows.append(np.asarray(jax.nn.log_softmax(logits[1, :n])))
        if fill_module:
            q, k_new = draft(params, c, hidden, after(nxt, ids),
                             jnp.asarray(pos), table, kv,
                             jnp.asarray(valid), to_module(k_cache),
                             head_index="all")
            module_rows.append(np.asarray(jax.nn.log_softmax(q[1, :n])))
            k_cache = (k_cache[:entry] + (k_new[entry],)
                       + k_cache[entry + 1:]) if served == entry else k_cache
        else:
            module_rows.append(np.zeros((n, c.vocab_size), np.float32))
        start += n

    pages = c.page_cache
    at = prompt_len  # position of the last committed token
    while total - at >= 3:
        steps = min(burst, (total - at - 1) // 2)
        kv0 = jnp.asarray([0, at], jnp.int32)
        tail = lambda: jnp.zeros(  # noqa: E731
            (2, 2 * steps, pages.heads, pages.width), c.jax_dtype)
        k_tails = tuple(tail() for _ in range(entry + 1)) + (k_cache[-1],)
        act = jnp.asarray([[False, False], [True, True]])
        for _ in range(steps):
            ids = jnp.asarray([[0, 0], [tokens[at], tokens[at + 1]]],
                              jnp.int32)
            nxt = jnp.asarray([[0, 0], [tokens[at + 1], tokens[at + 2]]],
                              jnp.int32)
            pos = jnp.asarray([[0, 1], [at, at + 1]], jnp.int32)
            logits, hidden, k_tails, _ = forward(
                params, c, ids, pos, table, kv0, act,
                k_cache[:-1] + (k_tails[-1],), v_cache,
                kv_tail=(k_tails, v_cache), return_hidden=True)
            main_rows.append(np.asarray(jax.nn.log_softmax(logits[1])))
            q, k_new = draft(
                params, c, hidden, after(nxt, ids), pos, table, kv0, act,
                to_module(k_cache[:-1] + (k_tails[-1],)),
                kv_tail=(to_module(k_tails), v_cache), head_index="all")
            module_rows.append(np.asarray(jax.nn.log_softmax(q[1])))
            if served == entry:
                k_tails = (k_tails[:entry] + (k_new[entry],)
                           + (k_new[-1],))
            at += 2
        count = jnp.asarray([0, 2 * steps], jnp.int32)
        tail_pos = kv0[:, None] + jnp.arange(2 * steps)[None, :]
        tail_valid = jnp.arange(2 * steps)[None, :] < count[:, None]
        k_cache = tuple(
            write_to_pages(plane, t, table, tail_pos, tail_valid)
            for plane, t in zip(k_cache[:-1], k_tails[:-1])
        ) + (k_tails[-1],)
    return (np.concatenate(main_rows), np.concatenate(module_rows), at)


def _jitted(forward, draft):
    """``forward`` and ``draft`` with the same signatures, each call
    compiled: the configuration is closed over, ``return_hidden`` and
    ``head_index`` are as ``served_log_probs`` always gives them."""
    def compiled(fn, **fixed):
        cache = {}

        def call(params, config, *args, kv_tail=None, **ignored):
            if id(config) not in cache:
                cache[id(config)] = jax.jit(
                    lambda params, args, kv_tail: fn(
                        params, config, *args, kv_tail=kv_tail, **fixed))
            return cache[id(config)](params, args, kv_tail)
        return call
    return (compiled(forward, return_hidden=True),
            compiled(draft, head_index="all"))


def main(argv) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench import family
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models.registry import get_model

    with open(argv[1]) as f:
        config = json.load(f)
    with open(argv[2]) as f:
        sequences = json.load(f)
    bench = config.pop("chipbench")
    reference = family.module("reference", {"chipbench": bench})
    model = ModelConfig.from_hf_config(config)
    model.dtype = bench["dtype"]
    model.attention_impl = "pallas" if "--pallas" in argv else "xla"
    init, _ = get_model(model)
    params = init(model, jax.random.PRNGKey(bench["weights_seed"]))
    cpu = jax.devices("cpu")[0]
    ref_model = reference.model_of(
        model, {k: jax.device_put(v, cpu) for k, v in params.items()})
    report = []
    for seq in sequences:
        tokens = seq["prompt_ids"] + seq["answer_ids"]
        prompt = len(seq["prompt_ids"])
        got_main, got_module, n = served_log_probs(
            model, params, tokens, prompt,
            bench["server_flags"]["prefill-chunk-size"],
            page_size=bench["server_flags"]["page-size"], burst=4,
            jit="--jit" in argv)
        with jax.default_device(cpu):
            decode = list(range(prompt, n))
            want_main = np.asarray(reference.log_probs(
                ref_model, tokens, decode))
            want_module = np.asarray(reference.draft_log_probs(
                ref_model, tokens, decode))
        top = np.argsort(-want_main, -1)[:, :6]
        top_q = np.argsort(-want_module, -1)[:, :6]
        take = np.take_along_axis
        d_main = np.abs(take(got_main[prompt:n], top, -1)
                        - take(want_main, top, -1))
        d_module = np.abs(take(got_module[prompt:n], top_q, -1)
                          - take(want_module, top_q, -1))
        greedy = got_main[prompt:n].argmax(-1)
        report.append({
            "prompt_tokens": prompt, "positions": n - prompt,
            "main_worst": float(d_main.max()),
            "main_mean": float(d_main.mean()),
            "module_worst": float(d_module.max()),
            "module_mean": float(d_module.mean()),
            # An iteration's first position (the committed token) and
            # its second (the accepted draft), apart.
            "main_mean_first": float(d_main[0::2].mean()),
            "main_mean_second": float(d_main[1::2].mean()),
            # Is each next token the program's own argmax at the
            # position before it (a greedy row accepts it as a draft),
            # and the reference's?
            "program_accepts": [
                bool(greedy[i] == tokens[prompt + i + 1])
                for i in range(n - prompt - 1)],
            "reference_agrees": [
                bool(want_main[i].argmax() == tokens[prompt + i + 1])
                for i in range(n - prompt - 1)]})
        print(json.dumps(report[-1]), flush=True)
    with open(argv[3], "w") as f:
        json.dump(report, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
