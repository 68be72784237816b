"""Chip smoke: does the serving stack start and answer on the accelerator?

    python3 chip_smoke.py            # on a machine with one TPU chip

Drives the system's main path once — router -> engine server ->
scheduler -> paged cache -> step programs -> attention kernels — at the
full width of one model the server builds without a checkpoint
(bench-1b: hidden 2048, 16 layers, random weights from the server's
seed), checks what comes out, and prints as the last line of stdout

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the engine's jax reports it, and on the line before
it one JSON object of findings (impls served, kernel verdicts, compile
seconds, tokens, wall seconds; also chiprun_out/chip_smoke/report.json).
Any failed check, a missing accelerator or a timeout exits non-zero and
prints neither line; the tail of the engine and router logs goes to
stderr.

Phase A (one child process, exits before phase B): each Pallas
attention kernel is compiled by the real backend (Mosaic on a TPU) at
the smoke config's serving shapes and compared with the XLA reference
(ops/attention.paged_attention) on seeded inputs, model-dtype and int8
KV pages.

Phase B: ``python -m production_stack_tpu.engine.server`` (every
selector at ``auto``) behind ``python -m production_stack_tpu.router.app``;
through the router one non-streaming and one streaming chat completion,
then concurrent ~512-token prompts, all at once, so a batched prefill
chunk, a short prefill bucket, the decode burst and the unified ragged
step all compile and run. Then the engine is asked what it is
(``GET /version``, ``/metrics``, ``/debug/compiles``) and the answers
are checked against what ``auto`` is documented to serve.

This process never imports jax: a chip belongs to one process at a
time, so all device work happens in the children, one at a time.
``--expect-platform cpu --model tiny-llama`` drives the same code on a
CPU (tests/test_chip_smoke.py); it is an expectation that fails on a
mismatch, not a fallback. This is not a benchmark: it reports no rate.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# The contract allows 1200 s, compilation included; every wait below is
# cut off by this deadline as well as by its own limit, and stopping the
# children afterwards is bounded by 2 x 60 s each.
TOTAL_BUDGET_S = 1000.0

# Server shapes per model. bench-1b's are the one full-width server
# invocation the repo records (32-wide decode, 8-row 512-token prefill
# chunks, 32-step bursts, page 128 = one lane tile); tiny-llama's keep
# the same structure small enough for a CPU test.
SMOKE_CONFIGS = {
    "bench-1b": dict(page_size=128, num_pages=768, max_num_seqs=32,
                     max_model_len=2048, prefill_chunk_size=512,
                     prefill_batch_size=8, decode_steps=32,
                     prompt_chars=500, out_tokens=64, concurrent=16),
    "tiny-llama": dict(page_size=16, num_pages=256, max_num_seqs=8,
                       max_model_len=256, prefill_chunk_size=64,
                       prefill_batch_size=4, decode_steps=4,
                       prompt_chars=90, out_tokens=12, concurrent=8),
}

# What ``--attention-impl auto`` is documented to serve (README
# "Attention kernels"): on a TPU the Pallas decode and prefill kernels
# and a Pallas impl for the unified step; XLA everywhere on a CPU, and
# under tensor parallelism (GSPMD cannot partition a Mosaic call).
XLA_EVERYWHERE = {"decode": ("xla",), "prefill": ("xla",),
                  "unified": ("xla",)}
EXPECTED_IMPLS = {
    "tpu": {"decode": ("pallas",), "prefill": ("pallas",),
            "unified": ("pallas", "pallas_ragged")},
    "cpu": XLA_EVERYWHERE,
}

BAD_LOG_LINES = ("Engine step failed", "failed TPU lowering",
                 "failed its lowering probe")


class SmokeFailure(Exception):
    pass


def _remaining(deadline: float, limit: float) -> float:
    left = min(limit, deadline - time.time())
    if left <= 0:
        raise SmokeFailure("out of time (total budget "
                           f"{TOTAL_BUDGET_S:.0f}s)")
    return left


# ---- phase A child: kernels on the real backend ---------------------------


def _kernel_cases(model, cfg, rng):
    """Seeded serving-shape inputs for the three kernels.

    Returns ``(make_cache, cases)``; each case is ``(name, kernel_fn,
    kernel_args, ref_args, mask)``: ``ref_args`` are the ``(q, page_table,
    q_positions, kv_lens)`` the XLA reference takes for the same
    problem, ``mask`` the outputs to compare (None = all)."""
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
    )
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )

    nh, nkv, d = (model.num_attention_heads, model.num_key_value_heads,
                  model.head_dim)
    dtype = model.jax_dtype
    page, npages = cfg["page_size"], cfg["num_pages"]
    max_pages = -(-cfg["max_model_len"] // page)
    b_dec, b_pre = cfg["max_num_seqs"], cfg["prefill_batch_size"]
    t = cfg["prefill_chunk_size"]
    max_len = max_pages * page

    def cache():
        return jnp.asarray(
            rng.randn(nkv, npages, d, page), jnp.float32).astype(dtype)

    def table(kv_lens):
        pt = np.zeros((len(kv_lens), max_pages), np.int32)
        nxt = 1  # page 0 is the trash page
        for i, n in enumerate(kv_lens):
            for j in range(-(-int(n) // page)):
                pt[i, j] = nxt
                nxt += 1
        assert nxt <= npages, "smoke config has too few pages"
        return jnp.asarray(pt)

    def queries(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32).astype(dtype)

    cases = []

    # decode [B]: one query per running sequence.
    kv = np.asarray(rng.randint(1, max_len, size=b_dec), np.int32)
    kv[0], kv[-1] = 1, max_len - 1
    q, pt, kvj = queries(b_dec, nh, d), table(kv), jnp.asarray(kv)
    cases.append(("decode", paged_decode_attention, (q, pt, kvj),
                  (q[:, None], pt, (kvj - 1)[:, None], kvj), None))

    # prefill [B, T]: full chunks at assorted chunk starts.
    prior = np.asarray(rng.randint(0, (max_len - t) // page + 1,
                                   size=b_pre) * page, np.int32)
    prior[0] = 0
    kv = prior + t
    pos = jnp.asarray(prior[:, None] + np.arange(t, dtype=np.int32))
    q, pt, kvj = queries(b_pre, t, nh, d), table(kv), jnp.asarray(kv)
    cases.append(("prefill", paged_prefill_attention, (q, pt, pos, kvj),
                  (q, pt, pos, kvj), None))

    # ragged [R, W] at the widest bucket: decode rows, full and short
    # prefill-chunk rows, pad rows — the unified step's mixed batch.
    r = b_dec + b_pre
    kv = np.zeros(r, np.int32)
    last = np.zeros(r, np.int32)
    n_dec = b_dec - 4  # leave four pad rows (kv_len 0)
    kv[:n_dec] = rng.randint(1, max_len, size=n_dec)
    chunk = rng.randint(1, t + 1, size=b_pre)
    chunk[0], chunk[1] = t, 1
    before = rng.randint(0, max_len - t, size=b_pre)
    before[0] = 0
    kv[n_dec:n_dec + b_pre] = before + chunk
    last[n_dec:n_dec + b_pre] = chunk - 1
    pos = np.maximum((kv - 1 - last)[:, None]
                     + np.arange(t, dtype=np.int32), 0).astype(np.int32)
    live = ((np.arange(t)[None] <= last[:, None])
            & (kv[:, None] > 0))  # [R, W] slots the sampler reads
    q, pt, kvj = queries(r, t, nh, d), table(kv), jnp.asarray(kv)
    cases.append(("ragged", paged_ragged_attention,
                  (q, pt, kvj, jnp.asarray(last)),
                  (q, pt, jnp.asarray(pos), kvj), live))
    return cache, cases


def run_kernels_child(args) -> int:
    """Compile + run + compare every kernel; prints one JSON line."""
    import importlib.metadata

    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.engine.config import (
        bench_1b_model_config,
        tiny_model_config,
    )
    from production_stack_tpu.ops.attention import paged_attention
    from production_stack_tpu.ops.quant_kv import QuantKV, quantize_kv
    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != args.expect_platform:
        print(f"[chip_smoke] jax.devices() is {platform!r}, expected "
              f"{args.expect_platform!r}", file=sys.stderr)
        return 1
    # Mosaic wherever there is a chip (checked below: the executable
    # must hold a Mosaic custom call); the Pallas interpreter is only
    # how the same code is exercised where no Mosaic exists (CPU).
    interpret = platform == "cpu"

    model = (bench_1b_model_config() if args.model == "bench-1b"
             else tiny_model_config("llama"))
    cfg = SMOKE_CONFIGS[args.model]
    rng = np.random.RandomState(0)
    make_cache, cases = _kernel_cases(model, cfg, rng)
    k_full, v_full = make_cache(), make_cache()

    def quantized(cache):
        q8, scale = quantize_kv(jnp.transpose(cache, (0, 1, 3, 2)))
        return QuantKV(jnp.transpose(q8, (0, 1, 3, 2)), scale)

    reference = jax.jit(paged_attention)

    def reference_in_row_slices(q, pt, pos, kv, kc, vc, rows=8):
        # The XLA reference materializes [rows, kv, g, T, pages, page]
        # f32 scores; slicing rows keeps that under ~1 GB at [40, 512].
        return np.concatenate([
            np.asarray(reference(q[i:i + rows], kc, vc, pt[i:i + rows],
                                 pos[i:i + rows], kv[i:i + rows]),
                       np.float32)
            for i in range(0, q.shape[0], rows)])

    caches = {str(jnp.dtype(model.jax_dtype)): (k_full, v_full),
              "int8": (quantized(k_full), quantized(v_full))}
    results = []
    ok = True
    for kv_name, (kc, vc) in caches.items():
        for name, fn, (q, *rest), ref_args, mask in cases:
            t0 = time.time()
            try:
                compiled = fn.lower(q, kc, vc, *rest,
                                    interpret=interpret).compile()
                compile_s = time.time() - t0
                if not interpret and (
                        "tpu_custom_call" not in compiled.as_text()):
                    raise RuntimeError("no Mosaic custom call in the "
                                       "compiled executable")
                t0 = time.time()
                out = jax.block_until_ready(compiled(q, kc, vc, *rest))
                run_s = time.time() - t0
            except Exception as e:  # noqa: BLE001 — report every
                # kernel's verdict, not only the first refusal.
                ok = False
                results.append({"kernel": name, "kv": kv_name,
                                "ok": False, "error": repr(e)[:1500]})
                print(f"[chip_smoke] kernel {name} kv={kv_name} "
                      f"REFUSED: {repr(e)[:1500]}",
                      file=sys.stderr, flush=True)
                continue
            ref = reference_in_row_slices(*ref_args, kc, vc)
            out = np.asarray(out, np.float32).reshape(ref.shape)
            diff = np.abs(out - ref)
            err = float((diff if mask is None else diff[mask]).max())
            # Inputs are N(0,1) and outputs are convex combinations of
            # v rows, so |out| <= ~4; 0.05 absolute is a few bf16 ulps
            # of that and far below what a wrong mask or page produces.
            case_ok = bool(np.isfinite(out).all()) and err < 0.05
            ok = ok and case_ok
            results.append({
                "kernel": name, "kv": kv_name, "ok": case_ok,
                "compile_s": round(compile_s, 2),
                "first_run_s": round(run_s, 3),
                "max_abs_err": round(err, 5)})
            print(f"[chip_smoke] kernel {name} kv={kv_name} "
                  f"compile={compile_s:.1f}s err={err:.5f} "
                  f"{'ok' if case_ok else 'MISMATCH'}",
                  file=sys.stderr, flush=True)

    def pkg_version(name):
        try:
            return importlib.metadata.version(name)
        except importlib.metadata.PackageNotFoundError:
            return None

    print(json.dumps({
        "ok": ok,
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "num_devices": len(devices),
        "interpret": interpret,
        "jax": jax.__version__,
        "jaxlib": pkg_version("jaxlib"),
        "libtpu": pkg_version("libtpu"),
        "compile_cache_dir": cache_dir,
        "kernels": results,
    }))
    return 0 if ok else 1


# ---- parent: processes ----------------------------------------------------


class Procs:
    """Children started by the smoke; every one is stopped on exit."""

    def __init__(self):
        self._procs = []

    def start(self, name, cmd):
        os.makedirs(LOG_DIR, exist_ok=True)
        log_path = os.path.join(LOG_DIR, f"{name}.log")
        log = open(log_path, "wb")
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        log.close()
        self._procs.append((name, proc, log_path))
        return proc, log_path

    def stop_all(self) -> bool:
        """SIGTERM, then SIGKILL, newest first (the router's open
        connections would hold the engine's graceful shutdown). The
        waits are long because a TPU runtime can take a minute to let
        go of its chips; returns False if a child outlived them."""
        stopped = True
        for name, proc, _ in reversed(self._procs):
            for sig, wait_s in ((signal.SIGTERM, 60), (signal.SIGKILL, 60)):
                if proc.poll() is not None:
                    break
                try:
                    os.killpg(proc.pid, sig)
                except ProcessLookupError:
                    pass
                try:
                    proc.wait(timeout=wait_s)
                except subprocess.TimeoutExpired:
                    pass
            if proc.poll() is None:
                print(f"[chip_smoke] {name} (pid {proc.pid}) survived "
                      "SIGKILL", file=sys.stderr)
                stopped = False
        return stopped

    def dump_tails(self, nbytes=6000):
        for name, _, log_path in self._procs:
            with open(log_path, "rb") as f:
                tail = f.read()[-nbytes:].decode("utf-8", "replace")
            print(f"---- tail of {name} log ({log_path}) ----\n{tail}",
                  file=sys.stderr)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout: float) -> str:
    """Body of a 2xx reply (urlopen raises on anything else)."""
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def _wait_http_ok(url, proc, name, deadline, limit):
    end = time.time() + _remaining(deadline, limit)
    while time.time() < end:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"{name} exited with code {proc.returncode} before "
                f"answering {url}")
        try:
            _get(url, timeout=5)
            return
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.5)
    raise SmokeFailure(f"{name} did not answer {url} within {limit:.0f}s")


# ---- parent: requests -----------------------------------------------------


def _chat(base, model, content, max_tokens, stream, timeout):
    """One greedy chat completion. Returns (text, completion_tokens,
    saw_done); raises SmokeFailure on a non-200 or a malformed reply."""
    body = {"model": model, "max_tokens": max_tokens,
            "temperature": 0.0, "ignore_eos": True,
            "messages": [{"role": "user", "content": content}]}
    if stream:
        body["stream"] = True
        body["stream_options"] = {"include_usage": True}
    req = urllib.request.Request(
        base + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"content-type": "application/json"})
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
    except urllib.error.HTTPError as e:
        raise SmokeFailure(f"HTTP {e.code}: {e.read()[:300]!r}")
    with resp:
        if resp.status != 200:
            raise SmokeFailure(f"HTTP {resp.status}")
        if not stream:
            reply = json.loads(resp.read())
            choice = reply["choices"][0]
            if choice.get("finish_reason") != "length":
                raise SmokeFailure(f"finish_reason {choice!r}")
            return (choice["message"]["content"],
                    reply["usage"]["completion_tokens"], False)
        pieces, tokens, done, finish = [], None, False, None
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                done = True
                break
            event = json.loads(data)
            if event.get("usage"):
                tokens = event["usage"]["completion_tokens"]
            for choice in event.get("choices", []):
                finish = choice.get("finish_reason") or finish
                delta = choice.get("delta", {}).get("content")
                if delta:
                    pieces.append(delta)
        if finish != "length":
            raise SmokeFailure(f"stream finish_reason {finish!r}")
        return "".join(pieces), tokens, done


def _prompt(i: int, chars: int) -> str:
    # Byte-level tokenizer: one token per character. A shared opening
    # (a prefix-cache hit for later arrivals) then text unique to i.
    shared = "You are a careful assistant. Answer briefly. " * 3
    words = " ".join(f"w{i}x{j}" for j in range(chars))
    return (shared + words)[:chars]


def drive_requests(base, model, cfg, deadline):
    """The request script; returns (requests_served, tokens_requested)."""
    n_out = cfg["out_tokens"]
    served = asked = 0

    # 1-2. One short greedy prompt twice, alone on the engine, once
    # non-streaming and once streaming: same programs, same inputs, so
    # the text must be identical (and the stream must end in [DONE]).
    text_a, tok_a, _ = _chat(base, model, "Say hi.", n_out, False,
                             _remaining(deadline, 420))
    text_b, tok_b, done = _chat(base, model, "Say hi.", n_out, True,
                                _remaining(deadline, 420))
    if tok_a != n_out or tok_b != n_out:
        raise SmokeFailure(f"asked {n_out} tokens, got {tok_a} "
                           f"(non-stream) / {tok_b} (stream)")
    if not done:
        raise SmokeFailure("stream ended without data: [DONE]")
    if not text_a or text_a != text_b:
        raise SmokeFailure("the same greedy prompt gave two texts: "
                           f"{text_a!r} vs {text_b!r}")
    served, asked = served + 2, asked + 2 * n_out

    # 3. Concurrent long prompts, all at once and twice as many as one
    # prefill step takes rows: the first rows to finish their prompt
    # decode while the others still wait for theirs (-> the unified
    # ragged step), the prefill steps are batched (-> the batched
    # step) and the rows decode on together (-> the burst). No wave
    # waits for a token of another: the engine serves a tiny model's
    # whole answer before a loaded client has read its first token.
    n = cfg["concurrent"]
    limit = _remaining(deadline, 600)

    def one(i):
        return _chat(base, model, _prompt(i, cfg["prompt_chars"]), n_out,
                     True, limit)

    with concurrent.futures.ThreadPoolExecutor(max_workers=n) as pool:
        futures = [pool.submit(one, i) for i in range(n)]
        for i, fut in enumerate(futures):
            text, tokens, done = fut.result(timeout=limit + 30)
            if tokens != n_out or not done or not text:
                raise SmokeFailure(
                    f"concurrent request {i}: tokens={tokens} "
                    f"done={done} text={text[:40]!r}")
    return served + n, asked + n * n_out


# ---- parent: reading the engine -------------------------------------------


def _metric(text: str, name: str, **labels) -> float:
    """Value of one Prometheus sample; 0.0 when absent."""
    want = {f'{k}="{v}"' for k, v in labels.items()}
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(name):
            continue
        head, _, value = line.rpartition(" ")
        if head != name and not head.startswith(name + "{"):
            continue
        if all(w in head for w in want):
            return float(value)
    return 0.0


def read_engine(engine_url, expect_platform, expected_impls,
                asked_tokens, served, engine_log, deadline):
    version = json.loads(_get(engine_url + "/version",
                              _remaining(deadline, 30)))
    if version.get("platform") != expect_platform:
        raise SmokeFailure(f"engine runs on {version.get('platform')!r},"
                           f" expected {expect_platform!r}")
    if not version.get("device_kind") or not version.get("num_devices"):
        raise SmokeFailure(f"/version names no device: {version!r}")
    impls = version.get("attention_impl") or {}
    for phase, allowed in expected_impls.items():
        if impls.get(phase) not in allowed:
            raise SmokeFailure(
                f"attention impl for {phase} is {impls.get(phase)!r}; "
                f"'auto' on {expect_platform} should serve one of "
                f"{allowed} (all resolved: {impls})")

    metrics = _get(engine_url + "/metrics", _remaining(deadline, 30))
    for phase, impl in impls.items():
        if _metric(metrics, "vllm:engine_attention_impl",
                   phase=phase, impl=impl) != 1.0:
            raise SmokeFailure("vllm:engine_attention_impl disagrees "
                               f"with /version for {phase}={impl}")
    compile_events = {}
    for kind in ("step", "decode_burst", "unified"):
        compile_events[kind] = int(_metric(
            metrics, "vllm:engine_compile_events_total", kind=kind))
        if compile_events[kind] < 1:
            raise SmokeFailure(f"no compile event for {kind!r}: that "
                               "program never ran")
    generated = int(_metric(metrics, "vllm:generation_tokens_total"))
    if generated != asked_tokens:
        raise SmokeFailure(f"engine generated {generated} tokens, "
                           f"{asked_tokens} were asked for")
    finished = int(_metric(metrics, "vllm:request_success_total",
                           finished_reason="length"))
    if finished != served:
        raise SmokeFailure(f"engine finished {finished} requests by "
                           f"length, {served} were sent")
    with open(engine_log, "rb") as f:
        log_text = f.read().decode("utf-8", "replace")
    for bad in BAD_LOG_LINES:
        if bad in log_text:
            raise SmokeFailure(f"engine log contains {bad!r}")

    compile_seconds = json.loads(_get(
        engine_url + "/debug/compiles?limit=0",
        _remaining(deadline, 30)))["seconds"]
    memory = json.loads(_get(engine_url + "/debug/memory",
                             _remaining(deadline, 30)))
    return {
        "version": version,
        "compile_events": compile_events,
        "compile_seconds": {k: round(v, 2)
                            for k, v in compile_seconds.items()},
        "prompt_tokens": int(_metric(metrics,
                                     "vllm:prompt_tokens_total")),
        "generation_tokens": generated,
        "memory": memory,
    }


# ---- parent: main ---------------------------------------------------------


def run_parent(args) -> int:
    start = time.time()
    deadline = start + TOTAL_BUDGET_S
    cfg = SMOKE_CONFIGS[args.model]
    wall = {}
    procs = Procs()

    def on_term(signum, frame):
        raise SmokeFailure(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_term)
    try:
        # Phase A: kernels, in a child that exits before the server
        # claims the chip.
        t0 = time.time()
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--child-kernels", "--model", args.model,
             "--expect-platform", args.expect_platform],
            cwd=REPO, stdout=subprocess.PIPE,
            timeout=_remaining(deadline, 600))
        lines = child.stdout.decode().strip().splitlines()
        if child.returncode != 0 or not lines:
            raise SmokeFailure(
                f"kernel phase failed (exit {child.returncode})")
        kernels = json.loads(lines[-1])
        wall["kernels_s"] = round(time.time() - t0, 1)

        # Phase B: the server behind the router.
        t0 = time.time()
        engine_port, router_port = _free_port(), _free_port()
        engine_url = f"http://127.0.0.1:{engine_port}"
        router_url = f"http://127.0.0.1:{router_port}"
        engine_cmd = [
            sys.executable, "-m", "production_stack_tpu.engine.server",
            "--model", args.model, "--random-weights",
            "--host", "127.0.0.1", "--port", str(engine_port),
            "--page-size", str(cfg["page_size"]),
            "--num-pages", str(cfg["num_pages"]),
            "--max-num-seqs", str(cfg["max_num_seqs"]),
            "--max-model-len", str(cfg["max_model_len"]),
            "--prefill-chunk-size", str(cfg["prefill_chunk_size"]),
            "--prefill-batch-size", str(cfg["prefill_batch_size"]),
            "--decode-steps", str(cfg["decode_steps"]),
        ]
        if args.tensor_parallel_size > 1:
            engine_cmd += ["--tensor-parallel-size",
                           str(args.tensor_parallel_size)]
        engine, engine_log = procs.start("engine", engine_cmd)
        _wait_http_ok(engine_url + "/health", engine, "engine",
                      deadline, 600)
        router, _ = procs.start("router", [
            sys.executable, "-m", "production_stack_tpu.router.app",
            "--host", "127.0.0.1", "--port", str(router_port),
            "--service-discovery", "static",
            "--static-backends", engine_url,
            "--static-models", args.model,
        ])
        _wait_http_ok(router_url + "/health", router, "router",
                      deadline, 60)
        wall["startup_s"] = round(time.time() - t0, 1)

        t0 = time.time()
        served, asked = drive_requests(router_url, args.model, cfg,
                                       deadline)
        wall["requests_s"] = round(time.time() - t0, 1)

        report = read_engine(
            engine_url, args.expect_platform,
            (XLA_EVERYWHERE if args.tensor_parallel_size > 1
             else EXPECTED_IMPLS[args.expect_platform]),
            asked, served, engine_log, deadline)
        if engine.poll() is not None or router.poll() is not None:
            raise SmokeFailure("a server process exited during the run")
    except (SmokeFailure, subprocess.TimeoutExpired, OSError,
            ValueError, KeyError) as e:
        print(f"[chip_smoke] FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        procs.dump_tails()
        return 1
    finally:
        stopped = procs.stop_all()
    if not stopped:
        return 1

    version = report["version"]
    if (version["platform"], version["device_kind"]) != (
            kernels["platform"], kernels["device_kind"]):
        print("[chip_smoke] FAILED: the two phases saw different "
              f"devices: {kernels['device_kind']!r} vs "
              f"{version['device_kind']!r}", file=sys.stderr)
        return 1
    cache_dir = kernels["compile_cache_dir"]
    wall["total_s"] = round(time.time() - start, 1)
    device = {"platform": version["platform"],
              "kind": version["device_kind"],
              "count": version["num_devices"]}
    # The findings, one JSON line (also kept beside the logs), then the
    # result line: exactly {"ok", "device"}, the last line of stdout.
    report_line = json.dumps({
        "model": args.model,
        "tensor_parallel_size": args.tensor_parallel_size,
        "platform": device["platform"],
        "device_kind": device["kind"],
        "num_devices": device["count"],
        "jax": kernels["jax"], "jaxlib": kernels["jaxlib"],
        "libtpu": kernels["libtpu"],
        "attention_impl": version["attention_impl"],
        "kernels": kernels["kernels"],
        "engine_compile_events": report["compile_events"],
        "engine_compile_seconds": report["compile_seconds"],
        "compile_cache_dir": cache_dir,
        "compile_cache_nonempty": bool(
            os.path.isdir(cache_dir) and os.listdir(cache_dir)),
        "requests_served": served,
        "prompt_tokens": report["prompt_tokens"],
        "generation_tokens": report["generation_tokens"],
        "memory": report["memory"],
        "wall_s": wall,
    })
    with open(os.path.join(LOG_DIR, "report.json"), "w") as f:
        f.write(report_line + "\n")
    print(report_line)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--expect-platform", default="tpu",
                        choices=sorted(EXPECTED_IMPLS),
                        help="platform jax.devices() must report; a "
                             "mismatch fails")
    parser.add_argument("--model", default="bench-1b",
                        choices=sorted(SMOKE_CONFIGS))
    parser.add_argument("--tensor-parallel-size", type=int, default=1)
    parser.add_argument("--child-kernels", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child_kernels:
        return run_kernels_child(args)
    return run_parent(args)


if __name__ == "__main__":
    sys.exit(main())
