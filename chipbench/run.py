"""One run of one cell of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts ``python -m production_stack_tpu.engine.server`` behind ``python
-m production_stack_tpu.router.app``, refuses to go on unless the
server holds the platform and the chips the cell asks for, warms the
cell's shapes, checks the server's log-probabilities against the
float32 reference, offers the cell's traffic for ``--seconds``, and
prints one JSON object as the last line of stdout.  Any failure exits
non-zero with no result line.  This process never imports jax: the
chip belongs to the server.  See ``chipbench/README.md``.
"""

from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import hashlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

import aiohttp  # noqa: E402

from chipbench import e2e, family, wordtok  # noqa: E402
from chipbench.client import Load  # noqa: E402
from chipbench.procs import (  # noqa: E402
    Procs, RunFailure, free_port, http, wait_http_ok)
from chipbench.runfiles import RunFiles  # noqa: E402

# Everything a run writes lives here, at fixed paths inside the
# checkout: run directories, the model directories with the tokenizer,
# the reference's cached answers and the server's compile cache.
STATE = os.path.join(ROOT, ".chipbench")

E2E_UNITS = {"output_tok_s": "tokens/s", "setup_s": "s"}

# The correctness requests: 4 greedy prompts, 8 tokens each, top five
# log-probabilities.  Prompt lengths stay inside one prefill bucket
# (129-256) so that the log-probability variants of the step programs,
# which the timed traffic never uses, are two compiles and not four.
CHECK = {"prompts": 4, "min_tokens": 129, "max_tokens": 256,
         "answers": 8, "top": 5}

BAD_LOG_LINES = ("Engine step failed",)


def log(msg: str) -> None:
    print(f"[chipbench +{time.time() - PROCESS_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str) -> dict:
    for sub in ("workloads", os.path.join("rehearsal", "workloads")):
        path = os.path.join(BENCH, sub, name + ".json")
        if os.path.exists(path):
            cell = load_json(path)
            cell["name"] = name
            cell["config_file"] = os.path.join(
                os.path.dirname(os.path.dirname(path)), "configs",
                cell["config"] + ".json")
            return cell
    raise RunFailure(f"no workload file for {name!r}")


def layer_metric(name: str):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


def validate(cell: dict) -> None:
    """A cell reports setup_s, and every per-layer metric it lists
    moves an end-to-end metric it also reports."""
    if "setup_s" not in cell["end_to_end"]:
        raise RunFailure("a cell reports setup_s")
    for name in cell["end_to_end"]:
        if name not in E2E_UNITS:
            raise RunFailure(f"unknown end-to-end metric {name!r}")
    for name in cell["per_layer"]:
        moves = layer_metric(name).MOVES
        if moves not in cell["end_to_end"]:
            raise RunFailure(
                f"{name} moves {moves}, which {cell['name']} does not "
                "report")


# ---- the reference ---------------------------------------------------------


def check_requests(hf_config: dict, seed: int) -> list:
    rng = random.Random(f"check:{seed}")
    return [[rng.randrange(hf_config["vocab_size"])
             for _ in range(rng.randint(CHECK["min_tokens"],
                                        CHECK["max_tokens"]))]
            for _ in range(CHECK["prompts"])]


class Reference:
    """The float32 reference's answers for the correctness requests,
    from the cache beside the compile cache or from a child process on
    the CPU that is started with the server and fed the server's
    answers when they come."""

    def __init__(self, cell: dict, procs: Procs):
        with open(cell["config_file"], "rb") as f:
            key = hashlib.sha256(f.read()).hexdigest()[:16]
        self.dir = os.path.join(STATE, "reference", f"{cell['config']}-{key}")
        os.makedirs(self.dir, exist_ok=True)
        self.sequences_path = os.path.join(self.dir, "sequences.json")
        self.answers_path = os.path.join(self.dir, "log_probs.npz")
        self.cell = cell
        self.procs = procs
        self.child = None
        self.cached = (os.path.exists(self.answers_path)
                       and os.path.exists(self.sequences_path))
        if not self.cached:
            self._start_child()

    def _start_child(self) -> None:
        for path in (self.sequences_path, self.answers_path):
            if os.path.exists(path):
                os.remove(path)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.child, _ = self.procs.start("reference", [
            sys.executable, os.path.join(BENCH, "reference", "check.py"),
            "--config", self.cell["config_file"],
            "--sequences", self.sequences_path,
            "--out", self.answers_path], env=env)

    def compare(self, sequences: list, served: list):
        """``served[i][j]``: {token id: log-probability} of answer j of
        sequence i, the returned token and the top five.  Returns the
        absolute differences from the reference, one list per answer
        position: the first is the prefill's, the rest are decode
        steps through the cache."""
        if self.cached and load_json(self.sequences_path) != sequences:
            log("the server's answers differ from the cached ones: "
                "computing the reference again")
            self.cached = False
            self._start_child()
        if not self.cached:
            tmp = self.sequences_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(sequences, f)
            os.replace(tmp, self.sequences_path)
            log("waiting for the reference")
            code = self.child.wait(timeout=1500)
            if code != 0:
                raise RunFailure(f"the reference exited with {code}")
        # numpy only here: reading the cached array needs no jax.
        import numpy as np
        expected = np.load(self.answers_path)["log_probs"]
        return [[abs(value - float(expected[i, j, tid]))
                 for i, answers in enumerate(served)
                 for tid, value in answers[j].items()]
                for j in range(CHECK["answers"])]


# ---- the server ------------------------------------------------------------


def completion(url: str, model: str, prompt_ids: list, max_tokens: int,
               extra: dict, timeout: float) -> dict:
    body = {"model": model, "prompt": wordtok.text_of(prompt_ids),
            "max_tokens": max_tokens, "ignore_eos": True, **extra}
    reply = json.loads(http(url + "/v1/completions", timeout,
                            data=json.dumps(body).encode(),
                            headers={"content-type": "application/json"}))
    usage, choice = reply["usage"], reply["choices"][0]
    if (choice["finish_reason"] != "length"
            or usage["completion_tokens"] != max_tokens
            or usage["prompt_tokens"] != len(prompt_ids)):
        raise RunFailure(
            f"a prompt of {len(prompt_ids)} tokens asking for {max_tokens} "
            f"came back {choice['finish_reason']!r} with usage {usage}")
    return reply


def start_servers(cell: dict, bench: dict, procs: Procs, run_dir: str,
                  trace: bool):
    model_dir = os.path.join(STATE, "models", cell["config"])
    hf_config = {k: v for k, v in load_json(cell["config_file"]).items()
                 if k != "chipbench"}
    wordtok.write_model_dir(model_dir, hf_config)
    engine_url = f"http://127.0.0.1:{free_port()}"
    router_url = f"http://127.0.0.1:{free_port()}"
    cmd = [sys.executable, "-m", "production_stack_tpu.engine.server",
           "--model", model_dir, "--tokenizer", model_dir,
           "--served-model-name", cell["config"], "--random-weights",
           "--dtype", bench["dtype"], "--seed", str(bench["weights_seed"]),
           "--host", "127.0.0.1", "--port", engine_url.rsplit(":", 1)[1]]
    for flag, value in bench["server_flags"].items():
        cmd += [f"--{flag}", str(value)]
    if trace:
        cmd += ["--request-span-log", os.path.join(run_dir, "spans.jsonl")]
    # The server takes the compile cache the benchmark gives it, with
    # no size limit: a machine's own limit of 192 MiB evicted every
    # 36-layer program before the next run could find it.
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=os.path.join(STATE, "jax_cache"))
    env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    engine, engine_log = procs.start("engine", cmd, env=env)
    wait_http_ok(engine_url + "/health", engine, "engine", 900)
    router, _ = procs.start("router", [
        sys.executable, "-m", "production_stack_tpu.router.app",
        "--host", "127.0.0.1", "--port", router_url.rsplit(":", 1)[1],
        "--service-discovery", "static",
        "--static-backends", engine_url,
        "--static-models", cell["config"]])
    wait_http_ok(router_url + "/health", router, "router", 60)
    return engine_url, router_url, engine_log, hf_config


def require_device(engine_url: str, cell: dict, bench: dict) -> dict:
    version = json.loads(http(engine_url + "/version", 30))
    want = cell.get("platform", "tpu")
    if (version["platform"], version["num_devices"]) != (want,
                                                          bench["chips"]):
        raise RunFailure(
            f"the server holds {version['num_devices']} x "
            f"{version['platform']!r}; the cell asks for "
            f"{bench['chips']} x {want!r}")
    return version


def warm_and_check(cell: dict, hf_config: dict, bench: dict,
                   router_url: str, reference: Reference):
    """Every shape the cell's traffic uses, once, by name; then the
    correctness requests.  All before the window, all set-up."""
    model = cell["config"]
    rng = random.Random("warm")
    for length in cell["warm_prompt_tokens"]:
        prompt = [rng.randrange(hf_config["vocab_size"])
                  for _ in range(length)]
        completion(router_url, model, prompt, 2, cell["sampling"], 900)
        log(f"warmed a prompt of {length}")
    prompts = check_requests(hf_config, bench["weights_seed"])
    sequences, served = [], []
    for prompt in prompts:
        reply = completion(
            router_url, model, prompt, CHECK["answers"],
            {"temperature": 0.0, "logprobs": CHECK["top"]}, 900)
        lp = reply["choices"][0]["logprobs"]
        answer_ids = [wordtok.token_id(t) for t in lp["tokens"]]
        if len(answer_ids) != CHECK["answers"]:
            raise RunFailure("a correctness request came back short")
        sequences.append({"prompt_ids": prompt, "answer_ids": answer_ids})
        served.append([
            {**{wordtok.token_id(t): v for t, v in top.items()},
             tid: value}
            for tid, value, top in zip(answer_ids, lp["token_logprobs"],
                                       lp["top_logprobs"])])
    tolerance = bench["reference_tolerance"]
    by_position = reference.compare(sequences, served)
    diffs = [d for position in by_position for d in position]
    worst, mean = max(diffs), sum(diffs) / len(diffs)
    ok = (worst <= tolerance["max_abs_logprob_diff"]
          and mean <= tolerance["mean_abs_logprob_diff"])
    log(f"reference: |difference| worst {worst:.5f} mean {mean:.5f} over "
        f"{len(diffs)} log-probabilities: {'ok' if ok else 'FAILED'}")
    return {"ok": ok, "worst_abs_diff": worst, "mean_abs_diff": mean,
            "mean_abs_diff_by_position": [sum(p) / len(p)
                                          for p in by_position],
            "compared": len(diffs), "tolerance": tolerance}


# ---- the window ------------------------------------------------------------


async def poll_json(session, url: str):
    async with session.get(url) as resp:
        return await resp.json()


async def traced_side(load, engine_url: str, run_dir: str, out: dict):
    """What only the traced run does during the window: the step
    records, the cache gauge each second, and one profiler slice."""
    steps, usage = {}, []
    # Long enough to hold a few whole decode bursts (2 to 3 s each).
    slice_at = load.seconds * 0.3
    slice_s = min(8.0, load.seconds * 0.4)
    trace_dir = os.path.join(run_dir, "profile")
    async with aiohttp.ClientSession() as session:
        async def profiler():
            await load.sleep_until(slice_at)
            async with session.post(
                    engine_url + "/debug/profiler/start",
                    params={"dir": trace_dir}) as resp:
                await resp.read()
            started = time.time()
            await asyncio.sleep(slice_s)
            async with session.post(
                    engine_url + "/debug/profiler/stop") as resp:
                await resp.read()
            out["slice_unix"] = [started, started + slice_s]
        prof = asyncio.ensure_future(profiler())
        await load.sleep_until(0)
        while load.now() < load.seconds + 1.0:
            tick = load.now()
            data = await poll_json(session,
                                   engine_url + "/debug/steps?limit=512")
            for step in data["steps"]:
                steps[step["step"]] = step
            async with session.get(engine_url + "/metrics") as resp:
                for line in (await resp.text()).splitlines():
                    if line.startswith("vllm:gpu_cache_usage_perc"):
                        usage.append(float(line.rsplit(" ", 1)[1]))
            await load.sleep_until(tick + 1.0)
        await prof
    out["steps"] = [steps[k] for k in sorted(steps)]
    out["cache_usage"] = usage


async def window(load, requests, traffic, engine_url, run_dir,
                 trace: bool, side: dict):
    async with aiohttp.ClientSession() as session:
        async def snapshot_compiles():
            await load.sleep_until(0)
            side["compiles_before"] = await poll_json(
                session, engine_url + "/debug/compiles?limit=64")
            await load.sleep_until(load.seconds)
            side["compiles_after"] = await poll_json(
                session, engine_url + "/debug/compiles?limit=64")
        tasks = [asyncio.ensure_future(snapshot_compiles())]
        if trace:
            tasks.append(asyncio.ensure_future(
                traced_side(load, engine_url, run_dir, side)))
        await load.run(traffic.drive, requests)
        await asyncio.gather(*tasks)


def reduce_trace(run_dir: str, procs: Procs, platform: str) -> None:
    child, _ = procs.start("reduce", [
        sys.executable, os.path.join(BENCH, "reduce.py"),
        os.path.join(run_dir, "profile"),
        os.path.join(run_dir, "trace_summary.json"), platform],
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if child.wait(timeout=300) != 0:
        raise RunFailure("the trace reduction failed")


def device_time_by_source(summary: dict) -> list:
    """[["<program>: <source file>", device seconds]], the most first:
    what the ledger's reader can place in the program, where an
    operation's own name (``fusion.6205``) says nothing.  Empty where
    the trace names no source (the rehearsal's stand-in)."""
    by_source = [[f"{program}: {file}", seconds]
                 for program, files in summary.get("sources", {}).items()
                 for file, seconds in files.items()]
    return sorted(by_source, key=lambda kv: -kv[1])


def run(args) -> int:
    if not os.path.isdir(os.path.join(ROOT, "production_stack_tpu")):
        raise RunFailure("the system under test is not in this checkout")
    cell = find_cell(args.workload)
    validate(cell)
    config = load_json(cell["config_file"])
    family.name_of(config)  # or the run ends here, saying what to add
    bench = config["chipbench"]
    trace = bool(args.trace)
    run_dir = os.path.join(STATE, "runs", cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    procs = Procs(run_dir, ROOT)

    def on_term(signum, frame):
        raise RunFailure(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_term)
    try:
        reference = Reference(cell, procs)
        engine_url, router_url, engine_log, hf_config = start_servers(
            cell, bench, procs, run_dir, trace)
        version = require_device(engine_url, cell, bench)
        log(f"serving on {version['num_devices']} x "
            f"{version['device_kind']}")
        checked = warm_and_check(cell, hf_config, bench, router_url,
                                 reference)

        traffic = importlib.import_module(
            f"chipbench.traffic.{cell['traffic_kind']}")
        params = cell["traffic_params"]
        requests = traffic.plan(params, args.seconds, args.seed,
                                hf_config["vocab_size"])
        load = Load(router_url, cell["config"], params, args.seconds,
                    cell["sampling"], start_in_s=params["ramp_s"] + 0.5)
        side = {}
        asyncio.run(window(load, requests, traffic, engine_url, run_dir,
                           trace, side))
        setup_s = load.t0_unix - PROCESS_START
        log("window done")

        health = json.loads(http(engine_url + "/health", 30))
        memory = json.loads(http(engine_url + "/debug/memory", 30))
        # After the drain: what compiled or was loaded behind the
        # window shows as a stall in the arrivals (PERF.md, PR 25).
        side["compiles_end"] = json.loads(
            http(engine_url + "/debug/compiles?limit=64", 30))
        with open(engine_log, errors="replace") as f:
            text = f.read()
        bad = [line for line in BAD_LOG_LINES if line in text]
    except (RunFailure, subprocess.TimeoutExpired, OSError, ValueError,
            KeyError) as e:
        print(f"[chipbench] FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        procs.dump_tails()
        procs.stop_all()
        return 1
    except BaseException:
        procs.stop_all()
        raise
    if not procs.stop_all():
        return 1

    files = {"records.json": load.records, "arrivals.json": load.arrivals,
             "memory.json": memory,
             "compiles.json": {"before": side["compiles_before"],
                               "after": side["compiles_after"],
                               "end": side["compiles_end"]},
             "steps.json": side.get("steps"),
             "cache_usage.json": side.get("cache_usage"),
             "cell.json": {**cell, "config_as_run": config,
                           "t0_unix": load.t0_unix,
                           "seconds": args.seconds, "seed": args.seed,
                           "slice_unix": side.get("slice_unix"),
                           "version": version}}
    for name, content in files.items():
        if content is not None:
            with open(os.path.join(run_dir, name), "w") as f:
                json.dump(content, f)
    if trace:
        reduce_trace(run_dir, procs, version["platform"])
        procs.stop_all()

    summary = e2e.summarize(load.records, load.arrivals, args.seconds)
    summary["setup_s"] = setup_s
    lags = [r["sent"] - r["due"] for r in load.records
            if r["phase"] == "window" and r["sent"] is not None]
    peaks = [d["peak_bytes_in_use"] for d in memory.get("devices", [])]
    device = {"platform": version["platform"],
              "kind": version["device_kind"],
              "count": version["num_devices"],
              "memory_peak_bytes": max(peaks) if peaks else 0}
    correct = (checked["ok"] and summary["failed"] == 0
               and summary["unfinished"] == 0 and summary["attempted"] > 0
               and health.get("status") == "ok" and not bad)
    result = {"correct": correct, "attempted": summary["attempted"],
              "failed": summary["failed"],
              "unfinished": summary["unfinished"], "metrics": {},
              "device": device}
    runfiles = RunFiles(run_dir)
    if trace:
        for name in cell["per_layer"]:
            reader = layer_metric(name)
            value = reader.read(runfiles)
            if value is not None:
                result["metrics"][name] = {"value": value,
                                           "unit": reader.UNIT}
        summary_trace = runfiles.trace or {}
        device["busy_s"] = summary_trace.get("busy_s", 0.0)
        device["window_s"] = summary_trace.get("window_s", 0.0)
        if summary_trace.get("device_ops"):
            result["breakdown"] = {
                "device_ops": summary_trace["device_ops"][:10],
                "device_sources": device_time_by_source(summary_trace)[:10],
                "idle_gaps": summary_trace["idle_gaps"][:10]}
    else:
        for name in cell["end_to_end"]:
            value = summary[name]
            result["metrics"][name] = {
                "value": value if math.isfinite(value) else 1e9,
                "unit": E2E_UNITS[name]}
    report = {"cell": cell["name"], "seed": args.seed, "trace": trace,
              "seconds": args.seconds, "summary": summary,
              "reference": checked, "health": health, "bad_log_lines": bad,
              "client_lag_max_ms": max(lags) * 1e3 if lags else None,
              "client_lag_p90_ms": (e2e.percentile(lags, 90) * 1e3
                                    if lags else None),
              "phases": {p: sum(r["phase"] == p for r in load.records)
                         for p in ("ramp", "window", "post")},
              "in_flight_mid_end": [
                  e2e.in_flight(load.records, args.seconds / 2),
                  e2e.in_flight(load.records, args.seconds)],
              "compile_seconds": side["compiles_after"].get("seconds"),
              "window_compiles": (
                  sum(side["compiles_after"]["events"].values())
                  - sum(side["compiles_before"]["events"].values())),
              "drain_compiles": (
                  sum(side["compiles_end"]["events"].values())
                  - sum(side["compiles_after"]["events"].values())),
              "attention_impl": version.get("attention_impl"),
              "result": result}
    with open(os.path.join(run_dir, "report.json"), "w") as f:
        json.dump(report, f)
    print(json.dumps({k: v for k, v in report.items() if k != "result"}))
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if args.seconds is None:
        args.seconds = load_json(
            os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"]
    try:
        return run(args)
    except (RunFailure, family.UnknownFamily) as e:
        print(f"[chipbench] FAILED: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
