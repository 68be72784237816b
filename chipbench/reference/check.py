"""Child process: what the float32 reference expects for the
correctness requests.

    JAX_PLATFORMS=cpu python3 chipbench/reference/check.py \
        --config <configs/x.json> --sequences <in.json> --out <out.npz>

Runs on the CPU beside the server.  It is given the same parameter
*values* as the server: the program's own random init with the
configuration's weights seed is data here (int8 leaves dequantised as
value x scale), and nothing else of the program is used.  It builds the
weights first, then waits for ``--sequences`` (written by the harness
once the server has answered: each sequence is the prompt plus the
tokens the server returned), runs one full forward for each, and
writes the log-probabilities of the next token after the prompt and
after each returned token but the last: [sequences, answers, vocab].
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.reference import llama_family  # noqa: E402


def program_weights(hf_config: dict, quantization: str, seed: int):
    """The server's random weights, by the server's own init, as
    ``llama_family.Weights``."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.engine.quantization import (
        init_random_quantized,
    )
    from production_stack_tpu.models.registry import get_model

    config = ModelConfig.from_hf_config(hf_config)
    config.quantization = quantization
    init_fn, _ = get_model(config)
    if quantization == "int8":
        params = init_random_quantized(init_fn, config, seed)
    else:
        params = init_fn(config, jax.random.PRNGKey(seed))
    per_layer = [k for k, v in params.items()
                 if k not in ("embed", "final_norm", "lm_head")]

    def layer(i: int) -> dict:
        out = {}
        for name in per_layer:
            leaf = params[name]
            if isinstance(leaf, tuple):  # (int8 values, scale per column)
                q, scale = leaf
                out[name] = (q[i].astype(jnp.float32)
                             * scale[i].astype(jnp.float32)[None, :])
            else:
                out[name] = leaf[i].astype(jnp.float32)
        return out

    shape = llama_family.Shape(
        num_layers=config.num_hidden_layers,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim, rms_eps=config.rms_norm_eps,
        rope_theta=config.rope_theta)
    return llama_family.Weights(
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params.get("lm_head"), layer=layer), shape


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--sequences", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--wait-s", type=float, default=1500.0)
    args = parser.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    bench = config.pop("chipbench")
    t0 = time.time()
    weights, shape = program_weights(
        config, bench["quantization"], bench["weights_seed"])
    print(f"[reference] weights after {time.time() - t0:.1f}s",
          file=sys.stderr, flush=True)
    deadline = time.time() + args.wait_s
    while not os.path.exists(args.sequences):
        if time.time() > deadline:
            print("[reference] no sequences came", file=sys.stderr)
            return 1
        time.sleep(0.5)
    with open(args.sequences) as f:
        sequences = json.load(f)
    rows = []
    for seq in sequences:
        tokens = seq["prompt_ids"] + seq["answer_ids"]
        first = len(seq["prompt_ids"]) - 1
        positions = list(range(first, first + len(seq["answer_ids"])))
        rows.append(np.asarray(llama_family.log_probs(
            weights, shape, tokens, positions)))
    tmp = args.out + ".tmp.npz"
    np.savez(tmp, log_probs=np.stack(rows))
    os.replace(tmp, args.out)
    print(f"[reference] done after {time.time() - t0:.1f}s",
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
