"""Child process: what the float32 reference expects for the
correctness requests.

    JAX_PLATFORMS=cpu python3 chipbench/reference/check.py \
        --config <configs/x.json> --sequences <in.json> --out <out.npz>

Runs on the CPU beside the server.  The forward pass is the module
``chipbench/reference/<family>.py`` that the configuration's
``chipbench`` group names (``family.py``), and two functions of it are
all this file knows of an architecture: ``program_model(hf_config,
bench)`` gives the model with the same parameter *values* as the
server's (the program's own random init with the configuration's
weights seed is data there, and nothing else of the program is used),
and ``log_probs(model, tokens, positions)`` the log-softmax of the next
token after each position.  It builds the model first, then waits for
``--sequences`` (written by the harness once the server has answered:
each sequence is the prompt plus the tokens the server returned), runs
one full forward for each, and writes the log-probabilities of the next
token after the prompt and after each returned token but the last:
[sequences, answers, vocab].
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

import numpy as np  # noqa: E402

from chipbench import family  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--sequences", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--wait-s", type=float, default=1500.0)
    args = parser.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    reference = family.module("reference", config)
    bench = config.pop("chipbench")
    t0 = time.time()
    model = reference.program_model(config, bench)
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
        rows.append(np.asarray(reference.log_probs(model, tokens,
                                                   positions)))
    tmp = args.out + ".tmp.npz"
    np.savez(tmp, log_probs=np.stack(rows))
    os.replace(tmp, args.out)
    print(f"[reference] done after {time.time() - t0:.1f}s",
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
