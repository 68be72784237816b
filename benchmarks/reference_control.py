"""The control reading of a configuration's reference tolerance: the
family's float32 reference against itself with every matrix rounded to
a coarser type, on the sequences a benchmark run was checked on.

    JAX_PLATFORMS=cpu python3 benchmarks/reference_control.py \
        <config.json> <sequences.json> [float8_e4m3fn] [<out.json>]

``sequences.json`` is what ``chipbench/run.py`` leaves beside its
reference cache (``.chipbench/reference/<config>-<key>/``: each
sequence the prompt and the tokens the server answered). For every
sequence the log-probabilities after the prompt and after each answer
but the last are computed twice, and the absolute differences over the
reference's six most likely tokens a position are printed a prompt and
overall: what the harness's comparison would read if the program were
exact in the coarser type. A tolerance belongs between the chip's
reading and this one (PERF.md section 6, PRs 36, 41, 43). Where the
family's reference has ``draft_log_probs`` the module's distribution is
read the same way. Runs on the CPU; a full-size configuration takes the
memory of its weights twice over, so run it on the chip machine's host.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import family  # noqa: E402


def rounded(model, dtype):
    """``model`` (a reference's dataclass) with every matrix, wherever
    it is kept (a field, a dict, what a per-layer callable returns),
    rounded to ``dtype`` and back; vectors (norms, biases) stay."""
    def cast(value):
        if callable(value):
            return lambda *args: cast(value(*args))
        if isinstance(value, dict):
            return {k: cast(v) for k, v in value.items()}
        if getattr(value, "ndim", 0) >= 2:
            return jnp.asarray(value, jnp.float32).astype(dtype).astype(
                jnp.float32)
        return value
    return dataclasses.replace(model, **{
        f.name: cast(getattr(model, f.name))
        for f in dataclasses.fields(model)})


def main(argv) -> int:
    with open(argv[1]) as f:
        config = json.load(f)
    with open(argv[2]) as f:
        sequences = json.load(f)
    dtype = getattr(jnp, argv[3] if len(argv) > 3 else "float8_e4m3fn")
    reference = family.module("reference", config)
    bench = config.pop("chipbench")
    model = reference.program_model(config, bench)
    coarse = rounded(model, dtype)
    readers = {"log_probs": reference.log_probs}
    if getattr(model, "module", None) is not None:
        readers["draft_log_probs"] = reference.draft_log_probs
    report = {name: [] for name in readers}
    for seq in sequences:
        tokens = seq["prompt_ids"] + seq["answer_ids"]
        first = len(seq["prompt_ids"]) - 1
        positions = list(range(first, first + len(seq["answer_ids"])))
        for name, fn in readers.items():
            at = positions if name == "log_probs" else positions[:-1]
            want = np.asarray(fn(model, tokens, at))
            got = np.asarray(fn(coarse, tokens, at))
            top = np.argsort(-want, -1)[:, :6]
            diff = np.abs(np.take_along_axis(got, top, -1)
                          - np.take_along_axis(want, top, -1))
            report[name].append([float(diff.max()), float(diff.mean())])
            print(name, len(seq["prompt_ids"]), "worst %.5f mean %.5f"
                  % tuple(report[name][-1]), flush=True)
    for name, rows in report.items():
        print(name, "overall worst %.5f mean %.5f; least a prompt "
              "%.5f / %.5f" % (max(r[0] for r in rows),
                               float(np.mean([r[1] for r in rows])),
                               min(r[0] for r in rows),
                               min(r[1] for r in rows)), flush=True)
    if len(argv) > 4:
        with open(argv[4], "w") as f:
            json.dump(report, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
