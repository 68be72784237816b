"""Did a change move the step programs of the families it did not mean
to touch?

    JAX_PLATFORMS=cpu python3 benchmarks/step_program_text.py <checkout>

Lowers, for the TPU and from the checkout given, the deferred decode
burst and the prefill step of a tiny bfloat16 model of each family the
chip benchmark has a cell for that generates left to right (Pallas
attention, 4 rows, pages of 128; ``glm4_moe_lite``'s burst is the
drafting one, since PR 57, when ``exaone_moe`` came too), and prints
one line a program: its name, the length of its
StableHLO text and a hash of that text with the Mosaic kernels'
serialized bodies left out (they embed the source files' paths, so two
checkouts never agree on them; compare the kernel files themselves).
Run it on the parent (``git archive`` into a directory) and on the
change and compare the lines: equal hashes are equal programs (PERF.md
section 6, PR 41; section 7 (28)).
"""

import hashlib
import re
import sys

sys.path.insert(0, sys.argv[1])

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from production_stack_tpu.engine import config as cfg  # noqa: E402
from production_stack_tpu.engine.model_runner import ModelRunner  # noqa: E402

ROWS, STEPS, CHUNK = 4, 8, 64


def text_hash(text: str) -> str:
    text = re.sub(r'backend_config = "[^"]*"', "backend_config = <kernel>",
                  text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def main() -> None:
    models = {"qwen2": cfg.tiny_model_config("llama"),
              "qwen3_next": cfg.tiny_qwen3_next_config(),
              "jamba": cfg.tiny_jamba_config(),
              "lfm2_moe": cfg.tiny_lfm2_moe_config(),
              "longcat_flash": cfg.tiny_longcat_flash_config(),
              "granitemoehybrid": cfg.tiny_granitemoehybrid_config(),
              "glm4_moe_lite": cfg.tiny_glm4_moe_lite_config(),
              "exaone_moe": cfg.tiny_exaone_moe_config()}
    models["qwen2"].attention_bias = True
    models["exaone_moe"].sliding_window = 128     # whole pages
    for name, model in models.items():
        model.attention_impl, model.dtype = "pallas", "bfloat16"
        runner = ModelRunner(cfg.EngineConfig(
            model=model,
            cache=cfg.CacheConfig(page_size=128, num_pages=32),
            scheduler=cfg.SchedulerConfig(
                max_num_seqs=ROWS, max_model_len=256,
                prefill_chunk_size=CHUNK, decode_steps=STEPS,
                deferred_kv_writes=True,
                draft_module=name == "glm4_moe_lite")))
        i32 = lambda *dims: jnp.zeros(dims, jnp.int32)  # noqa: E731
        sampling = (jnp.zeros((ROWS,), jnp.float32),
                    jnp.ones((ROWS,), jnp.float32), i32(ROWS),
                    jax.random.PRNGKey(0)) + (None,) * 7
        state = ({"state_slots": i32(ROWS)} if model.has_recurrent_state
                 else {})
        drafts = name == "glm4_moe_lite"
        burst = jax.jit(runner._decode_burst_draft_impl if drafts
                        else runner._decode_burst_deferred_impl,
                        static_argnames=("num_steps",)).trace(
            runner.params, runner.k_cache, runner.v_cache, i32(ROWS, 1),
            i32(ROWS, 1), i32(ROWS, runner.max_pages_per_seq), i32(ROWS),
            jnp.zeros((ROWS,), bool), i32(ROWS),
            jnp.full((ROWS, 16), -1, jnp.int32), *sampling,
            num_steps=STEPS, **state,
            **({"draft_rows": jnp.ones((ROWS,), bool)} if drafts else {}))
        step = jax.jit(runner._step_impl, static_argnames=(
            "sample_index_mode", "want_logprobs")).trace(
            runner.params, runner.k_cache, runner.v_cache,
            i32(ROWS, CHUNK), i32(ROWS, CHUNK),
            i32(ROWS, runner.max_pages_per_seq), i32(ROWS),
            jnp.zeros((ROWS, CHUNK), bool), i32(ROWS), *sampling,
            sample_index_mode="last", **state,
            **({"next_tokens": i32(ROWS)} if drafts else {}))
        for program, traced in (("burst", burst), ("step", step)):
            text = traced.lower(lowering_platforms=("tpu",)).as_text()
            print(name, program, len(text), text_hash(text), flush=True)


if __name__ == "__main__":
    main()
