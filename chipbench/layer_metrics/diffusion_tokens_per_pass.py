"""Tokens a row commits a forward pass of the block-diffusion burst:
the program's ``committed`` over ``decode_rows`` x ``window`` (the
passes that ran, denoising and store), summed over the window's decode
burst records.  1.33 at two denoising passes and a store pass for a
block of four; a model whose confidence lets the dynamic rule commit
more a pass, or a store pass fused into the next block's first pass,
raises it.  None where no record carries the counter (a family that
generates left to right, a parent whose program lacks it)."""

LAYER = "model + ops"
UNIT = "tokens"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(run):
    bursts = [s for s in run.window_steps
              if s.get("kind") == "decode" and "committed" in s
              and s.get("decode_rows") and s.get("window")]
    passes = sum(s["decode_rows"] * s["window"] for s in bursts)
    if not passes:
        return None
    return sum(s["committed"] for s in bursts) / passes
