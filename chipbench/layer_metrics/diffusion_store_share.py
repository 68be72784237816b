"""Share of the block-diffusion burst's forward passes that are store
passes (a block's final K/V written, nothing sampled): the program's
``store_passes`` over ``store_passes + denoise_passes``, summed over the
window's decode burst records.  33 at two denoising passes a block; the
yardstick of fusing a block's store pass with the next block's first
denoising pass.  None where no record carries the counters."""

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(run):
    bursts = [s for s in run.window_steps
              if s.get("kind") == "decode" and "store_passes" in s]
    stores = sum(s["store_passes"] for s in bursts)
    passes = stores + sum(s.get("denoise_passes", 0) for s in bursts)
    if not passes:
        return None
    return 100.0 * stores / passes
