"""How unevenly the held experts are loaded in a decode step: tokens
on the busiest held expert over the mean tokens a held expert (the
program's ``moe_tokens_per_expert_max`` and ``_mean``, each a mean over
the layers and steps of a burst), averaged over the window's decode
burst records.  1 is an even load; the grouped product's tiles follow
the busiest."""

LAYER = "model + ops"
UNIT = "ratio"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(run):
    ratios = [s["moe_tokens_per_expert_max"] / s["moe_tokens_per_expert_mean"]
              for s in run.window_steps
              if s.get("kind") == "decode"
              and s.get("moe_tokens_per_expert_mean")]
    return sum(ratios) / len(ratios) if ratios else None
