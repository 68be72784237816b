"""Share of the prediction module's drafts that the target model
accepted: the program's ``accepted`` over its ``drafts`` (the burst's
own counters: drafts verified on live rows and drafts accepted, before
any truncation on the host), summed over the window's decode burst
records.  A burst iteration commits 1 + this many tokens a drafting
row.  None where no record carries the counters (a family that does
not draft, a parent whose program lacks them, drafting switched off).
"""

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(run):
    bursts = [s for s in run.window_steps
              if s.get("kind") == "decode" and s.get("drafts")]
    offered = sum(s["drafts"] for s in bursts)
    if not offered:
        return None
    return 100.0 * sum(s.get("accepted", 0) for s in bursts) / offered
