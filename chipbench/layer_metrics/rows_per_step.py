"""Mean live (non-pad) rows per step over the window's step records:
decode rows of a decode burst, prefill rows of a prefill step."""

LAYER = "engine loop + scheduler"
UNIT = "rows"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(run):
    rows = [s.get("decode_rows", 0) + s.get("prefill_rows", 0)
            for s in run.window_steps if "kind" in s]
    return sum(rows) / len(rows) if rows else None
