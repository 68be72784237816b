"""Fullest the recurrent-state pool got: the most slots in use over the
window's step records (``state_slots_used``, written by the engine
where the model keeps a state beside its pages) as a percentage of the
pool (``state_slots_total``).  The twin of ``kv_pages_peak`` for the
state that is not paged."""

LAYER = "paged cache"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(run):
    used = [(s["state_slots_used"], s["state_slots_total"])
            for s in run.window_steps if s.get("state_slots_total")]
    if not used:
        return None
    return 100.0 * max(u for u, _ in used) / used[0][1]
