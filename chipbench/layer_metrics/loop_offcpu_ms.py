"""How long a decode turn keeps the loop thread off a core while it has
work to do: over the window's decode turns, the sum over the thread's
own phases (``host_phases.LOOP_PHASES``: all but ``wait`` and ``idle``)
of the phase's wall less the thread's CPU clock over it
(``phases[p] - cpu[p]`` of the ``/debug/steps`` records), divided by
the number of those turns.  A sum and not a median of single records:
where the host's CPU clock advances in ticks (10 ms on the v5e hosts)
one record's difference is a tick off either way, and only the sum is
true.  It is the wait for the interpreter's lock, a block inside the
runtime's enqueue, or the scheduler of a host short of cores;
``front_cpu_share`` of the same window tells the first from the
others.  Not read from a program whose records have no ``cpu``."""

from chipbench.host_phases import LOOP_PHASES

LAYER = "engine loop + scheduler"
UNIT = "ms"
MOVES = "output_tok_s"
SOURCE = "program_span"


def read(run):
    turns = [s for s in run.window_steps
             if s.get("kind") == "decode" and "cpu" in s]
    if not turns:
        return None
    return sum(s["phases"].get(p, 0.0) - s["cpu"].get(p, 0.0)
               for s in turns for p in LOOP_PHASES) / len(turns)
