"""Shard placement: mean number of sweeps launched and not yet pulled
when a pull begins (``in_flight`` on the program span ``sweep.pull``):
1.0 when the chips work one after another, up to the number of chips
when they work side by side.  None where no pull carries it."""
from bench.metrics._spans import load


def read(run):
    w = load(run)
    if w is None:
        return None
    n = [m["in_flight"] for name, _, _, _, m in w.spans
         if name == "sweep.pull" and "in_flight" in m]
    return sum(n) / len(n) if n else None
