"""Rack->fleet reduce: ``stage_seconds["reduce"]``, mean ms per round."""
from bench.metrics._stages import mean_ms


def read(run):
    return mean_ms(run, ("reduce",))
