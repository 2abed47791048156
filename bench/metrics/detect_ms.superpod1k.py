"""Layer-2 detect: ``stage_seconds["detect"]``, mean ms per round."""
from bench.metrics._stages import mean_ms


def read(run):
    return mean_ms(run, ("detect",))
