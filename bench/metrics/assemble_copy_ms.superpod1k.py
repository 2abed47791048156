"""Live staging: the aggregator's staging pass (program span
``assemble.copy``), mean ms per round."""
from bench.metrics._spans import ms_per_round


def read(run):
    return ms_per_round(run, "assemble.copy")
