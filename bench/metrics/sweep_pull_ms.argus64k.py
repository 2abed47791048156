"""Layer-2 detect: the host's wait on the chips for the sweeps' results
(program span ``sweep.pull``), mean ms per round."""
from bench.metrics._spans import ms_per_round


def read(run):
    return ms_per_round(run, "sweep.pull")
