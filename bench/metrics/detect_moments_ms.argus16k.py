"""Layer-2 detect: the incremental baseline moments (program span
``detect.moments``), mean ms per round."""
from bench.metrics._spans import ms_per_round


def read(run):
    return ms_per_round(run, "detect.moments")
