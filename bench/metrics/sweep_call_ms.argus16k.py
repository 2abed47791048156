"""Layer-2 detect: the sweep's device call from the host->device put to
the last pull (program span ``detect.sweep``), mean ms per round."""
from bench.metrics._spans import ms_per_round


def read(run):
    return ms_per_round(run, "detect.sweep")
