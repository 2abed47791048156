"""Layer-2 detect: the telemetry quarantine state machine (program span
``detect.quarantine``), mean ms per round."""
from bench.metrics._spans import ms_per_round


def read(run):
    return ms_per_round(run, "detect.quarantine")
