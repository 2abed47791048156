"""Layer-3 RCA: the evidence gather, ms per traced round: the summed
time of the program's ``monitor.gather`` spans (every one of a round, at
the shard or the fleet level).  None where no such span."""
from bench.metrics._spans import ms_per_round


def read(run):
    return ms_per_round(run, "monitor.gather")
