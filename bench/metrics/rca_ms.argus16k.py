"""Layer-3 RCA: ``stage_seconds`` gather + kernel + rank + assemble, mean
ms per round."""
from bench.metrics._stages import mean_ms


def read(run):
    return mean_ms(run, ("gather", "kernel", "rank", "assemble"))
