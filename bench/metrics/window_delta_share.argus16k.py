"""Layer-2 detect: share of the shards' sweep launches whose device
window took a delta put (``put`` 1 on the launch half of
``detect.sweep``: only the ticks that slid in were put), in %.  None
where no launch carries ``put``."""
from bench.metrics._spans import load

DELTA = 1


def read(run):
    w = load(run)
    if w is None:
        return None
    puts = [m["put"] for name, _, _, _, m in w.spans
            if name == "detect.sweep" and "put" in m]
    if not puts:
        return None
    return 100.0 * sum(p == DELTA for p in puts) / len(puts)
