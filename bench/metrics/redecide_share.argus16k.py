"""Layer-2 detect: share of the swept rows re-decided through the f64
oracle (rows of ``detect.redecide`` over rows of ``detect.sweep``), in %."""
from bench.metrics._spans import load


def read(run):
    w = load(run)
    if w is None:
        return None
    st = w.stats()
    swept = st["detect.sweep"].meta.get("rows", 0.0) \
        if "detect.sweep" in st else 0.0
    if not swept:
        return None
    redecided = st["detect.redecide"].meta.get("rows", 0.0) \
        if "detect.redecide" in st else 0.0
    return 100.0 * redecided / swept
