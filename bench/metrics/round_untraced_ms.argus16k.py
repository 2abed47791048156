"""Round time under no program span but the round's own
(``monitor.round``), mean ms per round."""
from bench.metrics._spans import load


def read(run):
    w = load(run)
    if w is None:
        return None
    return 1e3 * w.untraced_s(("monitor.round",)) / w.n_rounds
