"""Live staging: bytes written into the staging buffers
(``AggregatorStats.staged_bytes``) in the window, MB (1e6 B) per round."""


def read(run):
    a, b = run.counters_before, run.counters_after
    if "staged_bytes" not in a or not run.rounds:
        return None
    return (b["staged_bytes"] - a["staged_bytes"]) / 1e6 / len(run.rounds)
