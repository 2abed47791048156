"""Live staging: share of staged rows advanced by a delta read
(``AggregatorStats.delta_reads`` over the rows staged in the window: delta
reads, full restages and unchanged skips), in %."""


def read(run):
    a, b = run.counters_before, run.counters_after
    if not a:
        return None
    d = {k: b[k] - a[k] for k in ("delta_reads", "full_restages",
                                  "unchanged_skips")}
    rows = sum(d.values())
    return 100.0 * d["delta_reads"] / rows if rows else None
