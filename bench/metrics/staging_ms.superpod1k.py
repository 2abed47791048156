"""Live staging: the aggregator's assemble, mean ms per round, from the
harness's own timer around the instance's ``assemble``."""


def read(run):
    v = [r["staging_s"] for r in run.rounds if "staging_s" in r]
    return 1e3 * sum(v) / len(v) if v else None
