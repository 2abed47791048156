"""Round wall time not covered by any ``stage_seconds`` entry (provider
views, verdict lifecycle, ordering), mean ms per round."""


def read(run):
    if not run.rounds:
        return None
    return 1e3 * sum(r["wall"] - sum(r["stages"].values())
                     for r in run.rounds) / len(run.rounds)
