"""Mean per-round milliseconds of named ``FleetDiagnosis.stage_seconds``
entries; None when no round in the window has any of them."""


def mean_ms(run, names):
    if not any(n in r["stages"] for r in run.rounds for n in names):
        return None
    tot = sum(r["stages"].get(n, 0.0) for r in run.rounds for n in names)
    return 1e3 * tot / len(run.rounds)
