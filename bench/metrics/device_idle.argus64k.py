"""Device idle share of the traced window of whole rounds, in %, with
busy time averaged over the device planes that ran an operation (four
chips in ``argus64k-storm``)."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or t.n_devices == 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
