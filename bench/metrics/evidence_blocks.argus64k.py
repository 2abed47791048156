"""Layer-3 RCA: evidence blocks gathered per traced round, the
``hosts`` of the program's ``monitor.gather`` spans summed over the
window and divided by its rounds.  None where no such span carries
``hosts``."""
from bench.metrics._spans import load


def read(run):
    w = load(run)
    if w is None:
        return None
    st = w.stats().get("monitor.gather")
    if st is None or "hosts" not in st.meta:
        return None
    return st.meta["hosts"] / w.n_rounds
