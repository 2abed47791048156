"""Fused RCA kernel's share of its roofline, in %: the least time the
window's fused dispatches need (bench/roofline.py, from hosts x metrics x
lags x samples) over the kernel's device time in the trace."""
from bench import roofline


def read(run):
    if run.trace is None:
        return None
    n, sec = run.trace.op_seconds(roofline.is_fused_op)
    if not n or sec <= 0:
        return None
    g = run.geometry
    pk = roofline.peaks(run.device_kind)
    least = sum(roofline.least_s(*roofline.fused_cost(
        r["rca_n"], len(g.ev), g.rn, g.nb, g.K), pk)
        for r in run.rounds if r["rca_n"])
    return 100.0 * least / sec
