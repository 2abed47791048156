"""Sweep kernel's share of its roofline, in %: the least time the
window's sweep dispatches need (bench/roofline.py, from rows x window
samples) over the kernel's device time in the trace."""
from bench import roofline


def read(run):
    if run.trace is None:
        return None
    n, sec = run.trace.op_seconds(roofline.is_sweep_op)
    if not n or sec <= 0:
        return None
    pk = roofline.peaks(run.device_kind)
    least = sum(roofline.least_s(*roofline.sweep_cost(rows, run.wn), pk)
                for _ in run.rounds for rows in run.shard_rows)
    return 100.0 * least / sec
