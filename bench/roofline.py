"""Operations and bytes of the round's kernels, from the shapes the
decision needs (not padded lanes), and the least time the chip could take
for them: the larger of operations over peak FLOP/s and bytes over peak
bytes/s (``bench/peaks.json``, keyed by ``device_kind``; an unknown kind
is an error)."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Tuple

#: per window sample of a sweep decision: z = (x - mu) / sd (2), max (1),
#: threshold compare and count (2), first-hot onset (2)
SWEEP_OPS_PER_SAMPLE = 7


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def least_s(flops: float, nbytes: float, pk: dict) -> float:
    return max(flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"])


def sweep_cost(rows: int, wn: int) -> Tuple[float, float]:
    """One single-tick sweep over ``rows`` hosts' ``wn``-sample windows:
    reads the f32 window and per-row mean, sigma and valid length, writes
    fire, score, onset and marginal per row."""
    flops = float(rows) * wn * SWEEP_OPS_PER_SAMPLE
    nbytes = 4.0 * rows * wn + 4.0 * rows * 3 + 4.0 * rows * 4
    return flops, nbytes


def fused_cost(hosts: int, metrics: int, n: int, nb: int,
               max_lag: int) -> Tuple[float, float]:
    """One fused RCA dispatch: per (host, metric) baseline moments over
    ``nb`` samples (4 per sample), z and max over ``n`` (3 per sample),
    centring and norms (4 per sample), and 2K+1 lagged products over ``n``
    (2 per product); reads latency, window and baseline in f32, writes the
    score, max |rho| and lag."""
    lags = 2 * max_lag + 1
    per = 4.0 * nb + 7.0 * n + 2.0 * n * lags
    flops = float(hosts) * metrics * per
    nbytes = 4.0 * hosts * (n + metrics * n + metrics * nb) \
        + 4.0 * hosts * metrics * 3
    return flops, nbytes


#: the kernels' custom calls as the device trace names them today (the
#: instruction takes the name of the jitted function around the
#: ``pallas_call``: ``_sweep_jit`` in kernels/sweep/ops.py, ``fused_rca`` in
#: kernels/fused/ops.py)
SWEEP_OP = "_sweep_jit"
FUSED_OP = "fused_rca"


def is_sweep_op(name: str) -> bool:
    return name == SWEEP_OP


def is_fused_op(name: str) -> bool:
    return name == FUSED_OP
