"""Env/config-driven tile-size selection for the Pallas kernels.

The fused, xcorr and detect kernels tile their grids by ``block_m`` (metric
rows per grid cell — hosts, for the detect kernel) and pad the lag axis to
``LAG_PAD`` lanes.  The defaults below are the shapes the kernels were
written against (DESIGN.md §6: bm=8 keeps the (bm + 2K + 2) x N x 4-byte
working set far under VMEM); on real TPU hardware the sweet spot depends on
the generation, so both are overridable without code edits:

    REPRO_BLOCK_M=16 REPRO_LAG_PAD=128 python -m benchmarks.run --only kernel
    REPRO_SWEEP_BLOCK_T=64 REPRO_SWEEP_BLOCK_R=4 ... # sweep kernel tile

``benchmarks/kernelbench.py`` sweeps the ``block_m`` candidates
(`kernel/tile_sweep/*` rows) so a hardware run has a starting grid; on the
CPU those rows time the Pallas interpreter, not the kernel.
"""
from __future__ import annotations

import os

DEFAULT_BLOCK_M = 8      # metric rows per (host, metric-block) grid cell
DEFAULT_LAG_PAD = 64     # lag output lanes (>= 2K+1, lane-aligned)
DEFAULT_SWEEP_BLOCK_T = 128   # evaluation ticks per sweep tile / ref block
DEFAULT_SWEEP_BLOCK_R = 8     # latency rows per sweep-kernel grid cell
DEFAULT_SHARD_HOSTS = 1024    # hosts per fleet-monitor shard slab
DEFAULT_RACK_SHARDS = 8       # shards per rack in the two-level reduce
DEFAULT_SHARD_TOPK = 16       # evidence candidates shipped per shard/rack
DEFAULT_REANCHOR_ROUNDS = 32  # rounds between exact-f64 moment re-anchors
DEFAULT_MOMENT_BLOCK = 64     # ticks per cached incremental-moment block

#: candidates the kernel microbench sweeps (hardware starting grid)
BLOCK_M_CANDIDATES = (4, 8, 16)


def _env_int(name: str, default: int, minimum: int = 1) -> int:
    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer")
    if v < minimum:
        raise ValueError(f"{name}={v} must be >= {minimum}")
    return v


def block_m(override: int | None = None) -> int:
    """Metric-block rows for the fused and xcorr kernels."""
    if override is not None:
        return int(override)
    return _env_int("REPRO_BLOCK_M", DEFAULT_BLOCK_M)


def lag_pad(max_lag: int, override: int | None = None) -> int:
    """Lag-axis padding: env/explicit override, floored at 2K+1."""
    pad = (int(override) if override is not None
           else _env_int("REPRO_LAG_PAD", DEFAULT_LAG_PAD))
    return max(pad, 2 * int(max_lag) + 1)


def sweep_block_t(override: int | None = None) -> int:
    """Evaluation ticks per Layer-2 sweep tile (``REPRO_SWEEP_BLOCK_T``).

    Bounds peak memory of the batched detection sweep: the (rows, ticks,
    wn) z-block is only ever materialized ``block_t`` ticks at a time, both
    in the masked-XLA reference (a ``lax.map`` step) and as the tick axis
    of one Pallas grid cell.  Larger tiles amortize dispatch overhead;
    smaller ones cap the VMEM working set (~``block_r * block_t * wn * 4``
    bytes per live intermediate).
    """
    if override is not None:
        return int(override)
    return _env_int("REPRO_SWEEP_BLOCK_T", DEFAULT_SWEEP_BLOCK_T)


def sweep_block_r(override: int | None = None) -> int:
    """Latency rows per sweep-kernel grid cell (``REPRO_SWEEP_BLOCK_R``).

    Each cell keeps its ``block_r`` full (row, T) latency series VMEM-
    resident and gathers the cell's tick windows from them, so the row
    tile bounds the resident-slab footprint (``block_r * T * 4`` bytes) on
    top of the tick-block working set above.
    """
    if override is not None:
        return int(override)
    return _env_int("REPRO_SWEEP_BLOCK_R", DEFAULT_SWEEP_BLOCK_R)


def shard_hosts(override: int | None = None) -> int:
    """Hosts per fleet-monitor shard slab (``REPRO_SHARD_HOSTS``).

    The sharded fleet monitor (monitor/shard.py) cuts the (hosts, C, T)
    fleet into contiguous slabs of at most this many hosts; each slab is
    one detect dispatch (one device placement on the mesh) and one
    evidence gather.  Bounds per-shard resident memory at
    ``shard_hosts * C * T * 4`` bytes — the knob that keeps 64k-host
    fleets feasible on a box that could never hold the full slab.
    """
    if override is not None:
        return int(override)
    return _env_int("REPRO_SHARD_HOSTS", DEFAULT_SHARD_HOSTS)


def rack_shards(override: int | None = None) -> int:
    """Shards per rack in the two-level reduce (``REPRO_RACK_SHARDS``).

    Shard candidate lists are merged rack-first, then rack winners merge
    at fleet level — the fan-in at each tree level stays at most
    ``rack_shards`` (resp. ``ceil(n_shards / rack_shards)``) instead of
    ``n_shards``.  Shapes the reduce topology only; verdicts are
    invariant to it (the merge order is deterministic and the candidate
    order is a total order).
    """
    if override is not None:
        return int(override)
    return _env_int("REPRO_RACK_SHARDS", DEFAULT_RACK_SHARDS)


def reanchor_rounds(override: int | None = None) -> int:
    """Rounds between exact-f64 moment re-anchors (``REPRO_REANCHOR_ROUNDS``).

    The incremental streaming-moment state (core/rolling.py) is rebuilt
    from scratch and bitwise-compared against the incrementally-maintained
    cache every this-many monitor rounds — the drift guard that turns
    "incremental must equal from-scratch" from a hope into a continuously
    re-proven invariant (``fleet/incremental_parity``).  Lower values
    re-prove more often at O(rows * bn) per re-anchor; the block-anchored
    design makes equality exact by construction, so the default re-checks
    sparsely.  Forced re-anchors (chaos rounds, agent restarts, checkpoint
    restores) ignore this cadence.
    """
    if override is not None:
        return int(override)
    return _env_int("REPRO_REANCHOR_ROUNDS", DEFAULT_REANCHOR_ROUNDS)


def moment_block(override: int | None = None) -> int:
    """Ticks per cached incremental-moment block (``REPRO_MOMENT_BLOCK``).

    The incremental moments partition the absolute tick axis into fixed
    blocks of this many ticks and cache one f64 (sum, sum-of-squares)
    pair per (host, block).  Each block entry is a pure function of that
    block's values at fixed absolute positions — which is what makes the
    incremental state bitwise-identical to a from-scratch rebuild.  A
    monitor round pays O(delta) new-block work plus O(bn / block)
    combine; smaller blocks shrink the per-round head/tail partial
    reductions (<= 2 * block ticks) while growing the combine fan-in.
    """
    if override is not None:
        return int(override)
    return _env_int("REPRO_MOMENT_BLOCK", DEFAULT_MOMENT_BLOCK)


def shard_topk(override: int | None = None) -> int:
    """Evidence candidates shipped per shard/rack (``REPRO_SHARD_TOPK``).

    The deployment default for the ``rca_top_k`` cap a sharded fleet
    passes to its monitor (the bench's storm rows and the operations
    runbook use it): each shard then ships evidence blocks for at most
    this many of its worst flagged hosts, and each rack forwards at most
    this many of its shards' union — the bound that keeps cross-shard
    traffic at candidates, never raw telemetry, during an incident
    storm.  Not applied implicitly: a ``ShardedFleetMonitor`` built
    without ``rca_top_k`` explains every flagged host, exactly like the
    single-slab monitor it must stay byte-exact against.
    """
    if override is not None:
        return int(override)
    return _env_int("REPRO_SHARD_TOPK", DEFAULT_SHARD_TOPK)
