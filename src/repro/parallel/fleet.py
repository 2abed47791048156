"""Device placement for the sharded fleet monitor.

The monitor side of the distribution layer: where :mod:`sharding`/
:mod:`ctx` map *model* tensors onto the mesh, this module maps *telemetry
shards* — each shard of the (hosts, C, T) fleet slab runs its detect
dispatch on one device of a 1-D ``"shard"`` mesh.  On a single-device box
(CI, the CPU bench) every shard lands on the same device and the layout
degenerates to the single-slab path's placement; on a real multi-device
mesh the shards' sweeps dispatch onto distinct accelerators with no code
change in the monitor.

Placement never changes verdicts: the sweep's decision contract
(exact-f64 moments host-side, marginal ticks re-decided through the f64
oracle — see ``kernels/sweep/ops.py``) holds on every backend, so device
assignment here is purely a throughput/locality decision.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
from jax.sharding import Mesh


def monitor_devices(backend: Optional[str] = None) -> List[jax.Device]:
    """The device pool the sharded monitor schedules over.

    Defaults to every device of the default backend — the same pool the
    model mesh is built from.  A deployment that dedicates devices to
    monitoring passes a backend name.
    """
    return list(jax.devices(backend))


def fleet_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-D ``"shard"`` mesh over the monitor's device pool.

    One axis is all the monitor needs: shards are independent through
    detection (the rack→fleet reduce is a host-side candidate merge, not
    a collective), so there is no model/data axis split to express.
    """
    devs = list(devices) if devices is not None else monitor_devices()
    if not devs:
        raise ValueError("no devices available for the fleet mesh")
    import numpy as np
    return Mesh(np.array(devs), axis_names=("shard",))


def shard_devices(n_shards: int,
                  devices: Optional[Sequence[jax.Device]] = None,
                  ) -> List[jax.Device]:
    """Round-robin shard→device assignment over the pool.

    Returns a list of length ``n_shards``: shard ``i`` dispatches on
    ``devices[i % len(devices)]``.  Deterministic (no load balancing) so
    a round's placement — and therefore its performance profile — is
    reproducible run to run.  The sharded round keeps one shard in
    flight per distinct device of this list.
    """
    devs = list(devices) if devices is not None else monitor_devices()
    if not devs:
        raise ValueError("no devices available for shard placement")
    return [devs[i % len(devs)] for i in range(int(n_shards))]
