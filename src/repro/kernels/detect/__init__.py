"""Streaming fleet-detect: spike score + persistence gate + onset in one
pass over the (hosts, window) latency slab — since PR 5 a single-tick view
of the shared batched sweep core (:mod:`repro.kernels.sweep`)."""
from repro.kernels.detect.ops import (
    detect_hosts, detect_hosts_slab_launch, detect_window_launch,
    persistence_count,
)

__all__ = ["detect_hosts", "detect_hosts_slab_launch",
           "detect_window_launch", "persistence_count"]
