"""Continuous-batching dispatch for the device-resident EC read path.

When each coalesced batch runs to completion (device call + D2H +
per-needle HTTP responses) before the next batch dispatches, the device
idles through every host round-trip and the binding constraint is
dispatch software, not bytes.  This package grafts the inference-serving
fix — continuous batching — onto the storage read path:

  * `Coalescer` packs concurrent needle reads for the same resident
    EcVolume into wide `read_needles_batch` calls (tunable max batch
    width and a µs-scale max-wait admission window);
  * `EcReadDispatcher` keeps several batches in flight (bounded depth):
    batch N+1 dispatches while batch N's reconstructed bytes are still
    on their way back to the host, and saturation falls back to the native
    per-read path instead of queuing unboundedly;
  * per-batch Prometheus series (stats/metrics.py) make batch width,
    queue wait, device occupancy, and fallbacks dashboard-visible.

Reference path being outperformed: the per-needle goroutine fan-in of
weed/storage/store_ec.go:339-393.
"""
from .config import ServingConfig
from .coalescer import Coalescer, ReadRequest
from .dispatcher import EcReadDispatcher
from .qos import Breaker, QosController, normalize_tier
from .tiering import HeatTracker, HostShardCache, TieringController

__all__ = [
    "Breaker",
    "Coalescer",
    "EcReadDispatcher",
    "HeatTracker",
    "HostShardCache",
    "QosController",
    "ReadRequest",
    "ServingConfig",
    "TieringController",
    "normalize_tier",
]
