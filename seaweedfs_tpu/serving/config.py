"""Knobs for the continuous-batching EC serving dispatcher.

COUNT_BUCKETS in ops/rs_resident.py tops out at 256 (a wider coalesce
would hit an uncompiled shape), `max_inflight` keeps several batches in
flight so the device is not idle through host round-trips, and an
admission window needs to be far below the ~ms batch service time to be
free.  None of the defaults has been tuned on the current chip.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass
class ServingConfig:
    """Tunables for `EcReadDispatcher` (CLI: the -ec.serving.* flags)."""

    # route EC reads of resident volumes through the batching dispatcher;
    # False serves every read on the native per-read path
    # (-ec.serving.disable)
    enabled: bool = True
    # widest coalesced batch; matches COUNT_BUCKETS[-1] so a full batch
    # is one already-warm device shape (-ec.serving.maxBatch)
    max_batch: int = 256
    # admission window: when a dispatch slot frees and the queue holds a
    # partial batch, wait this long for the batch to fill before
    # dispatching.  Only applied once a drain loop is already hot (the
    # first batch after idle dispatches immediately), so a lone request
    # never waits.  0 disables the window.  (-ec.serving.maxWaitUs)
    max_wait_us: int = 200
    # pipelined batches in flight: batch N+1's device dispatch overlaps
    # batch N's D2H + response fan-out; 4 was chosen before the chip
    # and no ledger line compares depths (-ec.serving.maxInflight)
    max_inflight: int = 4
    # backpressure: queued requests beyond this fall back to the native
    # per-read path (counted in the fallback metric) instead of growing
    # the queue without bound (-ec.serving.maxQueue)
    max_queue: int = 2048
    # resident shard layout the reconstruct kernels serve through:
    # "blockdiag" is the ~157 GB/s round-3 g=4 system (default — the
    # host stages the segment layout for free at pin time), "flat" the
    # plain kernel kept as fallback (-ec.serving.layout)
    layout: str = "blockdiag"
    # double-buffered device staging: 2 slots let batch N+1 pack and
    # ship while batch N executes (only N's D2H blocks N); False = one
    # slot, one batch in the device section at a time
    # (-ec.serving.overlap.disable)
    overlap: bool = True
    # AOT serving grid + cold-shape shed (-ec.serving.aot.disable):
    # warm plans compile ahead-of-time on a background executor, and a
    # read that would hit a still-cold device shape is served on the
    # host path (shed_cold_shape route) instead of stalling the
    # dispatcher 20-40s behind an inline compile.  False restores the
    # legacy trace-and-execute warm and inline compiles.
    aot: bool = True
    # pod-scale mesh residency (-ec.serving.mesh.disable): lane-shard
    # resident volumes across the local device mesh under
    # PartitionSpec("shard") so a volume's resident capacity is the
    # WHOLE mesh's HBM, not one chip's, and batched reconstruct lane
    # work runs 1/n per device.  False pins volumes whole onto the
    # default device (the pre-r19 layout).  Only takes effect when >1
    # local device is visible.
    mesh: bool = True
    # devices the serving mesh may span (-ec.serving.mesh.devices):
    # 0 = every local device, n = the first n
    mesh_devices: int = 0
    # volumes whose shard files are smaller than this pin whole onto
    # the least-loaded mesh device instead of lane-sharding
    # (-ec.serving.mesh.minShardMB): spreading a tiny volume across the
    # mesh buys no capacity and pays cross-device dispatch per batch
    mesh_min_shard_mb: int = 8
    # multi-controller pod mesh (-ec.mesh.coordinator /
    # -ec.mesh.processId / -ec.mesh.processCount): when processCount > 1
    # this volume server joins a single global mesh via
    # jax.distributed.initialize(coordinator, ...) as process
    # `processId`, and residency lane-shards across EVERY process's
    # devices (parallel.mesh.global_serving_mesh) instead of this
    # host's slice.  processCount == 1 (the default) never touches the
    # coordinator and degrades to the local serving mesh — nothing
    # changes for existing single-process deployments.  Validation is
    # startup-time (validated() below): a bad coordinator string or an
    # out-of-range processId must fail the process before it takes
    # traffic, not the first dispatch.
    mesh_coordinator: str = ""
    mesh_process_id: int = 0
    mesh_process_count: int = 1
    # zero-copy response writes (-ec.serving.zerocopy.disable): needle
    # payloads stay memoryviews over the reconstruct/pread buffers all
    # the way into the aiohttp body write; False restores the legacy
    # bytes-materializing path.
    # SeaweedFS_volumeServer_response_copy_bytes_total measures the
    # difference.
    zero_copy: bool = True
    # QoS admission control (-ec.qos.disable): per-tier queue budgets,
    # deadline-aware shedding, and a trip/recover breaker in front of
    # the coalescer (serving/qos.py).  False = the pre-r13 single
    # shared queue with only the max_queue backstop.
    qos: bool = True
    # per-tier queue budgets: how many requests of each tier may sit in
    # the coalescer at once (-ec.qos.interactiveQueue / -ec.qos.bulkQueue).
    # The defaults PARTITION max_queue (1792 + 256 = 2048), so a tier
    # budget always binds before the global backstop and bulk can never
    # crowd the front door out of the queue.
    qos_interactive_queue: int = 1792
    qos_bulk_queue: int = 256
    # deadline budgets (ms): a request whose ESTIMATED queue wait (EWMA
    # of recent per-needle service time x queue depth / pipeline width)
    # already exceeds its tier deadline sheds to the host path at
    # admission instead of timing out inside the queue.  0 disables
    # deadline shedding for the tier (-ec.qos.interactiveDeadlineMs /
    # -ec.qos.bulkDeadlineMs).
    qos_interactive_deadline_ms: int = 2000
    qos_bulk_deadline_ms: int = 20000
    # breaker: this many CONSECUTIVE sheds trip a tier's breaker
    # (fast-fail to host) for recoverSeconds, then half-open probe
    # (-ec.qos.tripAfter / -ec.qos.recoverSeconds)
    qos_trip_after: int = 64
    qos_recover_seconds: float = 1.0
    # heat-tiered residency ladder (serving/tiering.py): HBM -> pinned
    # host-RAM reconstruct cache -> disk, driven by decayed per-volume
    # read heat fed from the dispatcher's admission accounting.
    # -ec.tier.disable turns the ladder off (residency falls back to
    # the manual pin/unpin + blind LRU budget eviction).
    tier: bool = True
    # rebalance cadence of the volume server's tier loop
    # (-ec.tier.intervalSeconds); 0 disables the loop — rebalance() can
    # still be driven manually (tests, bench)
    tier_interval_seconds: float = 5.0
    # pinned host-RAM warm tier budget (-ec.tier.hostCacheMB); 0
    # disables the host tier, so demotions fall straight to disk
    tier_host_cache_mb: int = 0
    # heat decay half-life (-ec.tier.halfLifeSeconds): popularity is an
    # exponentially-decayed read counter, so idle volumes cool to zero
    tier_half_life_seconds: float = 60.0
    # hysteresis, promotion side (-ec.tier.promoteRatio): a swap needs
    # the candidate to out-heat the coldest eligible resident by this
    # factor — the demotion threshold sits promoteRatio BELOW the
    # promotion threshold, so equally hot volumes never flap
    tier_promote_ratio: float = 1.5
    # hysteresis, time side (-ec.tier.minResidencySeconds): a promoted
    # volume is not swap-eligible before this age; over-budget pressure
    # demotions ignore it (staying over budget would re-trigger the
    # blind LRU eviction the ladder replaces)
    tier_min_residency_seconds: float = 10.0
    # QoS weight of bulk-tier reads in the heat signal
    # (-ec.tier.bulkWeight): a background scan must not out-heat the
    # interactive front door's hot set
    tier_bulk_weight: float = 0.25
    # slow-client guard: per-response stall budget for streamed bodies =
    # stall_budget_seconds + body_bytes / (stall_min_rate_kbps KB/s); a
    # client draining slower than that is disconnected so it can't hold
    # the download byte-lease + needle buffers open
    # (-ec.qos.stallBudgetSeconds / -ec.qos.stallMinRateKBps, 0 budget
    # disables the guard)
    stall_budget_seconds: float = 30.0
    stall_min_rate_kbps: int = 64

    @property
    def max_wait_s(self) -> float:
        return self.max_wait_us / 1e6

    @property
    def multiprocess(self) -> bool:
        """True when this server is one member of a multi-controller
        pod mesh (residency spans hosts)."""
        return self.mesh_process_count > 1

    def stall_budget_for(self, nbytes: int) -> float:
        """Total seconds a streamed response of `nbytes` may take before
        the dribbling client is disconnected (0 = unbounded)."""
        if self.stall_budget_seconds <= 0:
            return 0.0
        return self.stall_budget_seconds + nbytes / (
            max(1, self.stall_min_rate_kbps) * 1024.0
        )

    @property
    def pipeline_slots(self) -> int:
        return 2 if self.overlap else 1

    def validated(self) -> "ServingConfig":
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        if self.max_queue < self.max_batch:
            raise ValueError("max_queue must be >= max_batch")
        if self.max_wait_us < 0:
            raise ValueError("max_wait_us must be >= 0")
        if self.layout not in ("flat", "blockdiag"):
            raise ValueError("layout must be 'flat' or 'blockdiag'")
        if self.mesh_devices < 0:
            raise ValueError("mesh_devices must be >= 0 (0 = all local)")
        if self.mesh_min_shard_mb < 0:
            raise ValueError("mesh_min_shard_mb must be >= 0")
        if self.mesh_process_count < 1:
            raise ValueError("mesh_process_count must be >= 1")
        if self.mesh_process_count > 1:
            # multi-controller: the coordinator handshake happens at
            # startup, so a malformed rendezvous config must die HERE
            host, sep, port = self.mesh_coordinator.rpartition(":")
            if not (sep and host and port.isdigit() and 0 < int(port) < 65536):
                raise ValueError(
                    "mesh_coordinator must be host:port when "
                    f"mesh_process_count > 1 (got {self.mesh_coordinator!r})"
                )
            if not 0 <= self.mesh_process_id < self.mesh_process_count:
                raise ValueError(
                    f"mesh_process_id {self.mesh_process_id} out of range "
                    f"for mesh_process_count {self.mesh_process_count}"
                )
        elif self.mesh_process_id != 0:
            raise ValueError(
                "mesh_process_id must be 0 when mesh_process_count is 1"
            )
        if self.qos_interactive_queue < 1 or self.qos_bulk_queue < 1:
            raise ValueError("qos tier queue budgets must be >= 1")
        if (
            self.qos_interactive_deadline_ms < 0
            or self.qos_bulk_deadline_ms < 0
        ):
            raise ValueError("qos deadlines must be >= 0 (0 disables)")
        if self.qos_trip_after < 1:
            raise ValueError("qos_trip_after must be >= 1")
        if self.qos_recover_seconds <= 0:
            raise ValueError("qos_recover_seconds must be > 0")
        if self.stall_min_rate_kbps < 1:
            raise ValueError("stall_min_rate_kbps must be >= 1")
        if self.tier_interval_seconds < 0:
            raise ValueError("tier_interval_seconds must be >= 0")
        if self.tier_host_cache_mb < 0:
            raise ValueError("tier_host_cache_mb must be >= 0")
        if self.tier_half_life_seconds <= 0:
            raise ValueError("tier_half_life_seconds must be > 0")
        if self.tier_promote_ratio < 1.0:
            raise ValueError(
                "tier_promote_ratio must be >= 1 (hysteresis margin)"
            )
        if self.tier_min_residency_seconds < 0:
            raise ValueError("tier_min_residency_seconds must be >= 0")
        if not 0.0 <= self.tier_bulk_weight <= 1.0:
            raise ValueError("tier_bulk_weight must be in [0, 1]")
        return self
