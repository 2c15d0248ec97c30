"""What one run carries from set-up to the window and the checks."""
from __future__ import annotations

from dataclasses import dataclass, field

from .cluster import check, say, series_sum


@dataclass
class Volume:
    vid: int
    role: str  # "main" is measured, "warmup" only warms the programs up
    sizes: list[int]
    dat_size: int
    base: str
    shard_size: int = 0
    kept_dat: str = ""


@dataclass
class Context:
    config: dict
    sizes: dict  # the configuration's sizes, or its rehearsal sizes
    seed: int
    enforce: bool  # False only in a CPU rehearsal
    control: str
    session: object = None
    cluster: object = None
    env: object = None  # the shell's CommandEnv
    volumes: list[Volume] = field(default_factory=list)

    def main_volumes(self) -> list[Volume]:
        return [v for v in self.volumes if v.role == "main"]

    def cluster_has_cache(self) -> bool:
        return "-ec.deviceCacheMB=0" not in self.cluster.volume_flags

    def check_bulk_on_device(self, before: dict, after: dict,
                             pipeline: str, workload: str) -> None:
        """Every batch of a bulk pipeline ran as a device dispatch (the
        device ledger's count under every device label but "host")."""
        batches = (series_sum(after, "ec_bulk_batches_total",
                              {"pipeline": pipeline})
                   - series_sum(before, "ec_bulk_batches_total",
                                {"pipeline": pipeline}))
        on_device = (
            series_sum(after, "device_dispatches_total",
                       {"workload": workload}, {"device": "host"})
            - series_sum(before, "device_dispatches_total",
                         {"workload": workload}, {"device": "host"}))
        say(f"{pipeline} counters: bulk_batches=+{int(batches)} "
            f"device_dispatches{{{workload}}}=+{int(on_device)}")
        check(batches > 0, f"{pipeline} ran no bulk pipeline batch")
        check(on_device == batches, f"{int(on_device)} of {int(batches)} "
              f"{pipeline} batches ran on the device")
