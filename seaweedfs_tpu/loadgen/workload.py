"""Workload shapes for the load harness: key skew and client behavior.

Pure functions + a dataclass — no sockets — so the skew math and the
scenario knobs are unit-testable without a cluster.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class LoadScenario:
    """One load level: how many clients, how they pick keys, and how
    adversarially they behave on the wire."""

    connections: int  # concurrent closed-loop clients
    reads: int  # total reads across all clients this level
    # key skew: zipf exponent over the key popularity ranks; 0 = uniform.
    # ~1.1 models a CDN-ish hot set (a handful of keys take most reads)
    zipf_s: float = 1.1
    # hot-volume contention: this fraction of reads is forced onto keys
    # of ONE volume (the first key's volume), so per-volume batching and
    # the dispatcher queue see a genuinely contended volume
    hot_volume_frac: float = 0.0
    # slow clients: this fraction of connections drains responses in
    # dribble_chunk pieces with dribble_delay_s sleeps between them —
    # the client the per-response stall budget exists for
    slow_client_frac: float = 0.0
    dribble_chunk: int = 512
    dribble_delay_s: float = 0.02
    # connection churn: probability a client tears down its session and
    # reconnects (fresh TCP + TLS-less handshake) before a read
    churn: float = 0.0
    # QoS tier stamped on requests (X-Seaweed-QoS)
    tier: str = "interactive"
    # mixed read/write: this fraction of ops are uploads (the reference
    # `weed benchmark` write leg), with payload sizes drawn uniformly
    # from write_sizes — a discrete size distribution, one entry = the
    # reference's fixed -size.  Every written key feeds straight back
    # into the read key stream and is byte-verified like a pre-filled
    # key.  0 = the pure-read sweeps above.
    write_frac: float = 0.0
    write_sizes: list = field(default_factory=lambda: [4096])
    # working-set multiplier: how many times the device (HBM) budget
    # the key space is meant to span.  The sizing hook for
    # oversubscribed sweeps — `loadtest -oversubscribe N` scales its
    # fill phase by it (a caller may instead shrink the cache budget
    # to working_set/oversubscribe) — so a 4x-over-budget sweep
    # needs no hand-edited volume counts.  1.0 = the working set fits.
    oversubscribe: float = 1.0
    # byte-verify every response against the expected blob
    verify: bool = True
    seed: int = 1337
    # fault schedule (the chaos axis churn alone can't express: churn
    # reconnects CLIENTS, this kills a SERVER that may stay dead):
    # `kill_at` seconds into the sweep the harness abruptly stops
    # volume server `fault_target`; `revive_at` (optional, > kill_at)
    # brings it back.  kill_at set with revive_at None = the server
    # dies and STAYS dead mid-sweep — the repair scheduler's case.
    # The loadgen drivers don't act on these themselves: the chaos
    # harness (loadgen/chaos.py run_with_faults) executes the schedule
    # next to the driven load, so plain churn scenarios and the chaos
    # harness share one workload model.
    kill_at: float | None = None
    revive_at: float | None = None
    fault_target: int = 0
    # COMPOSABLE fault schedule (r18): arbitrary injector actions next
    # to (or instead of) the kill/revive pair, so one scenario can
    # express hang + slow-disk + partition together.  Each entry is
    # (seconds_into_sweep, action, kwargs); `action` names a
    # ChaosInjector verb ("kill", "revive", "partition",
    # "heal_partition", "slow_disk", "hang_shard_reads",
    # "stall_shard_reads", "delay_shard_reads", "flaky_shard_reads",
    # "corrupt_shard"), kwargs are passed through (an absent "idx"
    # defaults to `fault_target`).  Executed by
    # loadgen/chaos.py run_with_faults.
    faults: list = field(default_factory=list)
    # populated by callers that know the key->volume mapping
    extra: dict = field(default_factory=dict)

    def fault_events(self) -> list[tuple[float, str]]:
        """The validated kill/revive pair: sorted [(seconds_into_sweep,
        "kill"|"revive")].  Empty when no fault is scheduled."""
        if self.kill_at is None:
            if self.revive_at is not None:
                raise ValueError("revive_at requires kill_at")
            return []
        if self.kill_at < 0:
            raise ValueError("kill_at must be >= 0")
        events = [(float(self.kill_at), "kill")]
        if self.revive_at is not None:
            if self.revive_at <= self.kill_at:
                raise ValueError("revive_at must be > kill_at")
            events.append((float(self.revive_at), "revive"))
        return events

    def fault_schedule(self) -> list[tuple[float, str, dict]]:
        """The FULL composed schedule: the kill/revive pair merged with
        `faults`, validated and time-sorted — what run_with_faults
        executes.  Stable under ties: same-time events run in the order
        they were declared."""
        events: list[tuple[float, str, dict]] = [
            (at, action, {}) for at, action in self.fault_events()
        ]
        for entry in self.faults:
            if len(entry) == 2:
                at, action = entry
                kwargs: dict = {}
            else:
                at, action, kwargs = entry
            if at < 0:
                raise ValueError(f"fault at {at} must be >= 0")
            if not isinstance(kwargs, dict):
                raise ValueError(f"fault kwargs must be a dict: {entry!r}")
            events.append((float(at), str(action), dict(kwargs)))
        events.sort(key=lambda e: e[0])
        return events


def zipf_ranks(n_keys: int, n_samples: int, s: float, rng) -> np.ndarray:
    """Sample `n_samples` key indices in [0, n_keys) with popularity
    rank r drawn ∝ 1/(r+1)^s (s=0 → uniform).  Deterministic under the
    caller's rng, bounded (unlike numpy's unbounded zipf sampler), and
    O(n_keys) memory."""
    if n_keys <= 0:
        raise ValueError("n_keys must be >= 1")
    if s <= 0:
        return rng.integers(0, n_keys, size=n_samples)
    weights = 1.0 / np.power(np.arange(1, n_keys + 1, dtype=np.float64), s)
    weights /= weights.sum()
    return rng.choice(n_keys, size=n_samples, p=weights)


def plan_keys(
    keys: list[str],
    scenario: LoadScenario,
    volume_of=None,
) -> list[str]:
    """The full per-level read sequence: zipf-skewed key picks, with
    `hot_volume_frac` of them re-pinned onto the hottest volume's keys
    when a `volume_of(key)` mapping is supplied."""
    rng = np.random.default_rng(scenario.seed)
    idx = zipf_ranks(len(keys), scenario.reads, scenario.zipf_s, rng)
    picks = [keys[i] for i in idx]
    if scenario.hot_volume_frac > 0 and volume_of is not None:
        by_vol: dict = {}
        for k in keys:
            by_vol.setdefault(volume_of(k), []).append(k)
        hot_keys = max(by_vol.values(), key=len)
        hot_mask = rng.random(len(picks)) < scenario.hot_volume_frac
        hot_picks = zipf_ranks(
            len(hot_keys), int(hot_mask.sum()), scenario.zipf_s, rng
        )
        j = 0
        for i, hot in enumerate(hot_mask):
            if hot:
                picks[i] = hot_keys[hot_picks[j]]
                j += 1
    return picks


class ZipfPicker:
    """One-at-a-time zipf sampler over a GROWING key space — the mixed
    read/write driver's read-side picker, where every freshly written
    key joins the popularity tail mid-sweep (plan_keys can't: it needs
    the whole key space upfront).  The weight vector is recomputed only
    when the space has grown, so a sweep whose keys grow by W writes
    pays O(W) rebuilds, not one per read."""

    def __init__(self, s: float):
        self.s = s
        self._n = 0
        self._weights: np.ndarray | None = None

    def pick(self, n_keys: int, rng) -> int:
        if n_keys <= 0:
            raise ValueError("n_keys must be >= 1")
        if self.s <= 0:
            return int(rng.integers(0, n_keys))
        if n_keys != self._n:
            w = 1.0 / np.power(
                np.arange(1, n_keys + 1, dtype=np.float64), self.s
            )
            self._weights = w / w.sum()
            self._n = n_keys
        return int(rng.choice(n_keys, p=self._weights))


def percentile_ms(latencies_s: list[float], p: float) -> float | None:
    """Client-side latency percentile in ms (None when no samples)."""
    if not latencies_s:
        return None
    xs = sorted(latencies_s)
    i = min(len(xs) - 1, int(p / 100.0 * len(xs)))
    return round(xs[i] * 1e3, 3)
