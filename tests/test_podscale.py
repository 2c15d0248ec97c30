"""True pod scale (r23): multi-process resident serving over
`jax.distributed`, at four depths:

  * degrade equality — a single-process `global_serving_mesh` resolves
    to EXACTLY the local serving mesh (same devices, same width-1
    None degrade), and a `global_mesh=True` DeviceShardCache keeps the
    full r19 surface (n_hosts=1, every lane local, byte-equal
    reconstructs against the local-mesh cache and the numpy oracle);
  * the pod program itself — `cache.multiprocess = True` forces the
    replicated-output all_gather reconstruct path (the kernel every
    host of a real pod runs) on the conftest's 8-device mesh, still
    byte-exact (the check_rep=False replication-inference regression);
  * host-aware placement — with device_host split 4|4, whole pins land
    only on THIS process's lanes while the mesh claim for big shards
    stays a pure function of size (identical on every host);
  * a real 2-process boundary — two `tests/podscale_worker.py`
    subprocesses join over `jax.distributed.initialize` on a CPU mesh
    and each byte-verifies the lanes it owns; a killed pod member then
    escalates the repair planner (pod_exposed), `_avoid_pods` spreads
    replicas off the pod, a hedge prefers spares outside the slow
    peer's pod, the master's health doc flags the degraded pod row,
    and the `-ec.mesh.*` config fast-fails bad wiring at startup.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs, rs_resident
from seaweedfs_tpu.parallel import mesh as mesh_mod
from seaweedfs_tpu.pb import master_pb2
from seaweedfs_tpu.repair import planner
from seaweedfs_tpu.serving.config import ServingConfig
from seaweedfs_tpu.stats.cluster import ClusterTelemetry
from seaweedfs_tpu.topology.volume_growth import _avoid_pods
from seaweedfs_tpu.utils import faultpolicy as fp

N_DEV = 8


@pytest.fixture(scope="module")
def encoded():
    """One 64KB-shard volume's 14 shards + the numpy oracle."""
    rng = np.random.default_rng(2023)
    data = rng.integers(0, 256, size=(10, 64 * 1024), dtype=np.uint8)
    return rs.RSCodec(backend="numpy").encode_all(data)


def _pod_cache(**kw):
    kw.setdefault("shard_quantum", 1 << 18)
    kw.setdefault("mesh_devices", 0)
    kw.setdefault("mesh_min_shard_bytes", 0)
    kw.setdefault("global_mesh", True)
    c = rs_resident.DeviceShardCache(**kw)
    c.warm_sizes = ()  # CI convention: no AOT grid compile unless asked
    return c


# ------------------------------------------------- single-process degrade


class TestGlobalMeshDegrade:
    def test_global_mesh_matches_local_single_process(self):
        g = mesh_mod.global_serving_mesh(0)
        l = mesh_mod.serving_mesh(0)
        assert g is not None and l is not None
        assert g.axis_names == l.axis_names == (mesh_mod.SHARD_AXIS,)
        assert list(g.devices.flat) == list(l.devices.flat), (
            "single-process global mesh must resolve to the exact "
            "local device order — existing deployments see no change"
        )

    def test_global_mesh_width1_degrades_to_none(self):
        # same `_serving_mesh_or_none` rule as the local constructor
        assert mesh_mod.global_serving_mesh(1) is None

    def test_global_cache_keeps_the_r19_surface(self):
        c = _pod_cache()
        assert c.n_devices == N_DEV
        assert c.n_hosts == 1
        assert c.multiprocess is False
        assert c._local_dev_indices == list(range(N_DEV))
        # mesh claims spread over the full pod width
        plan = c.plan_pin(14, 1 << 20)
        assert set(plan) == set(range(N_DEV))

    def test_global_vs_local_reconstruct_byte_equal(self, encoded):
        reqs = [(3, 0, 1000), (3, 5000, 4096), (0, 111, 3333)]
        pieces = []
        for global_mesh in (True, False):
            c = _pod_cache(global_mesh=global_mesh)
            for sid in range(14):
                if sid != 3:
                    c.put(51, sid, encoded[sid])
            assert c.placement(51) == "mesh"
            pieces.append(rs_resident.reconstruct_intervals(c, 51, reqs))
        for (sid, off, size), g_piece, l_piece in zip(
            reqs, pieces[0], pieces[1]
        ):
            oracle = encoded[sid][off : off + size].tobytes()
            assert g_piece == oracle, f"global mesh wrong at sid={sid}"
            assert l_piece == oracle, f"local mesh wrong at sid={sid}"


# --------------------------------------------------- pod program (forced)


class TestPodProgramKernel:
    def test_forced_multiprocess_reconstruct_byte_equal(self, encoded):
        """`multiprocess = True` routes staging through
        make_array_from_process_local_data and reconstructs through the
        replicated-output all_gather kernel — the program every host of
        a real pod executes in lockstep.  Single-process it must stay
        byte-exact (and this anchors the check_rep=False fix: the
        replicated out_specs can't satisfy static replication
        inference, so a regression here is an XLA error, not a silent
        wrong answer)."""
        c = _pod_cache()
        c.multiprocess = True  # pod-program emulation, one process
        for sid in range(14):
            if sid != 5:
                c.put(52, sid, encoded[sid])
        assert c.placement(52) == "mesh"
        reqs = [(5, 0, 2048), (5, 60000, 4000), (1, 7, 1009)]
        got = rs_resident.reconstruct_intervals(c, 52, reqs)
        for (sid, off, size), piece in zip(reqs, got):
            assert piece == encoded[sid][off : off + size].tobytes(), (
                f"pod program mismatch at sid={sid} off={off}"
            )


# ------------------------------------------------- host-aware placement


class TestHostAwarePlacement:
    @pytest.fixture()
    def split_hosts(self, monkeypatch):
        """Pretend the 8-device mesh spans two 4-lane hosts (devices
        0-3 ours, 4-7 the peer's).  The lru-cached mesh object is
        host-agnostic, so only DeviceShardCache.__init__'s ownership
        bookkeeping sees the split."""
        monkeypatch.setattr(
            mesh_mod, "device_host", lambda d: 0 if d.id < 4 else 1
        )

    def test_whole_pins_stay_host_local(self, split_hosts, encoded):
        c = _pod_cache(mesh_min_shard_bytes=1 << 30)  # never mesh
        assert c.n_hosts == 2 and c.multiprocess
        assert c._local_dev_indices == [0, 1, 2, 3]
        for vid in (61, 62, 63):
            for sid in range(3):
                c.put(vid, sid, encoded[sid])
        for vid in (61, 62, 63):
            place = c.placement(vid)
            assert place in (0, 1, 2, 3), (
                f"whole pin for vid {vid} landed on a peer host's "
                f"lane ({place!r}) — unaddressable in a real pod"
            )
        arr = c.get(61, 0)
        got = np.asarray(arr)[: encoded[0].size]
        assert np.array_equal(got, encoded[0])

    def test_mesh_claim_is_pure_function_of_size(self, split_hosts):
        """Big shards claim "mesh" from EVERY host — the claim must be
        a pure function of the shard size so pod members agree on the
        layout without coordination (one volume never straddles)."""
        c = _pod_cache(mesh_min_shard_bytes=1 << 20)
        big = c.plan_pin(14, 2 << 20)
        assert set(big) == set(range(N_DEV)), "mesh spread, all lanes"
        small = c.plan_pin(14, 1 << 10)
        assert set(small) <= {0, 1, 2, 3}, "small pin stays host-local"


# ---------------------------------------------- real 2-process boundary


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(n_local_devices: int) -> dict:
    env = dict(os.environ)
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append(
        f"--xla_force_host_platform_device_count={n_local_devices}"
    )
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    return env


N_POD_VOLUMES = 2


def _spawn_pod(process_count: int, hold: bool) -> list:
    """Start `process_count` real `tests/podscale_worker.py` processes
    (4 forced CPU devices each) that join over
    `jax.distributed.initialize` and stage the same seeded working set
    in SPMD lockstep; with `hold` each keeps its lanes after reporting."""
    worker = os.path.join(os.path.dirname(__file__), "podscale_worker.py")
    port = _free_port()
    return [
        subprocess.Popen(
            [
                sys.executable,
                worker,
                json.dumps({
                    "process_id": rank,
                    "process_count": process_count,
                    "coordinator": f"127.0.0.1:{port}",
                    "n_volumes": N_POD_VOLUMES,
                    "shard_kb": 16,
                    "seed": 20260808,
                    "hold": hold,
                }),
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            env=_worker_env(4),
            text=True,
        )
        for rank in range(process_count)
    ]


def _reports(procs: list) -> dict:
    """rank -> the one JSON line each worker prints.  A worker that dies
    first fails the test with its stderr; one that hangs is killed at
    240 s, which ends its stdout and fails the same way."""
    watchdog = threading.Timer(240, lambda: [p.kill() for p in procs])
    watchdog.start()
    try:
        by_rank = {}
        for p in procs:
            line = p.stdout.readline()
            if not line.strip():
                p.kill()
                _, err = p.communicate()
                raise AssertionError(f"worker failed:\n{err[-2000:]}")
            report = json.loads(line)
            by_rank[report["rank"]] = report
    finally:
        watchdog.cancel()
    assert set(by_rank) == set(range(len(procs)))
    return by_rank


def _reap(procs: list) -> None:
    for p in procs:
        if p.poll() is None:
            p.kill()
        p.communicate()


def test_two_process_mesh_spans_hosts_and_byte_verifies():
    """Two processes, each byte-verifying every lane it owns, must
    present one 8-lane pod: disjoint local lanes covering the full
    mesh, zero mismatches — and the pod holds the whole working set
    (the per-chip budget is sized so that eight lanes hold exactly it)
    without one eviction.  Rank 1 is then SIGKILLed while it holds its
    lanes: it dies of the signal, not of a fault of its own."""
    procs = _spawn_pod(2, hold=True)
    try:
        by_rank = _reports(procs)
        procs[1].kill()
        assert procs[1].wait(timeout=60) == -signal.SIGKILL
        assert procs[0].poll() is None, "the survivor went down with it"
    finally:
        _reap(procs)
    for o in by_rank.values():
        assert o["n_devices"] == N_DEV, "each member sees the POD mesh"
        assert o["n_hosts"] == 2 and o["multiprocess"]
        assert o["all_mesh_placed"]
        assert o["lanes_checked"] > 0
        assert o["lane_mismatches"] == 0, "cross-host lane bytes wrong"
        assert o["resident_volumes"] == N_POD_VOLUMES
        assert o["evictions"] == 0
    lanes0 = set(by_rank[0]["local_lanes"])
    lanes1 = set(by_rank[1]["local_lanes"])
    assert lanes0 | lanes1 == set(range(N_DEV))
    assert not (lanes0 & lanes1), "hosts must own disjoint lanes"


def test_one_process_sheds_the_working_set_the_pod_holds():
    """Capacity scales with process count: the same working set under
    the same per-chip budget on ONE process (four lanes) cannot stay
    resident — it evicts and ends with fewer whole volumes — while its
    lanes still byte-verify."""
    procs = _spawn_pod(1, hold=False)
    try:
        (o,) = _reports(procs).values()
        assert procs[0].wait(timeout=60) == 0
    finally:
        _reap(procs)
    assert o["n_devices"] == N_DEV // 2 and o["n_hosts"] == 1
    assert not o["multiprocess"]
    assert o["evictions"] > 0
    assert o["resident_volumes"] < N_POD_VOLUMES
    assert o["lane_mismatches"] == 0


# ------------------------------------------- killed member -> repair plane


class TestPodFailureDomain:
    def test_pod_exposure_escalates_the_planner(self):
        """All healthy survivors inside ONE pod: a single correlated
        host failure is data loss, so the job is critical even at
        healthy=11 — the same census without pod info is not."""
        h0, h1 = "pod-h0:8080", "pod-h1:8080"
        shards = {sid: h0 for sid in range(11)}
        shards.update({sid: h1 for sid in range(11, 14)})
        pods = {h0: "podA", h1: "podA"}
        planned = planner.plan(
            {900: shards}, stale_nodes=frozenset({h1}), node_pods=pods
        )
        job = planned.jobs[0]
        assert job.pod_exposed and job.critical
        assert job.healthy == 11 > planner.DATA_SHARDS
        assert set(job.rescue) == {11, 12, 13}
        control = planner.plan({900: shards}, stale_nodes=frozenset({h1}))
        assert not control.jobs[0].critical
        assert not control.jobs[0].pod_exposed

    def test_survivors_across_pods_are_not_exposed(self):
        h0, h1 = "pod-h0:8080", "pod-h1:8080"
        shards = {sid: (h0 if sid < 7 else h1) for sid in range(14)}
        pods = {h0: "podA", h1: "podB"}
        planned = planner.plan({901: shards}, node_pods=pods)
        assert not planned.jobs, "healthy volume spread over two pods"
        assert planned.healthy_vids == [901]

    def test_avoid_pods_spreads_and_falls_back(self):
        a1 = SimpleNamespace(mesh_pod="podA")
        a2 = SimpleNamespace(mesh_pod="podA")
        b = SimpleNamespace(mesh_pod="podB")
        solo = SimpleNamespace(mesh_pod="")
        # a podA member already chosen: podA candidates are filtered
        assert _avoid_pods([a2, b, solo], [a1]) == [b, solo]
        # nothing chosen yet (or only pod-less nodes): no filtering
        assert _avoid_pods([a1, a2, b], [solo]) == [a1, a2, b]
        # every candidate shares the chosen pod: fall back to all of
        # them — anti-affinity must never make placement impossible
        assert _avoid_pods([a2], [a1]) == [a2]


# --------------------------------------------------- hedge anti-affinity


@pytest.fixture()
def fresh_policy():
    prev = fp.CONFIG
    fp.PEER_LATENCY.reset()
    fp.RETRY_BUDGETS.reset()
    fp.HEDGE_BUDGET.reset()
    fp.reset_totals()
    yield fp
    fp.configure(prev)
    fp.PEER_LATENCY.reset()
    fp.RETRY_BUDGETS.reset()
    fp.HEDGE_BUDGET.reset()
    fp.reset_totals()


def test_hedge_prefers_spare_outside_the_slow_pod(fresh_policy):
    """When a pod member goes tail-slow its siblings are suspect too
    (one host serves them all), so the hedge spare should come from a
    DIFFERENT pod when one is available."""
    fp.configure(
        fp.FaultPolicyConfig(hedge_quantile=0.95, hedge_budget_pct=100.0)
    )
    peers = {0: "p0", 1: "p1", 2: "p2", 3: "p3"}
    pods = {0: "podA", 1: "podB", 2: "podA", 3: "podB"}
    rng = np.random.default_rng(9)
    # primaries (0, 1) look cheap, spares (2, 3) dearer — sid 0 is
    # deterministically a primary and 2/3 are the spare pool.  The
    # history says 50 ms a fetch and the healthy ones return at once,
    # so on a loaded worker it is still sid 0 alone that crosses its
    # quantile; sid 0 hangs until the gather is over
    for p, base in (("p0", 0.05), ("p1", 0.05), ("p2", 0.1), ("p3", 0.1)):
        for _ in range(30):
            fp.PEER_LATENCY.observe(p, base * (0.75 + 0.5 * rng.random()))
    pool = ThreadPoolExecutor(8)
    gathered = threading.Event()

    def one_slow(sid):
        if sid == 0:
            gathered.wait(10)
        return b"d%d" % sid

    res = fp.hedged_gather(
        2, [0, 1, 2, 3], one_slow, pool=pool,
        peer_of=peers.get, pod_of=pods.get,
    )
    gathered.set()
    pool.shutdown(wait=True)
    assert len(res.got) == 2 and 0 not in res.got
    assert 3 in res.got, "spare must come from outside the slow pod"
    assert 2 not in res.got, "same-pod spare 2 should not be preferred"


# --------------------------------------------------- master health plane


class TestHealthPodTable:
    def test_pod_row_goes_degraded_when_a_member_goes_stale(self):
        ct = ClusterTelemetry(pulse_seconds=1.0)
        for rank, url in enumerate(("h0:8080", "h1:8080")):
            tel = master_pb2.VolumeServerTelemetry(
                mesh_process_id=rank, mesh_process_count=2
            )
            ct.observe(url, tel, now=100.0, mesh_pod="pod0")
        doc = ct.health(now=100.5)
        pod = doc["pods"]["pod0"]
        assert pod["process_count"] == 2
        assert pod["live_members"] == 2
        assert not pod["degraded"]
        # rank 1 stops pulsing (the SIGKILLed member) — past the
        # staleness window its pod row flips to degraded even though
        # rank 0 is still live: one member down stalls the SPMD mesh
        tel0 = master_pb2.VolumeServerTelemetry(
            mesh_process_id=0, mesh_process_count=2
        )
        ct.observe("h0:8080", tel0, now=104.0, mesh_pod="pod0")
        doc = ct.health(now=104.5)
        pod = doc["pods"]["pod0"]
        assert pod["live_members"] == 1
        assert pod["degraded"]
        stale_by_url = {m["url"]: m["stale"] for m in pod["members"]}
        assert stale_by_url == {"h0:8080": False, "h1:8080": True}

    def test_podless_cluster_has_no_pods_key(self):
        ct = ClusterTelemetry(pulse_seconds=1.0)
        ct.observe("solo:8080", None, now=50.0)
        assert "pods" not in ct.health(now=50.5), (
            "single-process health docs must stay byte-identical"
        )


# -------------------------------------------------------- config wiring


class TestMeshConfigValidation:
    def test_multi_process_requires_a_coordinator(self):
        with pytest.raises(ValueError, match="mesh_coordinator"):
            ServingConfig(mesh_process_count=2).validated()

    def test_process_id_must_be_in_range(self):
        with pytest.raises(ValueError, match="mesh_process_id"):
            ServingConfig(
                mesh_process_count=2,
                mesh_coordinator="127.0.0.1:9999",
                mesh_process_id=5,
            ).validated()

    def test_single_process_forbids_nonzero_rank(self):
        with pytest.raises(ValueError, match="mesh_process_id"):
            ServingConfig(mesh_process_id=1).validated()

    def test_bad_coordinator_port_fast_fails(self):
        with pytest.raises(ValueError, match="mesh_coordinator"):
            ServingConfig(
                mesh_process_count=2, mesh_coordinator="hostonly"
            ).validated()

    def test_valid_pod_config_passes(self):
        cfg = ServingConfig(
            mesh_process_count=2,
            mesh_coordinator="10.0.0.1:8476",
            mesh_process_id=1,
        ).validated()
        assert cfg.mesh_process_count == 2
        cfg = ServingConfig().validated()  # single-process default
        assert cfg.mesh_process_count == 1
