"""Hedged survivor gathers (utils/faultpolicy.hedged_gather) — the r18
tail-tolerance core — at three depths:

  * policy units against a fake fetch: a hedge fires only past the
    peer's EWMA-quantile threshold, losers are genuinely cancelled
    (never executed), and the hedge token budget caps amplification;
  * EcVolume integration: a degraded read through a tail-slow or HUNG
    peer stays byte-exact and bounded (the satellite-1 regression: a
    hung peer must not pin the gather), hedged == unhedged bytes;
  * a lockwatch+viewguard stress pass racing hedged gathers against
    device-cache budget eviction and host-tier demotion — the
    interleaving the netchaos sweep creates when a tier rebalance lands
    mid-outage.
"""
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import lockwatch
import viewguard
from seaweedfs_tpu.ops import rs_resident
from seaweedfs_tpu.serving.tiering import HostShardCache
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.storage.ec import volume as ec_volume_mod
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import faultpolicy as fp

VID = 41
LOCAL = {9, 10, 11, 12, 13}   # shards mounted at the "front door"
REMOTE = {1, 2, 3, 4, 5, 6, 7, 8}  # shards served by the peer hook
# shard 0 is missing everywhere: a small volume's every needle lives in
# it, so every read is a degraded reconstruct needing 5 remote shards


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


def _prime(peer_ids, latency_s=0.004, n=30):
    # +-25% jitter: real fetch latencies are never constant, and a
    # zero-deviation prime would test a degenerate threshold
    rng = random.Random(5)
    for p in peer_ids:
        for _ in range(n):
            fp.PEER_LATENCY.observe(
                p, latency_s * (0.75 + 0.5 * rng.random())
            )


# ------------------------------------------------------- policy units


class TestHedgePolicy:
    def test_hedge_fires_only_past_the_quantile(self, fresh_policy):
        fp.configure(fp.FaultPolicyConfig(
            hedge_quantile=0.95, hedge_budget_pct=100.0
        ))
        peers = {s: f"p{s}" for s in range(6)}
        # the history says 200 ms a fetch and these return at once: a
        # loaded worker that schedules a fetch late is still a fifth of
        # a second under every peer's quantile
        _prime(peers.values(), 0.2)
        pool = ThreadPoolExecutor(8)

        def fast(sid):
            return b"d%d" % sid

        res = fp.hedged_gather(
            3, [0, 1, 2, 3, 4, 5], fast, pool=pool,
            peer_of=peers.get,
        )
        assert len(res.got) == 3
        assert res.hedges_sent == 0  # nobody crossed the quantile
        pool.shutdown(wait=True)

    def test_hedge_fires_past_the_quantile_and_wins(self, fresh_policy):
        fp.configure(fp.FaultPolicyConfig(
            hedge_quantile=0.95, hedge_budget_pct=100.0
        ))
        peers = {s: f"p{s}" for s in range(6)}
        # pin the cheapest-first ordering: the soon-to-be-slow peer
        # looks CHEAP (a tail event, not a known-slow peer) and the
        # spares look dearer, so sid 0 is deterministically a primary
        # and sids 3-5 are the spares.  The history says 50 ms a fetch
        # and the healthy ones return at once, so on a loaded worker it
        # is still sid 0 alone that crosses its quantile; sid 0 hangs
        # until the gather is over
        _prime(["p0", "p1", "p2"], 0.05)
        _prime(["p3", "p4", "p5"], 0.1)
        pool = ThreadPoolExecutor(8)
        gathered = threading.Event()

        def one_slow(sid):
            if sid == 0:
                gathered.wait(10)
            return b"d%d" % sid

        res = fp.hedged_gather(
            3, [0, 1, 2, 3, 4, 5], one_slow, pool=pool,
            peer_of=peers.get,
        )
        gathered.set()
        assert len(res.got) == 3 and 0 not in res.got
        assert res.hedges_sent >= 1
        assert res.hedge_wins >= 1  # the spare beat the slow primary
        pool.shutdown(wait=True)

    def test_uniformly_slow_fetches_never_hedge(self, fresh_policy):
        """A peer is only hedged when it exceeds ITS OWN quantile: when
        everything is equally slow there is no tail to cut, and hedges
        would be pure amplification."""
        fp.configure(fp.FaultPolicyConfig(
            hedge_quantile=0.95, hedge_budget_pct=100.0
        ))
        # 0.3 s against a history of 0.4 s (quantile ~0.49 s): slow all
        # alike, and far enough under the threshold for a loaded worker
        _prime([f"p{s}" for s in range(4)], 0.4)
        pool = ThreadPoolExecutor(4)

        def fetch(sid):
            time.sleep(0.3)
            return b"d%d" % sid

        res = fp.hedged_gather(
            2, [0, 1, 2, 3], fetch, pool=pool,
            peer_of=lambda s: f"p{s}",
        )
        assert res.hedges_sent == 0
        assert len(res.got) == 2
        pool.shutdown(wait=True)

    def test_losers_are_cancelled_and_discarded(self, fresh_policy):
        """The loser side of the race: a hedge outlived by its primary
        is counted cancelled (cancelled-while-queued or abandoned
        mid-run — either way its bytes are discarded, never in `got`,
        and its pool thread is freed by its own per-fetch budget)."""
        fp.configure(fp.FaultPolicyConfig(
            hedge_quantile=0.9, hedge_budget_pct=100.0
        ))
        peers = {s: f"p{s}" for s in range(4)}
        _prime(peers.values())
        done_order = []
        lock = threading.Lock()

        def fetch(sid):
            # the primary is slow enough to get hedged, but the spare
            # is SLOWER: the primary lands first and the hedge loses
            time.sleep(0.06 if sid == 0 else 0.4)
            with lock:
                done_order.append(sid)
            return b"d%d" % sid

        pool = ThreadPoolExecutor(4)
        res = fp.hedged_gather(
            1, [0, 1], fetch, pool=pool, peer_of=peers.get,
        )
        assert sorted(res.got) == [0]   # the loser's bytes discarded
        assert res.hedges_sent == 1
        assert res.hedges_cancelled == 1
        assert res.hedge_wins == 0
        pool.shutdown(wait=True)

    def test_hedge_budget_caps_amplification(self, fresh_policy):
        fp.configure(fp.FaultPolicyConfig(
            hedge_quantile=0.9, hedge_budget_pct=10.0
        ))
        peers = {s: f"q{s}" for s in range(3)}
        _prime(peers.values())
        pool = ThreadPoolExecutor(4)

        def slow_primary(sid):
            time.sleep(0.06 if sid == 0 else 0.004)
            return b"d%d" % sid

        hedges = 0
        for _ in range(30):
            # keep the slow peer's EWMA primed-fast so every gather
            # sees the same slow-primary setup (observations would
            # otherwise reorder it out, which is the OTHER mechanism)
            _prime(["q0"], 0.004, n=50)
            res = fp.hedged_gather(
                1, [0, 1, 2], slow_primary, pool=pool, peer_of=peers.get,
            )
            assert len(res.got) == 1
            hedges += res.hedges_sent
        # 30 primaries x 10% + the 1-token burst: never ~30 hedges
        assert 1 <= hedges <= 6, hedges
        pool.shutdown(wait=True)

    def test_zero_budget_disables_hedging(self, fresh_policy):
        fp.configure(fp.FaultPolicyConfig(hedge_budget_pct=0.0))
        peers = {s: f"z{s}" for s in range(3)}
        _prime(peers.values())
        pool = ThreadPoolExecutor(4)

        def fetch(sid):
            time.sleep(0.05 if sid == 0 else 0.003)
            return b"d%d" % sid

        res = fp.hedged_gather(
            1, [0, 1, 2], fetch, pool=pool, peer_of=peers.get,
        )
        assert res.hedges_sent == 0 and len(res.got) == 1
        pool.shutdown(wait=True)

    def test_failed_fetches_replaced_without_hedge_tokens(
        self, fresh_policy
    ):
        fp.configure(fp.FaultPolicyConfig(hedge_budget_pct=0.0))
        pool = ThreadPoolExecutor(4)

        def fetch(sid):
            return None if sid < 2 else b"d%d" % sid

        res = fp.hedged_gather(
            2, [0, 1, 2, 3], fetch, pool=pool,
        )
        assert sorted(res.got) == [2, 3]  # failures widened to spares
        assert res.hedges_sent == 0
        pool.shutdown(wait=True)


# ----------------------------------------------- EcVolume integration


def _make_ec_volume(tmp_path, count=12, seed=23):
    rng = random.Random(seed)
    v = Volume(str(tmp_path), VID)
    blobs = {}
    for i in range(1, count + 1):
        data = rng.randbytes(rng.choice([150, 1024, 4096]))
        v.write(i, rng.getrandbits(32), data, name=f"f{i}".encode())
        blobs[i] = data
    v.sync()
    base = Volume.base_name(v.dir, v.id, v.collection)
    ec.write_ec_files(base, backend="cpu")
    ec.write_sorted_file_from_idx(base)
    v.close()
    ev = ec.EcVolume(str(tmp_path), VID)
    for sid in sorted(LOCAL):
        ev.add_shard(sid)
    return ev, blobs


def _disk_remote(tmp_path, delays=None, hung=None, hang_gate=None,
                 calls=None):
    """Peer hook serving REMOTE shards from the on-disk shard files —
    shard 0 is missing cluster-wide (returns None), `hung` shards block
    on `hang_gate` (the peer-hang network fault), `delays` adds
    per-shard latency."""
    base = Volume.base_name(str(tmp_path), VID, "")

    def read(sid, off, size):
        if calls is not None:
            calls.append(sid)
        if sid not in REMOTE:
            return None
        if hung and sid in hung:
            hang_gate.wait()
            return None
        if delays:
            time.sleep(delays.get(sid, 0.0))
        with open(base + ec.to_ext(sid), "rb") as f:
            f.seek(off)
            return f.read(size)

    read.peer_of = lambda sid: f"peer-{sid // 3}"  # 3 shards per "node"
    return read


class TestEcVolumeHedging:
    def test_byte_equality_hedged_vs_unhedged(
        self, tmp_path, fresh_policy
    ):
        ev, blobs = _make_ec_volume(tmp_path)
        try:
            # pin the cheapest-first ordering: peer-1 (shards 3-5) is
            # about to be TAIL-slow, so it must look cheap (primed
            # fastest) for its shards to be primaries — a known-slow
            # peer would just be sorted into the spares, and no hedge
            # would ever need to fire
            _prime(["peer-1"], 0.002)
            _prime(["peer-0", "peer-2", "peer-3", "peer-4"], 0.006)
            fp.configure(fp.FaultPolicyConfig(
                hedge_quantile=0.9, hedge_budget_pct=100.0
            ))
            slow = {sid: (0.12 if sid in (3, 4, 5) else 0.001)
                    for sid in REMOTE}
            hedged = {}
            remote = _disk_remote(tmp_path, delays=slow)
            for nid in sorted(blobs):
                hedged[nid] = ev.read_needle_bytes(
                    nid, remote_read=remote, backend="cpu",
                    use_device=False,
                )
            assert fp.totals()["hedge_sent"] >= 1
            # unhedged pass: same volume, hedging off, fresh memo
            with ev._reconstruct_memo_lock:
                ev._reconstruct_memo.clear()
                ev._reconstruct_memo_bytes = 0
            fp.configure(fp.FaultPolicyConfig(hedge_budget_pct=0.0))
            remote2 = _disk_remote(tmp_path)
            for nid in sorted(blobs):
                plain = ev.read_needle_bytes(
                    nid, remote_read=remote2, backend="cpu",
                    use_device=False,
                )
                assert bytes(hedged[nid]) == bytes(plain)
                n = ec_volume_mod.Needle.from_bytes(plain, ev.version)
                assert bytes(n.data) == blobs[nid]
        finally:
            ev.close()

    def test_hung_peer_cannot_pin_the_gather(self, tmp_path, fresh_policy):
        """The satellite-1 regression: a peer that ACCEPTS the fetch
        and never answers.  The gather must complete from the spares
        within the patience bound — not wait on the hung fetch — and
        the abandoned fetch must not poison correctness."""
        ev, blobs = _make_ec_volume(tmp_path)
        gate = threading.Event()
        try:
            fp.configure(fp.FaultPolicyConfig(hedge_budget_pct=10.0))
            remote = _disk_remote(
                tmp_path, hung={1, 2}, hang_gate=gate,
            )
            nid = sorted(blobs)[0]
            t0 = time.monotonic()
            raw = ev.read_needle_bytes(
                nid, remote_read=remote, backend="cpu", use_device=False,
            )
            elapsed = time.monotonic() - t0
            n = ec_volume_mod.Needle.from_bytes(raw, ev.version)
            assert bytes(n.data) == blobs[nid]
            # bounded by the patience backstop + spare fetches, nowhere
            # near the 10s gather deadline the hung fetch would pin
            assert elapsed < 5.0, elapsed
        finally:
            gate.set()  # release the hung pool threads
            ev.close()

    def test_gather_annotates_hedges(self, tmp_path, fresh_policy):
        """The flight-recorder half: hedge decisions land in the
        incident ring so a bundle can explain the shed."""
        from seaweedfs_tpu.obs import incident as obs_incident

        ev, blobs = _make_ec_volume(tmp_path)
        prev_cfg = obs_incident.CONFIG
        try:
            # an earlier suite member may have left the recorder
            # disabled; this test is ABOUT the recorded decision
            obs_incident.configure(obs_incident.IncidentConfig())
            obs_incident.EVENTS.clear()
            # same ordering pin as the byte-equality test: shard 3's
            # peer must be a primary for the hedge to have a tail to
            # cut (peer-1 covers shards 3-5)
            _prime(["peer-1"], 0.002)
            _prime(["peer-0", "peer-2", "peer-3", "peer-4"], 0.006)
            fp.configure(fp.FaultPolicyConfig(
                hedge_quantile=0.9, hedge_budget_pct=100.0
            ))
            remote = _disk_remote(
                tmp_path,
                delays={sid: (0.15 if sid in (3, 4, 5) else 0.001)
                        for sid in REMOTE},
            )
            nid = sorted(blobs)[0]
            ev.read_needle_bytes(
                nid, remote_read=remote, backend="cpu", use_device=False,
            )
            kinds = {e["kind"] for e in obs_incident.EVENTS.snapshot()}
            assert "hedge" in kinds, kinds
        finally:
            obs_incident.configure(prev_cfg)
            ev.close()


# ------------------------------------------------------------- stress


def test_hedged_gathers_race_eviction_and_demotion(
    tmp_path, fresh_policy
):
    """lockwatch + viewguard: zero-copy batched reads whose survivor
    gathers HEDGE around a jittery peer, racing (a) device-cache budget
    eviction/re-pin cycles and (b) host-tier stage/evict demotion — the
    netchaos interleaving, on a real schedule under both sanitizers."""
    ev, blobs = _make_ec_volume(tmp_path, count=16)
    fp.configure(fp.FaultPolicyConfig(
        hedge_quantile=0.9, hedge_budget_pct=50.0
    ))
    _prime({f"peer-{i}" for i in range(5)})
    errors: list[BaseException] = []
    good_reads = 0
    clean_misses = 0
    evict_cycles = 0
    demote_cycles = 0
    stop = threading.Event()
    lock = threading.Lock()
    rng_delay = random.Random(7)

    def jittery_remote():
        base = Volume.base_name(str(tmp_path), VID, "")

        def read(sid, off, size):
            if sid not in REMOTE:
                return None
            # peer-1 is tail-slow SOMETIMES: exactly the gray failure
            # hedging exists for
            if sid in (3, 4, 5) and rng_delay.random() < 0.3:
                time.sleep(0.03)
            else:
                time.sleep(0.001)
            with open(base + ec.to_ext(sid), "rb") as f:
                f.seek(off)
                return f.read(size)

        read.peer_of = lambda sid: f"peer-{sid // 3}"
        return read

    with lockwatch.watch() as w, viewguard.watch() as g:
        cache = rs_resident.DeviceShardCache(
            shard_quantum=1 << 20, layout="blockdiag"
        )
        cache.warm_sizes = ()  # CI convention: no AOT grid compile
        # PARTIAL residency (5 < 10 survivors): the device path must
        # CacheMiss into the host gather, which is where the hedging
        # lives
        ev.device_cache = cache
        cache.claim_pin_source(VID, ev.dir)
        for sid in sorted(LOCAL):
            cache.put(VID, sid, np.fromfile(
                ev.shards[sid].path, dtype=np.uint8
            ))
        host = HostShardCache(budget_bytes=64 << 20)
        ev.host_cache = host
        nids = sorted(blobs)
        remote = jittery_remote()

        def reader(seed):
            nonlocal good_reads, clean_misses
            rng = random.Random(seed)
            deadline = time.time() + 18
            mine = 0
            while time.time() < deadline and mine < 6:
                batch = rng.sample(nids, 3)
                # keep the gathers flowing: the memo would otherwise
                # absorb the hot set and the race would idle
                with ev._reconstruct_memo_lock:
                    ev._reconstruct_memo.clear()
                    ev._reconstruct_memo_bytes = 0
                try:
                    out = ev.read_needles_batch(
                        batch, remote_read=remote, backend="cpu",
                        zero_copy=True,
                    )
                except (rs_resident.CacheMiss, KeyError) as e:
                    del e
                    with lock:
                        clean_misses += 1
                    continue
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                ok = True
                for nid, res in zip(batch, out):
                    if isinstance(res, (rs_resident.CacheMiss, KeyError)):
                        with lock:
                            clean_misses += 1
                        ok = False
                        continue
                    if isinstance(res, Exception):
                        errors.append(res)
                        return
                    if bytes(res.data) != blobs[nid]:
                        errors.append(AssertionError(
                            f"stale bytes for needle {nid}"
                        ))
                        return
                    if isinstance(res.data, memoryview):
                        g.release(res.data)
                if ok:
                    mine += 1
                    with lock:
                        good_reads += 1

        def evictor():
            """Budget-eviction pressure: evict + re-pin the resident
            survivors the way the tier controller's swaps do."""
            nonlocal evict_cycles
            sids = sorted(LOCAL)
            i = 0
            while not stop.is_set():
                sid = sids[i % len(sids)]
                try:
                    cache.evict(VID, sid)
                    time.sleep(0.002)
                    cache.put(VID, sid, np.fromfile(
                        ev.shards[sid].path, dtype=np.uint8
                    ))
                    with lock:
                        evict_cycles += 1
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                i += 1

        def demoter():
            """Host-tier churn: stage the local shard set, serve a
            while, evict — a demotion/promotion cycle under the reads."""
            nonlocal demote_cycles
            while not stop.is_set():
                try:
                    host.put_volume(VID, ev.stage_host_shards())
                    time.sleep(0.01)
                    host.evict(VID)
                    with lock:
                        demote_cycles += 1
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return

        threads = [
            threading.Thread(target=reader, args=(1,), name="reader1"),
            threading.Thread(target=reader, args=(2,), name="reader2"),
            threading.Thread(target=evictor, name="evictor"),
            threading.Thread(target=demoter, name="demoter"),
        ]
        for t in threads:
            t.start()
        threads[0].join()
        threads[1].join()
        stop.set()
        threads[2].join()
        threads[3].join()
        ev.close()

    assert not errors, errors
    assert good_reads > 0, "no read ever succeeded under the race"
    assert evict_cycles > 0 and demote_cycles > 0
    assert g.exports_total > 0, "no zero-copy views were ever tracked"
    g.assert_clean()
    w.assert_no_cycles()
