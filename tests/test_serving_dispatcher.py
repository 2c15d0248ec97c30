"""Unit suite for the continuous-batching EC serving subsystem
(seaweedfs_tpu/serving/): coalescer packing rules, the dispatcher's
admission window, pipelined in-flight depth, backpressure fallback, and
batched-vs-unbatched result identity — all against a fake store, so the
batching semantics are pinned without booting a cluster.  The real-path
integration (HTTP -> dispatcher -> device cache) lives in
tests/test_serving_e2e.py.
"""
import asyncio
import threading
import time

import pytest

from seaweedfs_tpu import stats
from seaweedfs_tpu.serving import (
    Coalescer,
    EcReadDispatcher,
    ReadRequest,
    ServingConfig,
)


def run(coro):
    return asyncio.run(coro)


def req(vid, nid):
    loop = asyncio.get_running_loop()
    return ReadRequest(vid, nid, None, loop.create_future(), loop.time())


# --------------------------------------------------------------- coalescer


def test_coalescer_packs_fifo_and_groups_by_vid():
    async def go():
        c = Coalescer(max_batch=4, max_queue=100)
        for i in range(6):
            assert c.offer(req(vid=i % 2, nid=i))
        assert len(c) == 6
        groups = c.take()  # first 4 in arrival order, grouped by vid
        assert {v: [r.nid for r in rs] for v, rs in groups.items()} == {
            0: [0, 2],
            1: [1, 3],
        }
        assert len(c) == 2  # the overflow stays queued for the next take
        groups = c.take()
        assert {v: [r.nid for r in rs] for v, rs in groups.items()} == {
            0: [4],
            1: [5],
        }
        assert c.take() == {}

    run(go())


def test_coalescer_saturation_rejects():
    async def go():
        c = Coalescer(max_batch=2, max_queue=3)
        assert [c.offer(req(1, i)) for i in range(5)] == [
            True, True, True, False, False,
        ]
        c.take()
        assert c.offer(req(1, 9))  # drained below the limit: admits again

    run(go())


# -------------------------------------------------------------- dispatcher


class FakeStore:
    """Deterministic store double: batch and native paths return the
    same value for the same needle, so identity is checkable."""

    def __init__(self, resident=True, batch_sleep=0.0, gate=None):
        self.resident = resident
        self.batch_calls: list[list[int]] = []
        self.native_calls: list[int] = []
        self.batch_sleep = batch_sleep
        self.gate = gate  # threading.Event: batch blocks until set
        self._active = 0
        self.peak_active = 0
        self._lock = threading.Lock()

    def ec_volume_is_resident(self, vid):
        return self.resident

    def _value(self, vid, nid):
        return f"needle-{vid}-{nid}".encode()

    def read_ec_needles_batch(
        self, vid, requests, remote_read=None, zero_copy=False
    ):
        with self._lock:
            self._active += 1
            self.peak_active = max(self.peak_active, self._active)
            self.batch_calls.append([nid for nid, _ in requests])
        if self.gate is not None:
            self.gate.wait(5)
        if self.batch_sleep:
            time.sleep(self.batch_sleep)
        with self._lock:
            self._active -= 1
        out = []
        for nid, _cookie in requests:
            if nid == 666:
                out.append(KeyError("corrupt needle"))
            else:
                out.append(self._value(vid, nid))
        return out

    def read_ec_needle(
        self, vid, nid, cookie=None, remote_read=None, use_device=True,
        zero_copy=False,
    ):
        self.native_calls.append(nid)
        if nid == 666:
            raise KeyError("corrupt needle")
        return self._value(vid, nid)


def make(store, **kw):
    defaults = dict(max_inflight=1, max_wait_us=0)
    defaults.update(kw)
    return EcReadDispatcher(store, lambda vid: None, ServingConfig(**defaults))


def test_batched_results_byte_identical_to_unbatched():
    """The satellite contract: a concurrent burst served through the
    coalescer/pipeline returns byte-identical results to the native
    per-read path, with per-needle failures isolated."""

    async def go():
        store = FakeStore()
        d = make(store, max_inflight=3, max_wait_us=100)
        nids = list(range(40)) + [666]
        batched = await asyncio.gather(
            *(d.read(7, n, None) for n in nids), return_exceptions=True
        )
        for n, got in zip(nids, batched):
            if n == 666:
                assert isinstance(got, KeyError)
            else:
                assert got == store.read_ec_needle(7, n)
        # and the burst actually rode the batch path
        assert sum(len(b) for b in store.batch_calls) == len(nids)
        assert max(len(b) for b in store.batch_calls) > 1

    run(go())


def test_max_batch_splits_wide_bursts():
    async def go():
        store = FakeStore()
        d = make(store, max_batch=8, max_queue=1000)
        await asyncio.gather(*(d.read(1, n, None) for n in range(30)))
        assert max(len(b) for b in store.batch_calls) <= 8

    run(go())


def test_admission_window_fills_partial_batches():
    """A hot lane holds the max-wait window open so stragglers join the
    next batch instead of fragmenting into singletons; max_wait_us=0
    disables the window."""

    async def go(max_wait_us):
        gate = threading.Event()
        store = FakeStore(gate=gate)
        d = make(store, max_wait_us=max_wait_us)
        first = asyncio.ensure_future(d.read(1, 0, None))
        while not store.batch_calls:  # lane is now blocked in batch #1
            await asyncio.sleep(0.001)
        second = asyncio.ensure_future(d.read(1, 1, None))
        await asyncio.sleep(0.001)

        async def trickle():
            # lands inside a 100ms window, after a 0-width one closed
            await asyncio.sleep(0.02)
            return await d.read(1, 2, None)

        third = asyncio.ensure_future(trickle())
        gate.set()
        await asyncio.gather(first, second, third)
        return store.batch_calls

    calls = run(go(max_wait_us=100_000))
    assert calls[0] == [0]
    assert calls[1] == [1, 2], calls  # window held open for the straggler
    calls = run(go(max_wait_us=0))
    assert calls[1] == [1], calls  # no window: dispatches what is queued


def test_pipelined_batches_overlap():
    """max_inflight lanes genuinely overlap device calls: with 3 lanes
    and slow batches, at least two read_ec_needles_batch calls must be
    active at once (the continuous-batching property round 5 lacked)."""

    async def go():
        store = FakeStore(batch_sleep=0.05)
        d = make(store, max_inflight=3, max_batch=4, max_wait_us=0)
        await asyncio.gather(*(d.read(1, n, None) for n in range(24)))
        assert store.peak_active >= 2, store.batch_calls

    run(go())


def test_backpressure_falls_back_to_native():
    """Past max_queue the dispatcher sheds to the native path (counted
    in the fallback series) and every request still gets the right
    bytes."""

    async def go():
        gate = threading.Event()
        store = FakeStore(gate=gate)
        d = make(store, max_batch=2, max_queue=2)
        fallback0 = stats.VOLUME_SERVER_EC_BATCH_FALLBACK._value.get()
        first = asyncio.ensure_future(d.read(1, 0, None))
        while not store.batch_calls:
            await asyncio.sleep(0.001)
        # queue capacity is 2: the next two queue, the rest shed native
        rest = [asyncio.ensure_future(d.read(1, n, None)) for n in range(1, 8)]
        while len(store.native_calls) < 5:
            await asyncio.sleep(0.001)
        gate.set()
        results = await asyncio.gather(first, *rest)
        assert results == [store._value(1, n) for n in range(8)]
        assert len(store.native_calls) == 5
        shed = stats.VOLUME_SERVER_EC_BATCH_FALLBACK._value.get() - fallback0
        assert shed == 5

    run(go())


def test_non_resident_volume_routes_native():
    """An unpinned volume's reads never queue behind a batch — they run
    concurrently on the native path (the round-5 serialization hazard)."""

    async def go():
        store = FakeStore(resident=False)
        d = make(store)
        out = await asyncio.gather(*(d.read(3, n, None) for n in range(6)))
        assert out == [store._value(3, n) for n in range(6)]
        assert store.batch_calls == []
        assert store.native_calls == list(range(6))

    run(go())


def test_disabled_dispatcher_routes_native():
    async def go():
        store = FakeStore(resident=True)
        d = make(store, enabled=False)
        assert await d.read(1, 5, None) == store._value(1, 5)
        assert store.batch_calls == [] and store.native_calls == [5]

    run(go())


def test_config_validation():
    with pytest.raises(ValueError):
        ServingConfig(max_batch=0).validated()
    with pytest.raises(ValueError):
        ServingConfig(max_queue=4, max_batch=8).validated()
    with pytest.raises(ValueError):
        ServingConfig(max_inflight=0).validated()
    with pytest.raises(ValueError):
        ServingConfig(max_wait_us=-1).validated()


def test_dispatch_metrics_observed():
    """The observability series move: batch-size histogram counts the
    batches, queue-wait observes per request, occupancy returns to 0,
    and the route counter splits batched vs native."""

    async def go():
        size_hist = stats.VOLUME_SERVER_EC_BATCH_SIZE
        wait_hist = stats.VOLUME_SERVER_EC_BATCH_QUEUE_WAIT
        batched = stats.VOLUME_SERVER_EC_READ_ROUTE.labels(route="batched")
        native = stats.VOLUME_SERVER_EC_READ_ROUTE.labels(route="native")
        s0 = size_hist._sum.get()
        # bucket counters are per-bucket internally; the sum is the
        # observation count
        w0 = sum(b.get() for b in wait_hist._buckets)
        b0 = batched._value.get()
        n0 = native._value.get()

        store = FakeStore()
        d = make(store, max_inflight=2, max_wait_us=100)
        await asyncio.gather(*(d.read(1, n, None) for n in range(12)))
        await d.read(2, 0, None)
        store.resident = False
        await d.read(1, 99, None)

        assert size_hist._sum.get() - s0 == 13  # every batched read counted
        assert sum(b.get() for b in wait_hist._buckets) - w0 == 13
        assert batched._value.get() - b0 == 13
        assert native._value.get() - n0 == 1
        assert stats.VOLUME_SERVER_EC_BATCH_INFLIGHT._value.get() == 0

    run(go())


def test_admission_is_a_stage_and_the_batch_stages_stay_the_requests():
    """`get_admit` times the synchronous admission as a child of the
    request; the batch's stages, replayed from the sink, hang off the
    request's root too, not off the admission that queued it; and the
    native route (dispatcher disabled) is admitted under the same
    stage."""
    from seaweedfs_tpu import obs

    class SpanningStore(FakeStore):
        def read_ec_needles_batch(self, vid, requests, *a, **kw):
            with obs.span("device_execute", requests=len(requests)):
                return super().read_ec_needles_batch(vid, requests, *a, **kw)

    async def go(disp):
        trace, token = obs.start_trace("GET /7,01", "volume")
        try:
            assert await disp.read(7, 1, None) == b"needle-7-1"
        finally:
            obs.finish_trace(trace, token, 200)
        return trace

    trace = run(go(make(SpanningStore())))
    spans = {sp.name: sp for sp in trace.spans}
    assert {"get_admit", "queue_wait", "batch_dispatch",
            "device_execute"} <= set(spans)
    for name in ("get_admit", "batch_dispatch", "device_execute"):
        assert spans[name].parent_id == trace.root_id, name
    store = SpanningStore()
    trace = run(go(make(store, enabled=False)))
    assert store.native_calls == [1]
    assert [sp.name for sp in trace.spans] == ["get_admit"]
