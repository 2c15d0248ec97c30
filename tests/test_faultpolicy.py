"""Unit coverage for the tail-tolerant RPC plane (utils/faultpolicy.py):
deadline budget math + propagation surfaces, the shared retry policy
(backoff, transient classification, per-peer token budgets), and the
composable chaos fault schedule (loadgen/workload.py)."""
import asyncio
import time

import grpc
import pytest

from seaweedfs_tpu.loadgen.workload import LoadScenario
from seaweedfs_tpu.utils import faultpolicy as fp


@pytest.fixture()
def fresh_policy():
    """Isolate the process-global policy state: tests that drain
    budgets or prime EWMAs must not leak into each other (or into the
    serving tests sharing this process)."""
    prev = fp.CONFIG
    fp.PEER_LATENCY.reset()
    fp.RETRY_BUDGETS.reset()
    fp.reset_totals()
    yield fp
    fp.configure(prev)
    fp.PEER_LATENCY.reset()
    fp.RETRY_BUDGETS.reset()
    fp.reset_totals()


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


# ------------------------------------------------------------- deadlines


class TestDeadline:
    def test_no_scope_means_no_budget(self, fresh_policy):
        assert fp.remaining_s() is None
        assert fp.check_remaining("x") is None
        assert fp.rpc_timeout_s(7.0) == 7.0
        assert fp.outbound_headers() == {}
        assert fp.grpc_metadata() is None

    def test_scope_counts_down_and_caps_timeouts(self, fresh_policy):
        with fp.deadline_scope(0.5):
            rem = fp.remaining_s()
            assert 0.4 < rem <= 0.5
            # per-call timeout = min(default, remaining)
            assert fp.rpc_timeout_s(10.0) <= 0.5
            assert fp.rpc_timeout_s(0.1) == 0.1
            hdr = fp.outbound_headers()
            assert 0 < float(hdr[fp.DEADLINE_HEADER]) <= 500
            ((k, v),) = fp.grpc_metadata()
            assert k == fp.GRPC_DEADLINE_KEY and 0 < float(v) <= 500
        assert fp.remaining_s() is None

    def test_inner_scope_never_extends(self, fresh_policy):
        with fp.deadline_scope(0.2):
            with fp.deadline_scope(60.0):
                assert fp.remaining_s() <= 0.2
            # and a TIGHTER inner scope does bind
            with fp.deadline_scope(0.05):
                assert fp.remaining_s() <= 0.05

    def test_spent_budget_refuses_doomed_work(self, fresh_policy):
        with fp.deadline_scope(0.001):
            time.sleep(0.01)
            with pytest.raises(fp.DeadlineExceeded):
                fp.check_remaining("doomed")
            with pytest.raises(fp.DeadlineExceeded):
                fp.rpc_timeout_s(5.0, what="doomed rpc")
        t = fp.totals()
        assert t["deadline_exceeded"] == 2

    def test_doomed_read_is_refused_at_the_front_door(
        self, tmp_path, fresh_policy
    ):
        """End to end: a degraded GET whose X-Seaweed-Deadline-Ms budget
        is already spent when it reaches EC read admission is refused
        with a 504 that carries its trace id, counted once in
        deadline_exceeded; the same GET without a budget of its own
        comes back byte-equal."""
        import aiohttp

        from degraded_cluster import build_degraded_cluster
        from seaweedfs_tpu.obs.trace import TRACE_HEADER

        async def go():
            cluster, vs, blobs, _vid = await build_degraded_cluster(
                str(tmp_path), n_blobs=6
            )
            try:
                fid, data = next(iter(blobs.items()))
                url = f"http://{vs.url}/{fid}"
                async with aiohttp.ClientSession() as sess:
                    refused0 = fp.totals()["deadline_exceeded"]
                    # 100 ns: spent before any handler code has run
                    async with sess.get(
                        url, headers={fp.DEADLINE_HEADER: "0.0001"}
                    ) as r:
                        assert r.status == 504, await r.text()
                        assert r.headers.get(TRACE_HEADER)
                    assert (
                        fp.totals()["deadline_exceeded"] == refused0 + 1
                    )
                    async with sess.get(url) as r:
                        assert r.status == 200
                        assert await r.read() == data
            finally:
                await cluster.stop()

        asyncio.run(go())

    def test_parse_deadline_ms_rejects_garbage(self, fresh_policy):
        assert fp.parse_deadline_ms("250") == 250.0
        assert fp.parse_deadline_ms("") is None
        assert fp.parse_deadline_ms("nan") is None
        assert fp.parse_deadline_ms("-5") is None
        assert fp.parse_deadline_ms("bogus") is None
        assert fp.parse_deadline_ms("1e12") is None  # absurd budget

    def test_request_scope_adopts_header_else_stamps_default(
        self, fresh_policy
    ):
        fp.configure(fp.FaultPolicyConfig(deadline_ms=5000))
        with fp.request_scope({fp.DEADLINE_HEADER: "200"}):
            assert fp.remaining_s() <= 0.2
        with fp.request_scope({}):
            rem = fp.remaining_s()
            assert 4.5 < rem <= 5.0
        fp.configure(fp.FaultPolicyConfig(deadline_ms=0))
        with fp.request_scope({}):
            assert fp.remaining_s() is None  # 0 disables the stamp

    def test_spent_budget_adds_no_outbound_stamp(self, fresh_policy):
        with fp.deadline_scope(0.001):
            time.sleep(0.01)
            assert fp.outbound_headers() == {}
            assert fp.grpc_metadata() is None

    def test_config_validation(self, fresh_policy):
        with pytest.raises(ValueError):
            fp.FaultPolicyConfig(deadline_ms=-1).validated()
        with pytest.raises(ValueError):
            fp.FaultPolicyConfig(hedge_quantile=1.0).validated()
        with pytest.raises(ValueError):
            fp.FaultPolicyConfig(hedge_budget_pct=-2).validated()
        with pytest.raises(ValueError):
            fp.FaultPolicyConfig(retry_budget_pct=-1).validated()


# ------------------------------------------------------------- retry_rpc


class _FakeRpcError(grpc.RpcError):
    def __init__(self, code):
        self._code = code

    def code(self):
        return self._code


class TestRetryRpc:
    def test_transient_failure_retries_then_succeeds(self, fresh_policy):
        calls = {"n": 0}

        async def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise ConnectionError("transient")
            return "ok"

        out = run(fp.retry_rpc(flaky, "t", peer="p:1", base_delay_s=0.01))
        assert out == "ok" and calls["n"] == 2
        assert fp.totals()["retries"] == 1

    def test_deterministic_verdict_raises_immediately(self, fresh_policy):
        calls = {"n": 0}

        async def not_found():
            calls["n"] += 1
            raise _FakeRpcError(grpc.StatusCode.NOT_FOUND)

        with pytest.raises(grpc.RpcError):
            run(fp.retry_rpc(not_found, "t", peer="p:1"))
        assert calls["n"] == 1  # a real answer burns no attempts

    def test_exhausted_attempts_raise_failed_after(self, fresh_policy):
        async def always():
            raise ConnectionError("down")

        with pytest.raises(RuntimeError, match="failed after"):
            run(fp.retry_rpc(
                always, "t", peer="p:1", attempts=2, base_delay_s=0.01
            ))

    def test_retry_budget_fast_fails_a_sick_peer(self, fresh_policy):
        fp.configure(fp.FaultPolicyConfig(retry_budget_pct=10.0))
        calls = {"n": 0}

        async def down():
            calls["n"] += 1
            raise ConnectionError("down")

        failures = 0
        for i in range(20):
            with pytest.raises(RuntimeError, match="failed after"):
                run(fp.retry_rpc(
                    down, f"t{i}", peer="sick:1",
                    attempts=3, base_delay_s=0.001,
                ))
            failures += 1
        t = fp.totals()
        # un-budgeted, 20 calls x 2 retries = 40; the budget caps the
        # total at the bucket burst + 10% deposits and fast-fails the
        # rest — the no-retry-storm property the netchaos sweep asserts
        # cluster-wide
        assert t["retries"] <= 4, t
        assert t["retry_budget_exhausted"] >= 15, t
        assert calls["n"] <= 20 + t["retries"]
        assert failures == 20

    def test_spent_deadline_refuses_before_any_attempt(self, fresh_policy):
        calls = {"n": 0}

        async def never():
            calls["n"] += 1
            return "x"

        async def go():
            with fp.deadline_scope(0.001):
                await asyncio.sleep(0.01)
                await fp.retry_rpc(never, "t", peer="p:1")

        with pytest.raises(fp.DeadlineExceeded):
            run(go())
        assert calls["n"] == 0

    def test_zero_budget_pct_disables_retries(self, fresh_policy):
        fp.configure(fp.FaultPolicyConfig(retry_budget_pct=0.0))

        async def down():
            raise ConnectionError("down")

        with pytest.raises(RuntimeError, match="retry budget exhausted"):
            run(fp.retry_rpc(
                down, "t", peer="p:1", attempts=3, base_delay_s=0.001
            ))
        assert fp.totals()["retries"] == 0


# ------------------------------------------------------------ token math


class TestBudgets:
    def test_token_bucket_burst_and_deposit(self, fresh_policy):
        b = fp.TokenBucket(cap=2.0, initial=1.0)
        assert b.take() and not b.take()
        for _ in range(10):
            b.deposit(0.25)
        assert b.tokens == 2.0  # capped
        assert b.take() and b.take() and not b.take()

    def test_peer_latency_threshold_tracks_quantile(self, fresh_policy):
        fp.configure(fp.FaultPolicyConfig(hedge_quantile=0.95))
        for _ in range(50):
            fp.PEER_LATENCY.observe("a", 0.010)
        th = fp.PEER_LATENCY.threshold_s("a")
        assert th is not None and 0.010 <= th < 0.10
        # an unknown peer rides the aggregate; with no data at all
        # there is no threshold (and so no hedging)
        assert fp.PEER_LATENCY.threshold_s("unknown") is not None
        fp.PEER_LATENCY.reset()
        assert fp.PEER_LATENCY.threshold_s("a") is None


# -------------------------------------------------- QoS budget tie-in


class TestQosDeadlineTightening:
    """The admission end of the continuous budget: the QoS deadline
    shed judges the estimated queue wait against min(tier deadline,
    remaining request budget), not the tier's local guess alone."""

    def _controller(self, tier_deadline_s):
        from seaweedfs_tpu.serving.qos import (
            INTERACTIVE, QosController, TierPolicy,
        )

        q = QosController({
            INTERACTIVE: TierPolicy(INTERACTIVE, 100, tier_deadline_s)
        })
        q.observe_service(0.1)  # est wait at depth 10 / width 4 = 0.25s
        return q, INTERACTIVE

    def test_remaining_budget_tightens_the_tier_deadline(self):
        q, tier = self._controller(10.0)
        assert q.admit(tier, 10, 4) is None  # 0.25s wait vs 10s tier
        assert q.admit(tier, 10, 4, remaining_s=0.1) == "deadline"

    def test_budget_binds_even_when_tier_deadline_is_disabled(self):
        q, tier = self._controller(0.0)
        assert q.admit(tier, 10, 4) is None  # no tier deadline at all
        assert q.admit(tier, 10, 4, remaining_s=0.1) == "deadline"

    def test_generous_budget_changes_nothing(self):
        q, tier = self._controller(0.5)
        assert q.admit(tier, 10, 4, remaining_s=60.0) is None


# ------------------------------------------- composable fault schedules


class TestFaultSchedule:
    def test_kill_revive_pair_still_validates(self):
        sc = LoadScenario(connections=1, reads=1, kill_at=1.0, revive_at=2.0)
        assert sc.fault_events() == [(1.0, "kill"), (2.0, "revive")]
        with pytest.raises(ValueError):
            LoadScenario(connections=1, reads=1, revive_at=2.0).fault_events()
        with pytest.raises(ValueError):
            LoadScenario(
                connections=1, reads=1, kill_at=2.0, revive_at=1.0
            ).fault_events()

    def test_schedule_composes_and_sorts(self):
        sc = LoadScenario(
            connections=1, reads=1, kill_at=1.0,
            faults=[
                (0.5, "hang_shard_reads", {"idx": 2}),
                (0.2, "slow_disk", {"delay_s": 0.01}),
                (0.5, "partition"),  # 2-tuple form, kwargs default {}
            ],
        )
        sched = sc.fault_schedule()
        assert [e[0] for e in sched] == [0.2, 0.5, 0.5, 1.0]
        assert sched[0] == (0.2, "slow_disk", {"delay_s": 0.01})
        # same-time events keep declaration order
        assert sched[1][1] == "hang_shard_reads"
        assert sched[2] == (0.5, "partition", {})
        assert sched[3] == (1.0, "kill", {})

    def test_schedule_rejects_garbage(self):
        with pytest.raises(ValueError):
            LoadScenario(
                connections=1, reads=1, faults=[(-1.0, "kill", {})]
            ).fault_schedule()
        with pytest.raises(ValueError):
            LoadScenario(
                connections=1, reads=1,
                faults=[(1.0, "kill", "not-a-dict")],
            ).fault_schedule()

    def test_injector_rejects_unknown_action(self):
        from seaweedfs_tpu.loadgen.chaos import ChaosInjector

        inj = ChaosInjector(cluster=None)
        with pytest.raises(ValueError, match="unknown fault action"):
            run(inj.apply("set_on_fire", idx=0))
