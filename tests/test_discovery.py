"""Self-healing replica set under real injected chaos.

Unit layers: resolvers + the discovery loop's last-known-good error
containment, live membership (probation / graceful retire / eviction /
the last-healthy safety valve), jittered readiness probes, and the
sticky sequence policy's restart contract.  Streaming: the sync and aio
resilient streams reconnect across a mid-stream replica kill, replaying
only unacknowledged requests and deduping duplicate responses by request
id.  The churn acceptance scenario drives all of it at once — add a
replica, retire a replica, kill the stream-pinned replica, flap the
resolver — under sustained load with zero client-visible errors.
"""

import asyncio
import random
import threading
import time

import numpy as np
import pytest

import client_tpu.grpc as grpcclient
from client_tpu.balance import (
    CallableResolver,
    ConfigFileResolver,
    DiscoveryLoop,
    EndpointPool,
    ReplicatedClient,
    AsyncReplicatedClient,
    SequenceRestartError,
    SrvResolver,
    StaticResolver,
    Sticky,
    make_policy,
    make_resolver,
)
from client_tpu.balance.pool import (
    Endpoint,
    PHASE_ACTIVE,
    PHASE_PROBATION,
    PHASE_RETIRING,
)
from client_tpu.resilience import NoHealthyEndpointError, RetryPolicy
from client_tpu.serve import Model, Server, TensorSpec
from client_tpu.serve.metrics import BalancerMetricsObserver, Registry
from client_tpu.testing.faults import FaultProxy
from client_tpu.tracing import ClientTracer
from client_tpu.utils import (
    SERVER_NOT_READY,
    SERVER_READY,
    SERVER_UNREACHABLE,
    InferenceServerException,
)

_FAST_RECONNECT = [
    ("grpc.initial_reconnect_backoff_ms", 50),
    ("grpc.min_reconnect_backoff_ms", 50),
    ("grpc.max_reconnect_backoff_ms", 100),
]

# input-value markers the recording model reacts to
_SLEEPY = 1000  # >= this: hold the request ~100ms (in-flight at kill time)
_BAD = -1       # exactly this: answered application error (status 400)


def _recording_model(name, log, lock):
    """Echo model that records (sequence_id, value) per application —
    the double-apply detector the churn acceptance asserts over."""

    def fn(inputs, params, ctx):
        val = int(np.asarray(inputs["IN"]).reshape(-1)[0])
        if val == _BAD:
            raise InferenceServerException(
                "injected bad request", status="400"
            )
        if val >= _SLEEPY:
            time.sleep(0.1)
        with lock:
            log.append((params.get("sequence_id", 0), val))
        return {"OUT": inputs["IN"]}

    return Model(
        name,
        inputs=[TensorSpec("IN", "INT32", [-1, 4])],
        outputs=[TensorSpec("OUT", "INT32", [-1, 4])],
        fn=fn,
        max_batch_size=8,
    )


def _val_inputs(val):
    data = np.full((1, 4), val, dtype=np.int32)
    inp = grpcclient.InferInput("IN", [1, 4], "INT32")
    inp.set_data_from_numpy(data)
    return [inp]


def _start_servers(n, model_name="echo"):
    """n gRPC servers, each with its own application log."""
    servers, logs = [], []
    for _ in range(n):
        log, lock = [], threading.Lock()
        server = Server(
            models=[_recording_model(model_name, log, lock)],
            with_default_models=False,
            grpc_port=0,
        ).start()
        servers.append(server)
        logs.append(log)
    return servers, logs


def _fast_policy(**kw):
    kw.setdefault("max_attempts", 6)
    kw.setdefault("initial_backoff_s", 0.02)
    kw.setdefault("max_backoff_s", 0.1)
    return RetryPolicy(**kw)


def _wait_for(predicate, timeout_s=5.0, interval_s=0.01):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return predicate()


# -- resolvers ---------------------------------------------------------------


class TestResolvers:
    def test_static_resolver(self):
        r = StaticResolver(["a", ("b", 2.0)])
        assert r.resolve() == ["a", ("b", 2.0)]
        assert r.resolve() == r.resolve()  # stable

    def test_callable_resolver(self):
        calls = []

        def lookup():
            calls.append(1)
            return ["a", "b"]

        r = CallableResolver(lookup)
        assert r.resolve() == ["a", "b"]
        assert len(calls) == 1

    def test_config_file_resolver_text(self, tmp_path):
        path = tmp_path / "fleet.conf"
        path.write_text(
            "# the fleet\nhost1:8001\nhost2:8001 2.5\n\nhost3:8001  # canary\n"
        )
        r = ConfigFileResolver(str(path))
        assert r.resolve() == [
            "host1:8001", ("host2:8001", 2.5), "host3:8001",
        ]
        # edits are picked up on the next resolve (no stale cache)
        path.write_text("host9:8001\n")
        assert r.resolve() == ["host9:8001"]

    def test_config_file_resolver_json(self, tmp_path):
        path = tmp_path / "fleet.json"
        path.write_text('["h1:8001", ["h2:8001", 3]]')
        assert ConfigFileResolver(str(path)).resolve() == [
            "h1:8001", ("h2:8001", 3.0),
        ]
        path.write_text('{"endpoints": ["h1:8001"]}')
        assert ConfigFileResolver(str(path)).resolve() == ["h1:8001"]

    def test_config_file_resolver_missing_raises(self, tmp_path):
        with pytest.raises(OSError):
            ConfigFileResolver(str(tmp_path / "absent.conf")).resolve()

    def test_make_resolver_dispatch(self, tmp_path):
        assert isinstance(make_resolver(["a"]), StaticResolver)
        assert isinstance(make_resolver(lambda: ["a"]), CallableResolver)
        assert isinstance(
            make_resolver(str(tmp_path / "f.conf")), ConfigFileResolver
        )
        r = StaticResolver(["a"])
        assert make_resolver(r) is r


class TestSrvResolver:
    """DNS SRV-style resolution honoring record TTLs (the PR 5
    carry-over): cached until the smallest TTL expires, re-resolved
    after, last-known-good on lookup failure."""

    def _clock(self):
        state = {"now": 100.0}

        def advance(dt):
            state["now"] += dt

        return (lambda: state["now"]), advance

    def test_ttl_caches_until_expiry_then_re_resolves(self):
        time_fn, advance = self._clock()
        calls = []

        def lookup():
            calls.append(1)
            return [("h1:8001", 1.0, 5.0), ("h2:8001", 2.0, 9.0)]

        r = SrvResolver(lookup, time_fn=time_fn)
        assert r.resolve() == [("h1:8001", 1.0), ("h2:8001", 2.0)]
        # inside the smallest record TTL (5s): served from cache
        advance(4.9)
        assert r.resolve() == [("h1:8001", 1.0), ("h2:8001", 2.0)]
        assert len(calls) == 1
        # past it: re-resolved
        advance(0.2)
        r.resolve()
        assert len(calls) == 2
        assert r.resolutions == 2

    def test_records_without_ttl_use_default(self):
        time_fn, advance = self._clock()
        calls = []

        def lookup():
            calls.append(1)
            return ["h1:8001", ("h2:8001", 2.0)]

        r = SrvResolver(lookup, default_ttl_s=30.0, time_fn=time_fn)
        assert r.resolve() == ["h1:8001", ("h2:8001", 2.0)]
        advance(29.0)
        r.resolve()
        assert len(calls) == 1
        advance(2.0)
        r.resolve()
        assert len(calls) == 2

    def test_zero_ttl_floored_not_a_hot_loop(self):
        time_fn, _advance = self._clock()
        calls = []

        def lookup():
            calls.append(1)
            return [("h1:8001", 1.0, 0.0)]  # misconfigured zone

        r = SrvResolver(lookup, min_ttl_s=1.0, time_fn=time_fn)
        r.resolve()
        r.resolve()  # same instant: still cached (TTL floored to 1s)
        assert len(calls) == 1

    def test_lookup_failure_serves_last_known_good(self):
        time_fn, advance = self._clock()
        answers = [["h1:8001"], RuntimeError("registry down"), ["h2:8001"]]

        def lookup():
            answer = answers.pop(0)
            if isinstance(answer, Exception):
                raise answer
            return answer

        r = SrvResolver(lookup, default_ttl_s=5.0, min_ttl_s=1.0,
                        time_fn=time_fn)
        assert r.resolve() == ["h1:8001"]
        advance(6.0)  # TTL expired, lookup now fails
        assert r.resolve() == ["h1:8001"]  # stale-on-error
        assert r.errors == 1 and "registry down" in str(r.last_error)
        # the outage re-probes after the floor, not the full TTL
        advance(1.1)
        assert r.resolve() == ["h2:8001"]

    def test_initial_failure_raises(self):
        def lookup():
            raise RuntimeError("cold start, registry down")

        r = SrvResolver(lookup)
        with pytest.raises(RuntimeError):
            r.resolve()
        # DiscoveryLoop contains it like any resolver error
        pool = EndpointPool(["seed:8001"])
        loop = DiscoveryLoop(pool, r, interval_s=3600)
        assert loop.refresh_now() is None
        assert pool.urls() == ["seed:8001"]  # last-known-good membership

    def test_feeds_discovery_loop_on_ttl_churn(self):
        time_fn, advance = self._clock()
        membership = [["a:8001", "b:8001"], ["b:8001", "c:8001"]]

        def lookup():
            return [(u, 1.0, 2.0) for u in membership[0]]

        r = SrvResolver(lookup, time_fn=time_fn)
        pool = EndpointPool(["a:8001"])
        loop = DiscoveryLoop(pool, r, interval_s=3600)
        assert loop.refresh_now() is not None
        assert sorted(pool.urls()) == ["a:8001", "b:8001"]
        membership.pop(0)
        advance(3.0)  # TTL expiry picks up the new records
        summary = loop.refresh_now()
        assert summary["added"] == ["c:8001"]
        assert "a:8001" in summary["retired"]


class TestDiscoveryLoop:
    def test_refresh_applies_membership(self):
        pool = EndpointPool(["a", "b"])
        members = [["a", "b", "c"]]
        loop = DiscoveryLoop(pool, CallableResolver(lambda: members[0]))
        summary = loop.refresh_now()
        assert summary["added"] == ["c"]
        assert sorted(pool.urls()) == ["a", "b", "c"]
        assert loop.updates == 1 and loop.errors == 0

    def test_resolver_error_keeps_last_known_good(self):
        pool = EndpointPool(["a", "b"])

        def flaky():
            raise RuntimeError("registry outage")

        loop = DiscoveryLoop(pool, CallableResolver(flaky))
        assert loop.refresh_now() is None
        assert sorted(pool.urls()) == ["a", "b"]  # membership untouched
        assert loop.errors == 1
        assert isinstance(loop.last_error, RuntimeError)

    def test_empty_membership_refused(self):
        pool = EndpointPool(["a"])
        loop = DiscoveryLoop(pool, CallableResolver(lambda: []))
        assert loop.refresh_now() is None
        assert pool.urls() == ["a"]
        assert loop.errors == 1

    def test_background_polling(self):
        pool = EndpointPool(["a"])
        members = [["a"]]
        with DiscoveryLoop(
            pool, CallableResolver(lambda: members[0]), interval_s=0.02
        ).start():
            members[0] = ["a", "b"]
            assert _wait_for(lambda: "b" in pool.urls())


# -- live membership ---------------------------------------------------------


class TestMembership:
    def test_add_without_prober_is_immediately_routable(self):
        pool = EndpointPool(["a"])
        summary = pool.update_endpoints(["a", "b"])
        assert summary["added"] == ["b"]
        assert pool.phases() == {"a": PHASE_ACTIVE, "b": PHASE_ACTIVE}
        seen = set()
        for _ in range(6):
            lease = pool.lease()
            seen.add(lease.url)
            lease.success()
        assert seen == {"a", "b"}

    def test_add_with_prober_enters_probation(self):
        states = {"a": SERVER_READY, "b": SERVER_NOT_READY}
        pool = EndpointPool(["a"])
        pool.start_probes(lambda url: states[url], interval_s=0.02)
        try:
            pool.update_endpoints(["a", "b"])
            assert pool.phases()["b"] == PHASE_PROBATION
            # unproven: never takes traffic while its probe says not-ready
            for _ in range(8):
                lease = pool.lease()
                assert lease.url == "a"
                lease.success()
            # first READY probe promotes it
            states["b"] = SERVER_READY
            assert _wait_for(lambda: pool.phases()["b"] == PHASE_ACTIVE)
            seen = set()
            for _ in range(8):
                lease = pool.lease()
                seen.add(lease.url)
                lease.success()
            assert "b" in seen
        finally:
            pool.close()

    def test_retire_waits_for_inflight_then_evicts(self):
        pool = EndpointPool(["a", "b"])
        held = pool.lease(excluded=("b",))
        assert held.url == "a"
        summary = pool.update_endpoints(["b"])
        assert summary["retired"] == ["a"]
        assert summary["evicted"] == []
        assert pool.phases()["a"] == PHASE_RETIRING
        # no NEW leases on the retiring endpoint, in-flight finishes
        for _ in range(6):
            lease = pool.lease()
            assert lease.url == "b"
            lease.success()
        held.success()  # the in-flight lease finishes -> eviction
        assert pool.urls() == ["b"]

    def test_idle_retiree_evicted_immediately(self):
        pool = EndpointPool(["a", "b"])
        summary = pool.update_endpoints(["b"])
        assert summary["retired"] == ["a"]
        assert summary["evicted"] == ["a"]
        assert pool.urls() == ["b"]

    def test_unretire_on_flap_back(self):
        pool = EndpointPool(["a", "b"])
        held = pool.lease(excluded=("b",))
        pool.update_endpoints(["b"])
        assert pool.phases()["a"] == PHASE_RETIRING
        summary = pool.update_endpoints(["a", "b"])
        assert summary["unretired"] == ["a"]
        assert pool.phases()["a"] == PHASE_ACTIVE
        held.success()
        assert sorted(pool.urls()) == ["a", "b"]

    def test_last_healthy_endpoint_is_never_evicted(self):
        pool = EndpointPool(["a", "b"])
        pool.set_state("b", SERVER_UNREACHABLE)
        # resolver flap says "only b" — but b is dead and a is the last
        # healthy endpoint: the safety valve retains it
        summary = pool.update_endpoints(["b"])
        assert summary["retained"] == ["a"]
        assert summary["retired"] == []
        assert pool.phases()["a"] == PHASE_ACTIVE
        lease = pool.lease()
        assert lease.url == "a"
        lease.success()

    def test_safety_valve_releases_once_replacement_is_healthy(self):
        states = {"a": SERVER_READY, "b": SERVER_NOT_READY}
        pool = EndpointPool(["a"])
        pool.start_probes(lambda url: states[url], interval_s=0.02)
        try:
            pool.update_endpoints(["b"])  # b unproven: a retained
            assert pool.phases()["a"] == PHASE_ACTIVE
            states["b"] = SERVER_READY
            assert _wait_for(lambda: pool.phases().get("b") == PHASE_ACTIVE)
            summary = pool.update_endpoints(["b"])  # now a can retire
            assert summary["retired"] == ["a"] or summary["evicted"] == ["a"]
            assert _wait_for(lambda: pool.urls() == ["b"])
        finally:
            pool.close()

    def test_update_rejects_empty_and_duplicates(self):
        pool = EndpointPool(["a"])
        with pytest.raises(ValueError, match="empty"):
            pool.update_endpoints([])
        with pytest.raises(ValueError, match="duplicate"):
            pool.update_endpoints(["b", "b"])
        assert pool.urls() == ["a"]  # both rejections left the pool intact

    def test_update_applies_weights(self):
        pool = EndpointPool([("a", 1.0)])
        pool.update_endpoints([("a", 3.0), ("b", 0.5)])
        weights = {s["url"]: s["weight"] for s in pool.snapshot()}
        assert weights == {"a": 3.0, "b": 0.5}

    def test_membership_metrics(self):
        registry = Registry()
        pool = EndpointPool(
            ["a", "b"], observer=BalancerMetricsObserver(registry)
        )
        pool.update_endpoints(["a", "c"])  # add c, retire+evict b (idle)

        def changes(op, url):
            return registry.get(
                "ctpu_client_membership_changes_total",
                {"op": op, "endpoint": url},
            )

        assert changes("add", "c") == 1
        assert changes("retire", "b") == 1
        assert changes("evict", "b") == 1
        assert registry.get(
            "ctpu_client_pool_endpoints", {"phase": "active"}
        ) == 2
        assert registry.get(
            "ctpu_client_endpoint_phase", {"endpoint": "c"}
        ) == 0  # active (no prober -> no probation)
        # an evicted endpoint's gauges are dropped, not parked at their
        # last value forever (counters remain: they are history)
        assert registry.get(
            "ctpu_client_endpoint_phase", {"endpoint": "b"}
        ) is None
        assert registry.get(
            "ctpu_client_endpoint_state", {"endpoint": "b"}
        ) is None


# -- probation ramp-up / slow start (satellite) ------------------------------


class TestProbationRampup:
    def test_ramp_fraction_math(self):
        e = Endpoint("a")
        assert e.ramp_fraction() == 1.0  # never promoted: full share
        e.ramp_started, e.ramp_span, e.ramp_floor = 100.0, 10.0, 0.1
        assert e.ramp_fraction(now=100.0) == pytest.approx(0.1)  # floored
        assert e.ramp_fraction(now=105.0) == pytest.approx(0.5)
        assert e.ramp_fraction(now=107.0) == pytest.approx(0.7)
        assert e.ramp_fraction(now=110.5) == 1.0
        assert e.ramp_started is None  # completed ramp clears itself

    def test_weighted_policy_ramp_not_double_applied(self):
        """The ramp lives in the pool's candidate thinning ONLY: a ramping
        replica at fraction f must get ~f of its fair share under the
        weighted policy, not ~f^2 (thinning AND weight-scaling would
        compound)."""
        pool = EndpointPool(
            ["a", "b"], policy="weighted", rampup_s=600.0,
            rng=random.Random(5),
        )
        try:
            b = next(e for e in pool.endpoints() if e.url == "b")
            b.ramp_started = time.monotonic()  # fraction pinned at floor
            b.ramp_span, b.ramp_floor = 600.0, 0.2
            policy = pool._policy
            policy._rng = random.Random(11)
            counts = {"a": 0, "b": 0}
            n = 1000
            for _ in range(n):
                lease = pool.lease()
                counts[lease.url] += 1
                lease.success()
            share = counts["b"] / n
            # expected: survives thinning w.p. 0.2, then equal-weight pick
            # among {a,b} -> ~0.1; the f^2 bug would give ~0.02
            assert 0.05 < share < 0.18, counts
        finally:
            pool.close()

    def test_promoted_replica_slow_starts_then_ramps_to_full(self):
        states = {"a": SERVER_READY, "b": SERVER_NOT_READY}
        pool = EndpointPool(
            ["a"], rampup_s=60.0, rng=random.Random(7)
        )
        pool.start_probes(lambda url: states[url], interval_s=0.02)
        try:
            pool.update_endpoints(["a", "b"])
            states["b"] = SERVER_READY
            assert _wait_for(lambda: pool.phases()["b"] == PHASE_ACTIVE)
            b = next(e for e in pool.endpoints() if e.url == "b")
            assert b.ramp_started is not None  # promote stamped the ramp

            def share(n=400):
                counts = {"a": 0, "b": 0}
                for _ in range(n):
                    lease = pool.lease()
                    counts[lease.url] += 1
                    lease.success()
                return counts["b"] / n

            # freshly promoted: thinning holds b well under its fair 50%
            assert share() < 0.25
            # mid-window: share grows but stays below fair
            b.ramp_started = time.monotonic() - 24.0  # 40% through
            assert 0.05 < share() < 0.45
            # past the window: full fair share again (round-robin ~50%)
            b.ramp_started = time.monotonic() - 120.0
            assert share() > 0.4
            assert b.ramp_started is None  # ramp state self-cleared
        finally:
            pool.close()

    def test_thinning_exempts_sticky_sequences(self):
        """A ramping replica must never be thinned out from under the
        sequences pinned to it: the sticky policy reads a missing pinned
        candidate as replica death and forces a SequenceRestartError —
        a fabricated restart on a perfectly healthy replica."""
        pool = EndpointPool(
            ["a", "b"], policy="sticky", rampup_s=600.0,
            rng=random.Random(3),
        )
        try:
            b = next(e for e in pool.endpoints() if e.url == "b")
            # force b deep into a ramp window (fraction at the floor)
            b.ramp_started = time.monotonic()
            b.ramp_span, b.ramp_floor = 600.0, 0.1
            ctx = {"sequence_id": 42}
            pinned = pool.lease(request_ctx=ctx)
            pinned_url = pinned.url
            pinned.success()
            for _ in range(100):
                lease = pool.lease(request_ctx=ctx)  # must never raise
                assert lease.url == pinned_url
                lease.success()
        finally:
            pool.close()

    def test_rampup_disabled_promotes_at_full_share(self):
        states = {"a": SERVER_READY, "b": SERVER_NOT_READY}
        pool = EndpointPool(["a"])  # rampup_s=0: no slow start
        pool.start_probes(lambda url: states[url], interval_s=0.02)
        try:
            pool.update_endpoints(["a", "b"])
            states["b"] = SERVER_READY
            assert _wait_for(lambda: pool.phases()["b"] == PHASE_ACTIVE)
            b = next(e for e in pool.endpoints() if e.url == "b")
            assert b.ramp_started is None
            counts = {"a": 0, "b": 0}
            for _ in range(100):
                lease = pool.lease()
                counts[lease.url] += 1
                lease.success()
            assert counts["b"] > 30  # instant full rotation share
        finally:
            pool.close()


# -- probe jitter (satellite) ------------------------------------------------


class TestProbeJitter:
    def test_probe_times_spread(self):
        """A fleet's first probes must not land in lockstep: per-endpoint
        full jitter spreads them across the probe interval."""
        urls = [f"ep{i}" for i in range(8)]
        times = {}
        lock = threading.Lock()
        t0 = time.monotonic()

        def probe(url):
            with lock:
                times.setdefault(url, time.monotonic() - t0)
            return SERVER_READY

        pool = EndpointPool(urls)
        interval = 0.4
        pool.start_probes(probe, interval_s=interval,
                          rng=random.Random(42))
        try:
            assert _wait_for(lambda: len(times) == len(urls), timeout_s=5)
        finally:
            pool.close()
        first = sorted(times.values())
        # not a synchronized burst: the first probes span a real fraction
        # of the interval, and no two fire at the same instant
        assert first[-1] - first[0] > 0.2 * interval
        gaps = [b - a for a, b in zip(first, first[1:])]
        assert max(gaps) > 0.02

    def test_probes_cover_discovered_endpoints(self):
        probed = set()
        lock = threading.Lock()

        def probe(url):
            with lock:
                probed.add(url)
            return SERVER_READY

        pool = EndpointPool(["a"])
        pool.start_probes(probe, interval_s=0.02)
        try:
            pool.update_endpoints(["a", "b"])
            assert _wait_for(lambda: "b" in probed)
            assert _wait_for(lambda: pool.phases()["b"] == PHASE_ACTIVE)
        finally:
            pool.close()


# -- sticky sequence routing -------------------------------------------------


def _eps(n):
    return [Endpoint(f"ep{i}") for i in range(n)]


class TestStickyPolicy:
    def test_sequence_pins_one_endpoint(self):
        eps = _eps(3)
        policy = Sticky()
        first = policy.pick(eps, {"sequence_id": 7})
        for _ in range(5):
            assert policy.pick(eps, {"sequence_id": 7}) is first

    def test_sequences_spread_via_fallback(self):
        eps = _eps(3)
        policy = Sticky()
        picked = {
            policy.pick(eps, {"sequence_id": seq}).url
            for seq in range(1, 7)
        }
        assert len(picked) == 3  # round-robin fallback spreads sequences

    def test_stateless_requests_fall_through(self):
        eps = _eps(2)
        policy = Sticky()
        urls = {policy.pick(eps, {}).url for _ in range(4)}
        assert urls == {"ep0", "ep1"}
        assert policy.sequences() == {}

    def test_sequence_end_drops_mapping(self):
        eps = _eps(2)
        policy = Sticky()
        policy.pick(eps, {"sequence_id": 9})
        assert 9 in policy.sequences()
        policy.pick(eps, {"sequence_id": 9, "sequence_end": True})
        assert 9 not in policy.sequences()

    def test_dead_endpoint_raises_restart_and_remaps(self):
        eps = _eps(3)
        policy = Sticky()
        pinned = policy.pick(eps, {"sequence_id": 5})
        survivors = [e for e in eps if e is not pinned]
        with pytest.raises(SequenceRestartError) as exc_info:
            policy.pick(survivors, {"sequence_id": 5})
        err = exc_info.value
        assert err.sequence_id == 5
        assert err.dead_endpoint == pinned.url
        assert err.new_endpoint in {e.url for e in survivors}
        # the restart error is NOT blind-retryable: replaying one
        # mid-sequence request is the state split it exists to prevent
        assert not RetryPolicy().retryable(err)
        # the remap is already installed: the restarted sequence sticks —
        # including the restart request itself (sequence_start honors it)
        restart = policy.pick(
            survivors, {"sequence_id": 5, "sequence_start": True}
        )
        assert restart.url == err.new_endpoint
        again = policy.pick(survivors, {"sequence_id": 5})
        assert again.url == err.new_endpoint

    def test_durable_sequence_remaps_silently(self):
        """A durable sequence's replica death never surfaces: its
        server-side state replicates through the fleet tier's sequence
        lane, so the remap is silent — the survivor rebuilds the context
        from a peer snapshot on first touch instead of forcing the
        client to restart (SequenceRestartError stays the non-durable
        contract)."""
        eps = _eps(3)
        policy = Sticky()
        ctx = {"sequence_id": 6, "sequence_durable": True}
        pinned = policy.pick(eps, ctx)
        survivors = [e for e in eps if e is not pinned]
        remapped = policy.pick(survivors, ctx)  # no raise
        assert remapped in survivors
        # the remap sticks for the rest of the sequence
        for _ in range(3):
            assert policy.pick(survivors, ctx) is remapped
        # the same death without the durable marker still raises
        bare = Sticky()
        pinned = bare.pick(eps, {"sequence_id": 7})
        survivors = [e for e in eps if e is not pinned]
        with pytest.raises(SequenceRestartError):
            bare.pick(survivors, {"sequence_id": 7})

    def test_sequence_start_keeps_healthy_mapping(self):
        eps = _eps(3)
        policy = Sticky()
        pinned = policy.pick(eps, {"sequence_id": 8})
        # a client restarting a sequence whose replica is alive stays put
        for _ in range(3):
            assert policy.pick(
                eps, {"sequence_id": 8, "sequence_start": True}
            ) is pinned

    def test_sequence_start_remaps_without_error(self):
        eps = _eps(2)
        policy = Sticky()
        pinned = policy.pick(eps, {"sequence_id": 3})
        survivors = [e for e in eps if e is not pinned]
        # an explicit restart never raises — the caller is already
        # rebuilding the sequence from its start
        fresh = policy.pick(
            survivors, {"sequence_id": 3, "sequence_start": True}
        )
        assert fresh in survivors

    def test_lru_bound(self):
        eps = _eps(2)
        policy = Sticky(max_sequences=3)
        for seq in range(1, 6):
            policy.pick(eps, {"sequence_id": seq})
        assert len(policy.sequences()) == 3
        assert set(policy.sequences()) == {3, 4, 5}

    def test_make_policy_knows_sticky(self):
        assert make_policy("sticky").name == "sticky"

    def test_replicated_client_sticky_end_to_end(self):
        """Sequences stick to one replica; killing it surfaces the
        retryable sequence-restart error instead of silently splitting
        state, and the restarted sequence lands whole on a survivor."""
        servers, logs = _start_servers(2)
        urls = [s.grpc_address for s in servers]
        client = ReplicatedClient(
            urls, transport="grpc", policy="sticky",
            probe_interval_s=None,
            retry_policy=_fast_policy(jitter=False),
            channel_args=_FAST_RECONNECT,
        )
        try:
            for step in range(4):
                client.infer(
                    "echo", _val_inputs(step), sequence_id=11,
                    sequence_start=(step == 0),
                )
            seq_counts = [
                sum(1 for seq, _ in log if seq == 11) for log in logs
            ]
            assert sorted(seq_counts) == [0, 4]  # one replica took it all
            pinned_index = seq_counts.index(4)
            servers[pinned_index].stop()
            # stop() returns while the server winds down (a grace of 2 s, in
            # which a request on the open connection is answered
            # CANCELLED): a killed replica is one whose connections are gone
            servers[pinned_index]._grpc._server.stop(grace=0).wait(5)
            with pytest.raises(SequenceRestartError):
                client.infer("echo", _val_inputs(4), sequence_id=11)
            # restart per the contract: the sequence rebuilds on the
            # survivor, whole
            for step in range(3):
                client.infer(
                    "echo", _val_inputs(100 + step), sequence_id=11,
                    sequence_start=(step == 0),
                )
            survivor_log = logs[1 - pinned_index]
            assert [
                val for seq, val in survivor_log if seq == 11
            ] == [100, 101, 102]
        finally:
            client.close()
            for s in servers:
                s.stop()


# -- resilient streaming -----------------------------------------------------


class TestResilientStreamSync:
    def _pin_to(self, pool, url):
        """Deterministic pinning: mark every other endpoint not-ready."""
        for other in pool.urls():
            if other != url:
                pool.set_state(other, SERVER_NOT_READY)

    def test_reconnect_replays_unacked_and_dedupes(self):
        servers, logs = _start_servers(2)
        proxy = FaultProxy(servers[0].grpc_address)
        url_a, url_b = proxy.address, servers[1].grpc_address
        registry = Registry()
        pool = EndpointPool(
            [url_a, url_b], observer=BalancerMetricsObserver(registry)
        )
        tracer = ClientTracer()
        client = ReplicatedClient(
            pool, transport="grpc", probe_interval_s=None,
            tracer=tracer, retry_policy=_fast_policy(jitter=False),
            channel_args=_FAST_RECONNECT,
        )
        events = []
        got = threading.Event()
        lock = threading.Lock()

        def callback(result, error):
            with lock:
                events.append((result, error))
            got.set()

        self._pin_to(pool, url_a)
        stream = client.resilient_stream(callback)
        try:
            assert stream.url == url_a
            pool.set_state(url_b, SERVER_READY)
            rid0 = stream.async_stream_infer("echo", _val_inputs(0))
            assert _wait_for(lambda: len(events) == 1, timeout_s=10)
            # queue sleepy requests so the kill catches them in flight
            rids = [
                stream.async_stream_infer("echo", _val_inputs(_SLEEPY + i))
                for i in range(3)
            ]
            time.sleep(0.05)
            proxy.refuse_connections(True)
            proxy.kill_active()
            assert _wait_for(lambda: len(events) == 4, timeout_s=15)
            rid_after = stream.async_stream_infer("echo", _val_inputs(7))
            assert _wait_for(lambda: len(events) == 5, timeout_s=10)

            with lock:
                assert all(err is None for _, err in events)
                answered = [r.get_response().id for r, _ in events]
            # exactly-once to the callback: every request id answered once
            assert sorted(answered) == sorted([rid0] + rids + [rid_after])
            assert stream.reconnects == 1
            assert stream.replayed >= 1
            assert stream.url == url_b
            # the hop and the replay are on the metrics surface
            assert registry.get(
                "ctpu_client_stream_reconnects_total", {"endpoint": url_a}
            ) == 1
            assert registry.get(
                "ctpu_client_stream_replayed_requests_total",
                {"endpoint": url_b},
            ) >= 1
            # ... and on one trace: consecutive endpoint-tagged attempts
            # under a single trace id
            hops = stream.trace.attempt_endpoints()
            assert hops[0] == url_a and hops[-1] == url_b
        finally:
            stream.close()
            client.close()
            proxy.close()
            for s in servers:
                s.stop()
        # closing released every inflight slot
        assert all(s["inflight"] == 0 for s in pool.snapshot())

    def test_app_error_propagates_without_reconnect(self):
        servers, logs = _start_servers(1)
        client = ReplicatedClient(
            [servers[0].grpc_address], transport="grpc",
            probe_interval_s=None,
            retry_policy=_fast_policy(jitter=False),
        )
        events = []
        lock = threading.Lock()

        def callback(result, error):
            with lock:
                events.append((result, error))

        stream = client.resilient_stream(callback)
        try:
            stream.async_stream_infer("echo", _val_inputs(_BAD))
            stream.async_stream_infer("echo", _val_inputs(1))
            assert _wait_for(lambda: len(events) == 2, timeout_s=10)
            with lock:
                errors = [err for _, err in events if err is not None]
            assert len(errors) == 1
            assert errors[0].status() == "400"
            assert stream.reconnects == 0  # answered error: no failover
        finally:
            stream.close()
            client.close()
            servers[0].stop()

    def test_independent_of_pinned_stream_slot(self):
        """A ResilientStream must coexist with the pinned start_stream on
        the SAME endpoint (it owns its transport client, so the one-
        stream-per-client limit never collides)."""
        servers, _ = _start_servers(1)
        client = ReplicatedClient(
            [servers[0].grpc_address], transport="grpc",
            probe_interval_s=None,
            retry_policy=_fast_policy(jitter=False),
        )
        pinned_events, resilient_events = [], []
        pinned_got = threading.Event()

        def pinned_cb(result, error):
            pinned_events.append((result, error))
            pinned_got.set()

        client.start_stream(pinned_cb)  # occupies the per-endpoint slot
        stream = client.resilient_stream(
            lambda result, error: resilient_events.append((result, error))
        )
        try:
            client.async_stream_infer("echo", _val_inputs(1))
            stream.async_stream_infer("echo", _val_inputs(2))
            assert pinned_got.wait(timeout=10)
            assert _wait_for(lambda: len(resilient_events) == 1,
                             timeout_s=10)
            assert pinned_events[0][1] is None
            assert resilient_events[0][1] is None
        finally:
            stream.close()
            client.close()
            servers[0].stop()

    def test_terminal_when_no_replica_left(self):
        servers, _ = _start_servers(1)
        proxy = FaultProxy(servers[0].grpc_address)
        client = ReplicatedClient(
            [proxy.address], transport="grpc", probe_interval_s=None,
            retry_policy=_fast_policy(
                max_attempts=2, jitter=False, initial_backoff_s=0.01
            ),
            channel_args=_FAST_RECONNECT,
        )
        events = []
        done = threading.Event()

        def callback(result, error):
            events.append((result, error))
            if error is not None:
                done.set()

        stream = client.resilient_stream(callback)
        try:
            stream.async_stream_infer("echo", _val_inputs(_SLEEPY))
            time.sleep(0.05)
            proxy.refuse_connections(True)
            proxy.kill_active()
            assert done.wait(timeout=15)
            terminal = [e for _, e in events if e is not None]
            assert terminal  # non-recoverable death reached the caller
        finally:
            stream.close()
            client.close()
            proxy.close()
            servers[0].stop()


class TestResilientStreamAio:
    def test_reconnect_replays_and_dedupes(self):
        servers, logs = _start_servers(2)
        proxy = FaultProxy(servers[0].grpc_address)
        url_a, url_b = proxy.address, servers[1].grpc_address

        class Feed:
            def __init__(self):
                self.queue = asyncio.Queue()

            def __aiter__(self):
                return self

            async def __anext__(self):
                item = await self.queue.get()
                if item is None:
                    raise StopAsyncIteration
                return item

        async def flow():
            pool = EndpointPool([url_a, url_b])
            client = AsyncReplicatedClient(
                pool, transport="grpc",
                retry_policy=_fast_policy(jitter=False),
                channel_args=_FAST_RECONNECT,
            )
            pool.set_state(url_b, SERVER_NOT_READY)  # pin to the proxy
            feed = Feed()
            stream = client.resilient_stream_infer(feed)
            results = []
            try:
                await feed.queue.put(
                    {"model_name": "echo", "inputs": _val_inputs(0),
                     "request_id": "r0"}
                )
                results.append(await stream.__anext__())
                pool.set_state(url_b, SERVER_READY)
                for i in range(3):
                    await feed.queue.put({
                        "model_name": "echo",
                        "inputs": _val_inputs(_SLEEPY + i),
                        "request_id": f"r{i + 1}",
                    })
                await asyncio.sleep(0.1)  # let them reach the wire
                proxy.refuse_connections(True)
                proxy.kill_active()
                await feed.queue.put(
                    {"model_name": "echo", "inputs": _val_inputs(9),
                     "request_id": "r4"}
                )
                await feed.queue.put(None)
                async for pair in stream:
                    results.append(pair)
                assert all(err is None for _, err in results)
                answered = [r.get_response().id for r, _ in results]
                # exactly-once per request id, across the reconnect
                assert sorted(answered) == ["r0", "r1", "r2", "r3", "r4"]
            finally:
                await stream.aclose()
                await client.close()
            assert all(s["inflight"] == 0 for s in pool.snapshot())

        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(flow())
        finally:
            loop.close()
            proxy.close()
            for s in servers:
                s.stop()

    def test_duplicate_request_id_rejected(self):
        """A reused request id would clobber the replay buffer and eat
        the second response — the aio path rejects it like the sync one."""
        servers, _ = _start_servers(1)

        async def flow():
            client = AsyncReplicatedClient(
                [servers[0].grpc_address], transport="grpc",
                retry_policy=_fast_policy(jitter=False),
            )

            async def feed():
                for _ in range(2):
                    yield {"model_name": "echo", "inputs": _val_inputs(1),
                           "request_id": "dup"}

            stream = client.resilient_stream_infer(feed())
            try:
                with pytest.raises(InferenceServerException,
                                   match="duplicate request id"):
                    async for _pair in stream:
                        pass
            finally:
                await stream.aclose()
                await client.close()
            assert all(
                s["inflight"] == 0 for s in client.pool.snapshot()
            )

        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(flow())
        finally:
            loop.close()
            servers[0].stop()


# -- churn chaos acceptance --------------------------------------------------


def _run_churn_scenario():
    """Sustained load while the fleet churns: add a replica, retire a
    replica, kill the stream-pinned replica, flap the resolver.  Zero
    client-visible errors, exactly-once responses per stream request, no
    request applied twice to a sequence on any replica, the last healthy
    endpoint never evicted, and metrics + a shared-trace-id timeline
    prove the reconnect hop."""
    servers, logs = _start_servers(3)
    proxies = [FaultProxy(s.grpc_address) for s in servers]
    urls = [p.address for p in proxies]
    by_url = dict(zip(urls, range(3)))

    membership = {"urls": list(urls), "flap": False}
    membership_lock = threading.Lock()

    def resolve():
        with membership_lock:
            if membership["flap"]:
                raise RuntimeError("resolver flap")
            return list(membership["urls"])

    registry = Registry()
    pool = EndpointPool(
        urls, policy="round-robin",
        observer=BalancerMetricsObserver(registry),
        failure_threshold=3, reset_timeout_s=60.0,
    )
    tracer = ClientTracer(max_traces=10000)
    client = ReplicatedClient(
        pool, transport="grpc",
        probe_interval_s=0.05,
        resolver=CallableResolver(resolve), discovery_interval_s=0.05,
        tracer=tracer,
        retry_policy=RetryPolicy(
            max_attempts=8, initial_backoff_s=0.02, max_backoff_s=0.2,
            deadline_s=20.0,
        ),
        channel_args=_FAST_RECONNECT,
    )

    # watcher: the pool must never go empty of healthy routable replicas
    min_healthy = [99]
    watch_stop = threading.Event()

    def watcher():
        while not watch_stop.is_set():
            snapshot = client.pool.snapshot()
            healthy = sum(
                1 for s in snapshot
                if s["phase"] == PHASE_ACTIVE and s["state"] == SERVER_READY
            )
            min_healthy[0] = min(min_healthy[0], healthy)
            time.sleep(0.01)

    # unary load
    errors = []
    load_lock = threading.Lock()

    def unary_worker(worker_id):
        for i in range(40):
            try:
                client.infer("echo", _val_inputs(10000 * worker_id + i))
            except Exception as exc:  # noqa: BLE001 - recorded, asserted
                with load_lock:
                    errors.append(exc)
            time.sleep(0.005)

    # resilient stream carrying a sequence
    stream_events = []
    stream_lock = threading.Lock()

    def stream_callback(result, error):
        with stream_lock:
            stream_events.append((result, error))

    threads = [
        threading.Thread(target=unary_worker, args=(w,)) for w in range(3)
    ]
    watch_thread = threading.Thread(target=watcher)
    new_server = None
    stream = None
    try:
        watch_thread.start()
        for t in threads:
            t.start()
        stream = client.resilient_stream(stream_callback)
        victim_url = stream.url
        assert victim_url in urls
        victim_proxy = proxies[by_url[victim_url]]
        retire_url = next(u for u in urls if u != victim_url)

        sent = []
        for step in range(10):
            sent.append(stream.async_stream_infer(
                "echo", _val_inputs(step), sequence_id=7,
                sequence_start=(step == 0),
            ))
        assert _wait_for(
            lambda: len(stream_events) == len(sent), timeout_s=30
        )

        # (1) grow the fleet: a new replica joins through discovery,
        # passes probation, and starts taking traffic
        log_d, lock_d = [], threading.Lock()
        new_server = Server(
            models=[_recording_model("echo", log_d, lock_d)],
            with_default_models=False, grpc_port=0,
        ).start()
        with membership_lock:
            membership["urls"].append(new_server.grpc_address)
        assert _wait_for(
            lambda: client.pool.phases().get(new_server.grpc_address)
            == PHASE_ACTIVE,
            timeout_s=10,
        )

        # (2) retire a replica gracefully
        with membership_lock:
            membership["urls"].remove(retire_url)
        assert _wait_for(
            lambda: retire_url not in client.pool.urls(), timeout_s=10
        )

        # (3) kill the stream-pinned replica mid-stream, with requests
        # in flight (sleepy values), and keep the sequence going
        burst = [
            stream.async_stream_infer(
                "echo", _val_inputs(_SLEEPY + step), sequence_id=7
            )
            for step in range(10, 14)
        ]
        sent.extend(burst)
        time.sleep(0.05)
        victim_proxy.refuse_connections(True)
        victim_proxy.kill_active()
        for step in range(14, 18):
            sent.append(stream.async_stream_infer(
                "echo", _val_inputs(step), sequence_id=7
            ))

        # (4) flap the resolver: errors keep last-known-good membership
        with membership_lock:
            membership["flap"] = True
            flap_urls = set(client.pool.urls())
        time.sleep(0.2)
        with membership_lock:
            membership["flap"] = False
        assert set(client.pool.urls()) == flap_urls
        assert client.discovery.errors > 0

        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert _wait_for(
            lambda: len(stream_events) == len(sent), timeout_s=40
        )

        # zero client-visible errors, unary and stream
        assert errors == []
        with stream_lock:
            assert all(err is None for _, err in stream_events)
            answered = [r.get_response().id for r, _ in stream_events]
        # exactly-once responses per request id across the reconnect
        assert sorted(answered) == sorted(sent)

        # no request applied twice to the sequence on ANY replica
        all_logs = logs + [log_d]
        for log in all_logs:
            seq_vals = [val for seq, val in log if seq == 7]
            assert len(seq_vals) == len(set(seq_vals))

        # the last healthy endpoint was never evicted (pool never empty)
        assert min_healthy[0] >= 1

        # membership metrics prove the churn
        def changes(op, url):
            return registry.get(
                "ctpu_client_membership_changes_total",
                {"op": op, "endpoint": url},
            )

        assert changes("add", new_server.grpc_address) == 1
        assert changes("promote", new_server.grpc_address) == 1
        assert changes("retire", retire_url) == 1
        assert changes("evict", retire_url) == 1
        # reconnect + replay metrics prove the stream hop
        assert registry.get(
            "ctpu_client_stream_reconnects_total", {"endpoint": victim_url}
        ) == 1
        assert stream.reconnects == 1 and stream.replayed >= 1
        new_home = stream.url
        assert registry.get(
            "ctpu_client_stream_replayed_requests_total",
            {"endpoint": new_home},
        ) >= 1
        # shared-trace-id timeline: the stream is ONE span whose
        # endpoint-tagged attempts hop from the victim to the new home
        hops = stream.trace.attempt_endpoints()
        assert hops[0] == victim_url
        assert hops[-1] == new_home
        assert len(set(hops)) > 1
    finally:
        watch_stop.set()
        watch_thread.join(timeout=5)
        if stream is not None:
            stream.close()
        client.close()
        for p in proxies:
            p.close()
        for s in servers:
            s.stop()
        if new_server is not None:
            new_server.stop()


class TestChurnChaos:
    # a 21 s chaos round: under the slow mark since PR 21 bought tier-1's
    # time back for tests/test_chip_smoke.py (ROADMAP D0); `make soak` runs
    # it, and test_churn_soak repeats the same scenario
    @pytest.mark.slow
    def test_churn_under_load(self):
        _run_churn_scenario()

    @pytest.mark.slow
    def test_churn_soak(self):
        """`make soak`: the same scenario, repeated — churn bugs are
        timing bugs, and repetition is how they surface."""
        for _ in range(3):
            _run_churn_scenario()
