"""Chaos-matrix acceptance: fault-domain hardening end to end.

Three layers under test:

1. the harness itself (``client_tpu/testing/chaos.py``): seeded
   deterministic schedules, the exactly-once step ledger, fault
   dispatch, driver error/wedge collection;
2. replicated sequence state at the engine level: durable
   ``SequenceContext`` snapshots push to peers at each applied step,
   a survivor resumes them (stale rejected, duplicate steps replayed
   idempotently, step gaps rejected);
3. the two fleet acceptances as one-scenario matrix entries:
   - **SIGKILL with active durable sequences** — three real HTTP servers
     behind chaos proxies, a sticky ``ReplicatedClient`` driving durable
     sequences, replica 0 SIGKILLed mid-sequence: every sequence resumes
     byte-exact on a survivor, zero client-visible errors, no
     ``(sequence, step)`` applied twice (orphaned applies on the corpse
     excepted);
   - **anti-entropy convergence** — hot prefix chains proactively pushed
     to peers survive replica 0's SIGKILL: the dead replica's chains are
     retrievable from survivors and save prefill there, byte-exact.

``make soak`` repeats the slow-marked scaled variants.
"""

import json
import os
import threading
import time
import types

import numpy as np
import pytest

import jax

from client_tpu import traceview
from client_tpu.balance.replicated import ReplicatedClient
from client_tpu.serve import InferenceEngine, Model, Server, TensorSpec
from client_tpu.serve.fleet import FleetTier
from client_tpu.serve.flight import FlightRecorder
from client_tpu.tracing import ClientTracer
from client_tpu.serve.lm import LmEngine
from client_tpu.serve.metrics import Registry
from client_tpu.serve.models import transformer as tfm
from client_tpu.testing.chaos import (
    ChaosMatrix,
    ChaosScenario,
    FaultSpec,
    StepLedger,
    assert_byte_exact,
    assert_kv_clean,
    dispatch_fault,
    run_scenario,
)
from client_tpu.testing.faults import FaultProxy

CLOSE = LmEngine.CLOSE


def _tier(**kwargs):
    kwargs.setdefault("gossip_interval_s", 0)
    return FleetTier(**kwargs).start()


def _peer_up(tiers):
    for tier in tiers:
        tier.set_peers([t.address for t in tiers if t is not tier])


def _seq_model(ledger, replica, name="chaos_sequence", busy_s=0.0):
    """Stateful accumulator that records every APPLIED step into the
    ledger — idempotent replays served from the retained rendering never
    reach this function, which is exactly what the exactly-once checker
    verifies.  ``busy_s`` holds the request in-flight so load is visible
    as engine pressure (the autoscale ramp's scale signal)."""

    def fn(inputs, params, ctx):
        value = inputs["INPUT"]
        if busy_s:
            time.sleep(busy_s)
        if ctx is None:
            return {"OUTPUT": value}
        if params.get("sequence_start") or "acc" not in ctx.state:
            ctx.state["acc"] = np.zeros_like(value)
        ctx.state["acc"] = ctx.state["acc"] + value
        ledger.record(ctx.sequence_id, ctx.step + 1, replica)
        return {"OUTPUT": ctx.state["acc"].copy()}

    return Model(
        name,
        inputs=[TensorSpec("INPUT", "INT32", [1])],
        outputs=[TensorSpec("OUTPUT", "INT32", [1])],
        fn=fn,
        stateful=True,
    )


def _seq_request(value, sid, step, start=False, end=False, durable=True):
    return {
        "id": f"s{sid}-{step}",
        "inputs": [{
            "name": "INPUT", "shape": [1], "datatype": "INT32",
            "data": [int(value)],
        }],
        "parameters": {
            "sequence_id": sid,
            "sequence_start": bool(start),
            "sequence_end": bool(end),
            "sequence_durable": bool(durable),
            "sequence_step": int(step),
        },
    }


def _out_value(response):
    return int(response["outputs"][0]["data"][0])


# -- harness units ----------------------------------------------------------

def test_scenario_schedule_is_seed_deterministic():
    faults = [
        FaultSpec("kill_replica", at_s=("uniform", 0.1, 0.9), target=0),
        FaultSpec("refuse", at_s=0.05, target=1),
    ]
    a = ChaosScenario("s", faults, seed=42).schedule()
    b = ChaosScenario("s", faults, seed=42).schedule()
    c = ChaosScenario("s", faults, seed=43).schedule()
    assert [t for t, _ in a] == [t for t, _ in b]  # same seed, same times
    assert [t for t, _ in a] != [t for t, _ in c]  # different seed differs
    assert a[0][1].kind == "refuse"  # sorted by time
    assert 0.1 <= a[1][0] <= 0.9
    with pytest.raises(ValueError):
        ChaosScenario(
            "bad", [FaultSpec("refuse", at_s=("gauss", 0, 1))]
        ).schedule()


def test_step_ledger_exactly_once_semantics():
    ledger = StepLedger()
    ledger.record(1, 1, "r0")
    ledger.record(1, 2, "r0")
    ledger.record(1, 3, "r0")   # applied on r0 but unacked: r0 dies
    ledger.record(1, 3, "r1")   # survivor re-applies from the snapshot
    ledger.record(1, 4, "r1")
    ledger.assert_exactly_once(orphans={"r0"})  # the resume carve-out
    with pytest.raises(AssertionError):
        ledger.assert_exactly_once()  # without the orphan: a duplicate
    assert ledger.steps_for(1) == [1, 2, 3, 4]
    # duplicates on one replica always fail, orphaned or not
    dup = StepLedger()
    dup.record(7, 1, "r0")
    dup.record(7, 1, "r0")
    with pytest.raises(AssertionError):
        dup.assert_exactly_once(orphans={"r0"})
    # a re-apply whose predecessor ran on a SURVIVOR always fails
    forked = StepLedger()
    forked.record(9, 2, "r1")
    forked.record(9, 2, "r2")
    with pytest.raises(AssertionError):
        forked.assert_exactly_once(orphans={"r0"})


def test_run_scenario_collects_errors_and_wedges():
    gate = threading.Event()

    def ok():
        gate.wait(timeout=10)

    def boom():
        raise RuntimeError("driver died")

    scenario = ChaosScenario(
        "units", [FaultSpec("custom", at_s=0.0, fn=gate.set)]
    )
    result = run_scenario(scenario, lambda f: dispatch_fault(f), [ok, boom])
    assert result.wedged == 0
    assert len(result.errors) == 1 and result.errors[0][0] == 1
    with pytest.raises(AssertionError):
        result.assert_clean()
    # a driver that outlives the join timeout is reported wedged
    slow = threading.Event()
    try:
        result = run_scenario(
            ChaosScenario("wedge"), lambda f: None,
            [lambda: slow.wait(timeout=5)], join_timeout_s=0.1,
        )
        assert result.wedged == 1
    finally:
        slow.set()


def test_chaos_matrix_round_under_race_witness(tmp_path):
    """A chaos-matrix round with the dynamic race witness armed (the
    TPULINT_RACE_WITNESS=1 shape `make chaos` runs): concurrent drivers
    hammering the @witness_shared StepLedger stay green through the
    assert_race_witness_clean invariant, and a seeded unguarded-write
    fixture goes red — with the violation evidence dumped to the
    fixture's flight recorder."""
    from client_tpu.analysis.witness import RaceViolation, RaceWitness
    from client_tpu.testing.chaos import assert_race_witness_clean

    class _LedgerFixture:
        def __init__(self, racy):
            self.racy = racy
            self.ledger = StepLedger()  # @witness_shared("_lock")
            self.flight = FlightRecorder(
                dump_dir=str(tmp_path), name="race-round"
            )
            self.seq = 0

        def flight_recorders(self):
            return [self.flight]

        def apply_fault(self, fault):
            dispatch_fault(fault)

        def drivers(self):
            def drive(replica):
                def run():
                    for step in range(40):
                        self.ledger.record(replica, step, f"r{replica}")
                        if self.racy:
                            try:
                                # a deliberately unguarded shared write —
                                # SWALLOWED here so only the matrix
                                # invariant can fail the round
                                self.seq = self.seq + 1
                            except RaceViolation:
                                pass
                return run

            return [drive(0), drive(1), drive(2)]

        def check(self, result):
            result.assert_clean()
            self.ledger.assert_exactly_once()

        def close(self):
            pass

    scenario = ChaosScenario("race-witness-round")

    witness = RaceWitness()
    with witness.installed():
        ChaosMatrix(
            [scenario],
            invariants=[lambda fx, res: assert_race_witness_clean(witness)],
        ).run(lambda s: _LedgerFixture(racy=False))
    assert witness.assert_race_free() > 0  # the ledger WAS witnessed
    assert witness.assert_acyclic() >= 0   # lock-order duty intact

    seeded = RaceWitness()
    seeded.watch_class(_LedgerFixture, fields=("seq",))
    fixtures = []

    def make_racy(s):
        fixtures.append(_LedgerFixture(racy=True))
        return fixtures[-1]

    with seeded.installed():
        with pytest.raises(RaceViolation):
            ChaosMatrix(
                [scenario],
                invariants=[
                    lambda fx, res: assert_race_witness_clean(seeded)
                ],
            ).run(make_racy)
    assert seeded.race_violations
    # the red round dumped its own postmortem via the matrix hook
    flight = fixtures[0].flight
    kinds = [r["kind"] for r in flight.snapshot()]
    assert "chaos_invariant_failure" in kinds
    assert flight.dumps


def test_chaos_matrix_round_under_resource_witness(tmp_path):
    """A chaos-matrix round with the dynamic resource witness armed (the
    TPULINT_RESOURCE_WITNESS=1 shape `make chaos` runs): drivers cycling
    KV block reservations through alloc/release stay green through the
    assert_no_leaked_resources invariant, and a seeded leak — a
    reservation deliberately never released — goes red with the
    acquisition stack in the report."""
    from client_tpu.analysis.witness import ResourceLeakError, ResourceWitness
    from client_tpu.serve.lm.kv import KvBlockPool
    from client_tpu.testing.chaos import assert_no_leaked_resources

    cfg = tfm.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=96, dtype="float32",
    )

    class _PoolFixture:
        def __init__(self, leak):
            self.leak = leak
            self.leaked = []
            self.pool = KvBlockPool(cfg, n_blocks=16, block_size=4)
            self.flight = FlightRecorder(
                dump_dir=str(tmp_path), name="resource-round"
            )

        def flight_recorders(self):
            return [self.flight]

        def apply_fault(self, fault):
            dispatch_fault(fault)

        def drivers(self):
            def run():
                for _ in range(20):
                    blocks = self.pool.alloc(2)
                    self.pool.retain(blocks)
                    self.pool.release(blocks)
                    self.pool.release(blocks)
                if self.leak:
                    self.leaked.extend(self.pool.alloc(1))

            return [run]

        def check(self, result):
            result.assert_clean()

        def close(self):
            pass

    scenario = ChaosScenario("resource-witness-round")

    witness = ResourceWitness()
    with witness.installed():
        ChaosMatrix(
            [scenario],
            invariants=[lambda fx, res: assert_no_leaked_resources(witness)],
        ).run(lambda s: _PoolFixture(leak=False))
    assert witness.assert_clean() > 0  # the pool WAS witnessed

    seeded = ResourceWitness()
    fixtures = []

    def make_leaky(s):
        fixtures.append(_PoolFixture(leak=True))
        return fixtures[-1]

    with seeded.installed():
        with pytest.raises(ResourceLeakError) as excinfo:
            ChaosMatrix(
                [scenario],
                invariants=[
                    lambda fx, res: assert_no_leaked_resources(seeded)
                ],
            ).run(make_leaky)
    assert "kv-blocks" in str(excinfo.value)
    assert "acquired at" in str(excinfo.value)
    # drain the seeded leak so an outer session-level audit (the
    # TPULINT_RESOURCE_WITNESS=1 conftest hook `make chaos` arms) stays
    # clean — the leak was the test subject, not a real loss
    for fx in fixtures:
        fx.pool.release(fx.leaked)


def test_dispatch_fault_drives_a_fault_proxy():
    import socket

    upstream = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    upstream.bind(("127.0.0.1", 0))
    upstream.listen(4)
    proxy = FaultProxy("%s:%d" % upstream.getsockname()[:2])
    try:
        host, _, port = proxy.address.rpartition(":")
        dispatch_fault(FaultSpec("refuse", target=0), proxies=[proxy])
        # the refused connection dies at accept: either the RST raises
        # or the FIN half of the hard close races it and reads as EOF
        try:
            data = socket.create_connection(
                (host, int(port)), timeout=2
            ).recv(1)
            assert data == b"", "refused connection served data"
        except OSError:
            pass
        dispatch_fault(FaultSpec("restore", target=0), proxies=[proxy])
        sock = socket.create_connection((host, int(port)), timeout=2)
        sock.close()
        killed = []
        dispatch_fault(
            FaultSpec("kill_replica", target=0), proxies=[proxy],
            kill=killed.append,
        )
        assert killed == [0]  # sigkill + the kill hook both fired
        with pytest.raises(ValueError):
            dispatch_fault(FaultSpec("martian"), proxies=[proxy])
    finally:
        proxy.close()
        upstream.close()


# -- replicated sequence state at the engine level --------------------------

def test_durable_sequence_resumes_on_survivor_engine():
    """The tentpole's core path without HTTP in the way: durable steps
    applied on engine A replicate to B's tier; after A's death B resumes
    the sequence byte-exact, replays the duplicate step idempotently,
    and rejects a step gap with a restartable 409."""
    ledger = StepLedger()
    tier_a, tier_b = _tier(replicate_k=1), _tier(replicate_k=1)
    _peer_up([tier_a, tier_b])
    eng_a = InferenceEngine(
        models=[_seq_model(ledger, "rA")], fleet=tier_a
    )
    eng_b = InferenceEngine(
        models=[_seq_model(ledger, "rB")], fleet=tier_b
    )
    try:
        sid, total = 31, 0
        for step, value in enumerate((3, 1, 4), start=1):
            total += value
            response, _ = eng_a.execute(
                "chaos_sequence", "",
                _seq_request(value, sid, step, start=(step == 1)), b"",
            )
            assert _out_value(response) == total
        # each applied step pushed a snapshot before responding
        assert tier_a.stats()["seq_pushes"] == 3
        snap = tier_b.seq_store.get(sid)
        assert snap is not None and snap["step"] == 3
        # A dies unplanned (no drain): B sees step 4 for a sequence it
        # never met, recovers the snapshot from its tier, and continues
        tier_a.close()
        response, _ = eng_b.execute(
            "chaos_sequence", "", _seq_request(5, sid, 4), b"",
        )
        assert _out_value(response) == total + 5
        assert eng_b.metrics.get("ctpu_fleet_seq_resumes_total") == 1
        # the duplicate declared step replays from the retained
        # rendering — same bytes, NO second apply in the ledger
        replay, _ = eng_b.execute(
            "chaos_sequence", "", _seq_request(5, sid, 4), b"",
        )
        assert _out_value(replay) == total + 5
        ledger.assert_exactly_once()
        assert ledger.steps_for(sid) == [1, 2, 3, 4]
        # a declared step AHEAD of the counter is the lost-steps fork:
        # restartable 409, never a silent wrong-state apply
        from client_tpu.utils import InferenceServerException

        with pytest.raises(InferenceServerException) as exc:
            eng_b.execute(
                "chaos_sequence", "", _seq_request(9, sid, 7), b"",
            )
        assert exc.value.status() == "409"
    finally:
        eng_a.close()
        eng_b.close()
        tier_a.close()
        tier_b.close()


def test_sequence_snapshots_reject_stale_and_fork_failed_lookup():
    """Staleness + miss behavior: an older snapshot never overwrites a
    newer one, and with no tier hit a mid-sequence miss falls back to a
    fresh context (today's non-durable semantics, preserved)."""
    ledger = StepLedger()
    tier = _tier()
    engine = InferenceEngine(models=[_seq_model(ledger, "r")], fleet=tier)
    try:
        engine.execute(
            "chaos_sequence", "", _seq_request(2, 5, 1, start=True), b"",
        )
        engine.execute("chaos_sequence", "", _seq_request(3, 5, 2), b"")
        newer = engine.export_sequence(5)
        assert newer["step"] == 2
        older = dict(newer)
        older["step"] = 1
        assert tier.seq_store.put(newer) is True
        assert tier.seq_store.put(older) is False  # stale rejected
        assert tier.seq_store.get(5)["step"] == 2
        assert tier.stats()["seq_stale_rejected"] == 1
        # unknown sequence, tier miss: fresh context (state forks only
        # when there is genuinely nothing to recover)
        response, _ = engine.execute(
            "chaos_sequence", "",
            _seq_request(7, 404, 1, durable=False), b"",
        )
        assert _out_value(response) == 7
    finally:
        engine.close()
        tier.close()


def test_restarted_sequence_epoch_beats_stale_incarnation():
    """A restarted sequence id is a NEW incarnation: its fresh epoch
    must overwrite the dead incarnation's higher-step snapshots on
    peers — and a reachable peer that REJECTS a snapshot as stale must
    not count as a durability ack."""
    ledger = StepLedger()
    tier_a, tier_b = _tier(replicate_k=1), _tier(replicate_k=1)
    _peer_up([tier_a, tier_b])
    eng_a = InferenceEngine(models=[_seq_model(ledger, "rA")],
                            fleet=tier_a)
    eng_b = InferenceEngine(models=[_seq_model(ledger, "rB")],
                            fleet=tier_b)
    try:
        sid = 77
        for step in range(1, 4):
            eng_a.execute(
                "chaos_sequence", "",
                _seq_request(step, sid, step, start=(step == 1)), b"",
            )
        old = tier_b.seq_store.get(sid)
        assert old is not None and old["step"] == 3
        # the client restarts the id (the 409 contract) on replica B:
        # fresh incarnation, step 1 — its snapshot must REPLACE the old
        # incarnation's step-3 leftovers wherever they live
        response, _ = eng_b.execute(
            "chaos_sequence", "", _seq_request(9, sid, 1, start=True), b"",
        )
        assert _out_value(response) == 9
        fresh = eng_b.export_sequence(sid)
        assert fresh["epoch"] > old["epoch"]
        assert tier_b.seq_store.put(dict(fresh)) is True  # overwrites
        stored = tier_b.seq_store.get(sid)
        assert stored["step"] == 1 and stored["epoch"] == fresh["epoch"]
        # the OLD incarnation arriving late (gossip race) is now stale
        assert tier_b.seq_store.put(dict(old)) is False
        # a peer that rejects as stale is NOT a durability ack
        assert tier_a.publish_sequence(dict(old)) == 0
        # and a resume restores the NEW incarnation, not the corpse's
        restored = tier_b.seq_store.get(sid)
        assert restored["epoch"] == fresh["epoch"]
    finally:
        eng_a.close()
        eng_b.close()
        tier_a.close()
        tier_b.close()


def test_drain_exports_sequences_to_the_tier():
    """Planned retire: every live sequence's snapshot lands on a peer
    even when it was never marked durable — drain is free durability."""
    ledger = StepLedger()
    tier_a, tier_b = _tier(), _tier()
    _peer_up([tier_a, tier_b])
    engine = InferenceEngine(models=[_seq_model(ledger, "rA")],
                             fleet=tier_a)
    try:
        engine.execute(
            "chaos_sequence", "",
            _seq_request(4, 11, 1, start=True, durable=False), b"",
        )
        assert tier_b.seq_store.get(11) is None  # not durable: no push yet
        assert engine.drain(timeout_s=5) is True
        snap = tier_b.seq_store.get(11)
        assert snap is not None and snap["step"] == 1
    finally:
        engine.close()
        tier_a.close()
        tier_b.close()


# -- quorum-durable sequences ------------------------------------------------

def test_seq_quorum_arithmetic():
    """ceil((K+1)/2) peers must report ``stored`` before a
    quorum="majority" durable step acks; best-effort mode never requires
    any; an unknown discipline is a loud constructor error."""
    with pytest.raises(ValueError):
        FleetTier(quorum="all")
    tier = _tier()
    try:
        assert tier.quorum == "any"
        assert tier.seq_quorum_required() == 0
    finally:
        tier.close()
    for k, need in ((1, 1), (2, 2), (3, 2), (4, 3), (5, 3)):
        tier = _tier(replicate_k=k, quorum="majority")
        try:
            assert tier.seq_quorum_required() == need, (k, need)
        finally:
            tier.close()


def test_quorum_refusal_is_retryable_and_never_reapplies():
    """Quorum unreachable: the step REFUSES with a retryable 503 naming
    the deficit, stays applied locally exactly once, and the client's
    retry of the SAME declared step (without re-declaring start) acks
    200 as soon as a peer is reachable — through the retained-rendering
    replay, never a second apply."""
    from client_tpu.utils import InferenceServerException

    ledger = StepLedger()
    tier_a = _tier(replicate_k=1, quorum="majority")
    tier_b = _tier(replicate_k=1)
    eng_a = InferenceEngine(models=[_seq_model(ledger, "rA")], fleet=tier_a)
    try:
        # no peers wired: zero acks possible — the partitioned shape
        with pytest.raises(InferenceServerException) as exc:
            eng_a.execute(
                "chaos_sequence", "",
                _seq_request(3, 21, 1, start=True), b"",
            )
        assert exc.value.status() == "503"
        msg = str(exc.value)
        assert "quorum" in msg and "0/1" in msg
        assert ledger.steps_for(21) == [1]  # applied locally, not lost
        assert tier_a.stats()["seq_quorum_refusals"] == 1
        # the partition heals; the retry declares the SAME step and goes
        # through the replay path, which re-publishes before releasing
        # the retained rendering
        tier_a.set_peers([tier_b.address])
        response, _ = eng_a.execute(
            "chaos_sequence", "", _seq_request(3, 21, 1), b"",
        )
        assert _out_value(response) == 3
        assert ledger.steps_for(21) == [1]  # STILL exactly once
        ledger.assert_exactly_once()
        assert tier_a.stats()["seq_quorum_acks"] >= 1
        snap = tier_b.seq_store.get(21)
        assert snap is not None and snap["step"] == 1
        # and the sequence continues normally, quorum-durable per step
        response, _ = eng_a.execute(
            "chaos_sequence", "", _seq_request(4, 21, 2), b"",
        )
        assert _out_value(response) == 7
        assert tier_b.seq_store.get(21)["step"] == 2
    finally:
        eng_a.close()
        tier_a.close()
        tier_b.close()


def test_stale_peer_reply_is_not_a_quorum_ack():
    """A reachable peer that REJECTS the snapshot as stale answered the
    RPC but stored nothing — it must not count toward the write quorum
    (the ACK-BEFORE-STORE lint rule guards this exact shape)."""
    from client_tpu.utils import InferenceServerException

    ledger = StepLedger()
    tier_a = _tier(replicate_k=1, quorum="majority")
    tier_b = _tier(replicate_k=1)
    _peer_up([tier_a, tier_b])
    eng_a = InferenceEngine(models=[_seq_model(ledger, "rA")], fleet=tier_a)
    eng_b = InferenceEngine(models=[_seq_model(ledger, "rB")], fleet=tier_b)
    try:
        # poison B's store with a higher-epoch incarnation of the id so
        # A's pushes are stale-rejected despite B being fully reachable
        eng_b.execute(
            "chaos_sequence", "",
            _seq_request(1, 55, 1, start=True, durable=False), b"",
        )
        poisoned = eng_b.export_sequence(55)
        poisoned["epoch"] = float(poisoned["epoch"]) + 1e6
        assert tier_b.seq_store.put(dict(poisoned)) is True
        with pytest.raises(InferenceServerException) as exc:
            eng_a.execute(
                "chaos_sequence", "",
                _seq_request(5, 55, 1, start=True), b"",
            )
        assert exc.value.status() == "503"
        msg = str(exc.value)
        assert "0/1" in msg  # the reply arrived but was NOT an ack
        assert "open breakers: none" in msg  # transport was healthy
        assert tier_b.stats()["seq_stale_rejected"] >= 1
        assert tier_a.stats()["seq_quorum_refusals"] >= 1
    finally:
        eng_a.close()
        eng_b.close()
        tier_a.close()
        tier_b.close()


def test_dispatch_partition_and_heal_fleet_tiers():
    """The partition fault kind: tiers in different groups cannot
    exchange frames (both directions), same-group tiers still can, an
    address OUTSIDE the partitioned set is unaffected, and heal restores
    everything."""
    tiers = [_tier() for _ in range(3)]
    _peer_up(tiers)
    outside = _tier()
    try:
        dispatch_fault(
            FaultSpec("partition", groups=[[0], [1, 2]]), tiers=tiers
        )
        with pytest.raises(OSError, match="partitioned"):
            tiers[0]._peer_call(tiers[1].address, {"op": "ping"})
        with pytest.raises(OSError, match="partitioned"):
            tiers[1]._peer_call(tiers[0].address, {"op": "ping"})
        tiers[1]._peer_call(tiers[2].address, {"op": "ping"})  # same group
        tiers[0]._peer_call(outside.address, {"op": "ping"})   # unlisted
        dispatch_fault(FaultSpec("heal"), tiers=tiers)
        tiers[0]._peer_call(tiers[1].address, {"op": "ping"})
    finally:
        for tier in tiers:
            tier.close()
        outside.close()


def test_best_effort_acks_without_quorum_and_loss_is_visible():
    """The quorum="any" contrast: under a partition, durable steps still
    ack 200 with ZERO peer acks (local-only durability), so the
    replica's death CAN lose them — but the loss surfaces as a loud
    restartable 409 on the survivor, never a silent wrong answer."""
    from client_tpu.testing.chaos import heal_fleet, partition_fleet
    from client_tpu.utils import InferenceServerException

    ledger = StepLedger()
    tier_a = _tier(replicate_k=1)  # quorum="any" is the default
    tier_b = _tier(replicate_k=1)
    _peer_up([tier_a, tier_b])
    partition_fleet([tier_a, tier_b], groups=[[0], [1]])
    eng_a = InferenceEngine(models=[_seq_model(ledger, "rA")], fleet=tier_a)
    eng_b = InferenceEngine(models=[_seq_model(ledger, "rB")], fleet=tier_b)
    try:
        total = 0
        for step, value in enumerate((2, 4), start=1):
            total += value
            response, _ = eng_a.execute(
                "chaos_sequence", "",
                _seq_request(value, 61, step, start=(step == 1)), b"",
            )
            assert _out_value(response) == total  # acked best-effort
        stats = tier_a.stats()
        assert stats["seq_quorum_acks"] == 0  # no quorum accounting
        assert stats["seq_quorum_refusals"] == 0
        assert tier_b.seq_store.get(61) is None  # nothing replicated
        # A dies unplanned; its acked-but-unreplicated steps are gone —
        # the survivor refuses with the restartable 409 rather than
        # serving silently forked state
        tier_a.close()
        eng_a.close()
        heal_fleet([tier_b])
        with pytest.raises(InferenceServerException) as exc:
            eng_b.execute(
                "chaos_sequence", "", _seq_request(9, 61, 3), b"",
            )
        assert exc.value.status() == "409"
    finally:
        eng_a.close()
        eng_b.close()
        tier_a.close()
        tier_b.close()


# -- acceptance 1: three-replica SIGKILL with active durable sequences ------

class _SeqChaosFixture:
    """Three HTTP servers behind chaos proxies, one sticky replicated
    client, N durable sequences as drivers.  ``check`` asserts the
    scenario's cross-cutting invariants."""

    MODEL = "chaos_sequence"

    def __init__(self, scenario):
        self.scenario = scenario
        self.ledger = StepLedger()
        self.sessions = int(scenario.params.get("sessions", 6))
        self.steps = int(scenario.params.get("steps", 8))
        self.think_s = float(scenario.params.get("think_s", 0.04))
        rng = scenario.rng()
        self.values = [
            [rng.randrange(1, 9) for _ in range(self.steps)]
            for _ in range(self.sessions)
        ]
        self.delivered = [[] for _ in range(self.sessions)]
        self.tiers = [
            _tier(replicate_k=1, fan_out=2, lookup_timeout_s=0.5)
            for _ in range(3)
        ]
        _peer_up(self.tiers)
        # fleet-wide tracing (the one-trace failover acceptance): each
        # replica writes its own trace file, the client a fourth —
        # traceview joins them by trace id after the run
        self.trace_dir = scenario.params.get("trace_dir")
        self.trace_files = []
        self.servers = []
        self.proxies = []
        for i, tier in enumerate(self.tiers):
            server = Server(
                models=[_seq_model(self.ledger, f"r{i}")],
                with_default_models=False, fleet=tier,
            ).start()
            if self.trace_dir:
                trace_file = os.path.join(
                    self.trace_dir, f"replica{i}.jsonl"
                )
                self.trace_files.append(trace_file)
                server.engine.update_trace_settings({
                    "trace_level": ["TIMESTAMPS"], "trace_rate": "1",
                    "trace_count": "-1", "trace_file": trace_file,
                })
            self.servers.append(server)
            self.proxies.append(FaultProxy(server.http_address))
        tracer = None
        if self.trace_dir:
            client_file = os.path.join(self.trace_dir, "client.jsonl")
            self.trace_files.append(client_file)
            tracer = ClientTracer(trace_file=client_file, trace_rate=1)
        self.client = ReplicatedClient(
            [proxy.address for proxy in self.proxies],
            transport="http", policy="sticky", probe_interval_s=0.5,
            tracer=tracer,
        )

    def apply_fault(self, fault):
        dispatch_fault(fault, proxies=self.proxies, kill=self._kill)

    def _kill(self, target):
        # SIGKILL semantics: connections RST, listener refused (the
        # proxy's sigkill already ran), and the server stops WITHOUT
        # drain — its sequence state and caches die with it.  Only the
        # snapshots it pushed at each applied step survive.
        self.servers[target].stop()

    def drivers(self):
        from client_tpu.http import InferInput

        def driver(index):
            sid = 1000 + index
            expected = 0
            for step in range(1, self.steps + 1):
                value = self.values[index][step - 1]
                expected += value
                inp = InferInput("INPUT", [1], "INT32")
                inp.set_data_from_numpy(np.array([value], np.int32))
                result = self.client.infer(
                    self.MODEL, [inp],
                    sequence_id=sid,
                    sequence_start=(step == 1),
                    sequence_end=(step == self.steps),
                    sequence_durable=True,
                    sequence_step=step,
                )
                got = int(result.as_numpy("OUTPUT")[0])
                assert got == expected, (
                    f"sequence {sid} step {step}: got {got}, "
                    f"want {expected} — resumed state diverged"
                )
                self.delivered[index].append(got)
                time.sleep(self.think_s)

        return [
            (lambda i=i: driver(i)) for i in range(self.sessions)
        ]

    def check(self, result):
        result.assert_clean()  # zero client-visible errors, no wedges
        # byte-exact: every session saw the exact running-sum series
        for index in range(self.sessions):
            want = list(np.cumsum(self.values[index]))
            assert_byte_exact(
                self.delivered[index], want, label=f"sequence {1000 + index}"
            )
        # exactly-once: no (sequence, step) applied twice — applies
        # orphaned on the SIGKILLed replica (applied but never acked /
        # never replicated) are superseded by the survivor's resume
        self.ledger.assert_exactly_once(orphans={"r0"})
        for index in range(self.sessions):
            assert self.ledger.steps_for(1000 + index) == list(
                range(1, self.steps + 1)
            )
        # the kill actually hit live state: replica 0 had applied steps,
        # and every sequence that CROSSED the kill (applies on r0 AND on
        # a survivor) resumed from a replicated snapshot — a fork to
        # fresh state would already have failed the byte-exact check,
        # and a crossing with zero resumes means the tier never served
        replicas = {r for _s, _p, r, _t in self.ledger.applies()}
        assert "r0" in replicas, "replica 0 never served — kill proved nothing"
        crossed = {
            sid
            for sid, _step, replica, _t in self.ledger.applies()
            if replica == "r0"
        } & {
            sid
            for sid, _step, replica, _t in self.ledger.applies()
            if replica != "r0"
        }
        resumes = sum(
            server.engine.metrics.get("ctpu_fleet_seq_resumes_total") or 0
            for server in self.servers[1:]
        )
        if self.scenario.params.get("require_resume"):
            # the deterministic acceptance pins its timing so sequences
            # MUST straddle the kill; randomized-timing soak scenarios
            # may legitimately kill after r0's sequences completed
            assert crossed, "no sequence straddled the kill"
        if crossed:
            assert resumes > 0, (
                f"{len(crossed)} sequence(s) crossed the kill but none "
                "resumed from a replicated snapshot"
            )
        pushes = sum(t.stats()["seq_pushes"] for t in self.tiers)
        assert pushes > 0

    def close(self):
        self.client.close()
        for proxy in self.proxies:
            proxy.close()
        for server in self.servers[1:]:
            server.stop()
        for tier in self.tiers[1:]:
            tier.close()
        self.tiers[0].close()


def _seq_sigkill_scenario(name, sessions, steps, at_s, seed=7, **extra):
    return ChaosScenario(
        name,
        [FaultSpec("kill_replica", at_s=at_s, target=0)],
        seed=seed, sessions=sessions, steps=steps, **extra,
    )


def test_sigkill_with_active_durable_sequences():
    matrix = ChaosMatrix([
        _seq_sigkill_scenario("seq-sigkill", sessions=5, steps=8,
                              at_s=0.35, think_s=0.08,
                              require_resume=True),
    ])
    results = matrix.run(_SeqChaosFixture, join_timeout_s=180)
    assert results[0].fired, "the kill never fired"


def test_sigkill_failover_joins_one_trace(tmp_path, capsys):
    """Acceptance: a kill-mid-stream failover reads as ONE trace spanning
    three processes' trace files.  The client pins every step of a
    sequence under one trace id, the dead replica's server spans joined
    it via traceparent, the survivor's ``__seq_resume__`` marker
    CONTINUES it from the replicated snapshot, and the peer-tier child
    spans (durability ``seq_put`` pushes, the resume-side lookup) hang
    under it — and traceview joins all four files into one timeline."""
    scenario = _seq_sigkill_scenario(
        "seq-sigkill-traced", sessions=5, steps=8, at_s=0.35,
        think_s=0.08, require_resume=True, trace_dir=str(tmp_path),
    )
    matrix = ChaosMatrix([scenario])
    results = matrix.run(_SeqChaosFixture, join_timeout_s=180)
    assert results[0].fired, "the kill never fired"
    files = sorted(str(p) for p in tmp_path.glob("*.jsonl"))
    assert len(files) == 4  # three replicas + the client
    records = traceview.load_records(files)
    traces = traceview.join_traces(records)
    by_file = {
        f: {r.get("trace_id") for r in traceview.load_records([f])}
        for f in files
    }
    # a survivor resumed the dead replica's sequence INTO the same trace
    resumes = [
        r for r in records if r.get("model_name") == "__seq_resume__"
    ]
    assert resumes, "no resume marker span — the failover left no trace"
    trace_id = resumes[0]["trace_id"]
    spans = traces[trace_id]
    assert {r.get("source") for r in spans} == {"client", "server"}
    # the ONE trace id appears in the client's file and >= 2 replicas'
    holding = [f for f, tids in by_file.items() if trace_id in tids]
    assert any(f.endswith("client.jsonl") for f in holding)
    assert sum(1 for f in holding if "replica" in f) >= 2, (
        f"trace {trace_id} should span the dead replica AND a survivor; "
        f"found only {holding}"
    )
    # peer-tier child spans under the same trace (durability pushes
    # and/or the survivor's sequence lookup)
    assert any(
        str(r.get("model_name", "")).startswith("__peer_seq")
        for r in spans
    )
    # the client's attempt pairs show the endpoint hop across the kill
    endpoints = {
        ts.get("endpoint")
        for r in spans if r.get("source") == "client"
        for ts in r.get("timestamps") or ()
        if ts.get("endpoint")
    }
    assert len(endpoints) >= 2, (
        f"expected attempts on both sides of the kill, saw {endpoints}"
    )
    # the traceview CLI joins the same story (and --format json scripts)
    assert traceview.main(["--format", "json", "--trace", trace_id,
                           *files]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    doc = json.loads(out[0])
    assert doc["trace_id"] == trace_id
    assert doc["critical_path"]["total_ms"] > 0
    assert doc["critical_path"]["peer_ms"] > 0


def test_invariant_failure_dumps_flight_recorders(tmp_path):
    """A failed chaos invariant ships its own postmortem: ChaosMatrix
    dumps every reachable flight recorder before the failure
    propagates, and the dump names the scenario and the error."""
    recorder = FlightRecorder(dump_dir=str(tmp_path), name="r0")
    recorder.note("tick", n=1)

    class _Fixture:
        servers = [types.SimpleNamespace(
            engine=types.SimpleNamespace(flight=recorder)
        )]

        def apply_fault(self, fault):
            pass

        def drivers(self):
            return []

        def check(self, result):
            raise AssertionError("invariant broken")

    matrix = ChaosMatrix([ChaosScenario("boom")])
    with pytest.raises(AssertionError, match="invariant broken"):
        matrix.run(lambda scenario: _Fixture(), join_timeout_s=5)
    dumps = sorted(tmp_path.glob("flight-*.jsonl"))
    assert dumps, "no flight dump written on invariant failure"
    lines = [json.loads(line) for line in open(dumps[0])]
    assert lines[0]["kind"] == "flight_dump"
    assert lines[0]["reason"].startswith("chaos-boom")
    kinds = {r["kind"] for r in lines[1:]}
    assert {"tick", "chaos_invariant_failure"} <= kinds


@pytest.mark.slow
def test_sigkill_durable_sequences_soak():
    """Scaled matrix for `make soak`: more sessions, longer sequences,
    randomized kill timing — repetition over seeds is what finds the
    apply/publish/ack window races."""
    matrix = ChaosMatrix([
        _seq_sigkill_scenario(f"seq-sigkill-{seed}", sessions=8, steps=12,
                              at_s=("uniform", 0.3, 0.9), seed=seed,
                              think_s=0.1)
        for seed in (11, 23)
    ])
    matrix.run(_SeqChaosFixture, join_timeout_s=300)


# -- acceptance 2: anti-entropy convergence under SIGKILL -------------------

CFG = tfm.TransformerConfig(
    vocab_size=128,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    max_seq=96,
    dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(0), CFG)


def _serial(params, prompt, n):
    return list(tfm.generate(params, CFG, prompt, n, readback_depth=0))


def _collect(q, timeout=120):
    out = []
    while True:
        tok = q.get(timeout=timeout)
        if tok is CLOSE:
            return out
        out.append(tok)


class _AntiEntropyFixture:
    """Three in-process LM replicas; replica 0 serves a hot shared
    prefix whose chain the anti-entropy loop pushes to peers; replica 0
    is then SIGKILLed and the sessions run on survivors — the chain must
    be retrievable from peers and save prefill there."""

    def __init__(self, scenario, params):
        self.scenario = scenario
        self.params = params
        self.shared = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]
        self.n_sessions = int(scenario.params.get("sessions", 4))
        self.budget = int(scenario.params.get("budget", 6))
        self.killed = threading.Event()
        self.tiers = [
            _tier(replicate_k=2, hot_hits=2, fan_out=2)
            for _ in range(3)
        ]
        _peer_up(self.tiers)
        self.engines = [
            LmEngine(params, CFG, max_slots=2, lane_counts=(2,),
                     block_size=8, prefill_chunk=16, min_bucket=4,
                     registry=Registry(), fleet=tier)
            for tier in self.tiers
        ]
        self.outputs = [None] * self.n_sessions
        # replica 0 serves the shared prefix HOT (re-publishes past the
        # first insert bump the chain's demand counter to hot_hits=2),
        # then the anti-entropy pass pushes the chain to both peers —
        # all BEFORE the kill, which is the entire point: pull-only
        # tiers lose content a dead replica never served to a peer
        for _ in range(3):
            _collect(self.engines[0].submit(self.shared + [99], 2)[0])
        # by this call or, where the streams took longer than its 0.2 s
        # between scans, by the tier's own anti-entropy thread ahead of it
        self.tiers[0].replicate_now()
        deadline = time.monotonic() + 10
        while (not self.tiers[0].replicated_items
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert self.tiers[0].replicated_items >= 1, \
            "hot chain never replicated"

    def apply_fault(self, fault):
        dispatch_fault(fault, kill=self._kill)

    def _kill(self, target):
        self.killed.set()
        self.engines[target].close()
        self.tiers[target].close()

    def drivers(self):
        def driver(index):
            prompt = self.shared + [10 + index] * 3
            # spread sessions over the fleet; a session landing on the
            # corpse hops to a survivor (the client-side failover shape)
            order = [
                self.engines[(index + hop) % 3] for hop in range(3)
            ]
            for _attempt in range(6):
                engine = next(
                    e for e in order
                    if not (e is self.engines[0] and self.killed.is_set())
                )
                try:
                    got = _collect(
                        engine.submit(prompt, self.budget)[0]
                    )
                except Exception:
                    continue  # engine closed mid-submit: hop
                if len(got) >= self.budget:
                    self.outputs[index] = got
                    return
            raise AssertionError(f"session {index} never completed")

        return [(lambda i=i: driver(i)) for i in range(self.n_sessions)]

    def check(self, result):
        result.assert_clean()
        # the killed replica's hot chain is retrievable from BOTH peers
        for tier in self.tiers[1:]:
            got = tier.store.lookup(np.asarray(self.shared), 8, 2,
                                    count_hits=False)
            assert got is not None and got[0] == 2, (
                "killed replica's hot chain not on this survivor"
            )
        # byte-exact on survivors
        for index in range(self.n_sessions):
            prompt = self.shared + [10 + index] * 3
            assert_byte_exact(
                self.outputs[index],
                _serial(self.params, prompt, self.budget),
                label=f"session {index}",
            )
        # and the replicated chain actually saved prefill somewhere: a
        # survivor either adopted peer blocks or hit its local trie on
        # the shared prefix
        saved = 0
        for engine in self.engines[1:]:
            saved += engine.fleet_stats()["remote_blocks"]
            saved += engine.prefix_stats().get("hits", 0)
        assert saved > 0, "replicated chain never saved any prefill"

    def close(self):
        for engine in self.engines[1:]:
            engine.close()
        for tier in self.tiers[1:]:
            tier.close()
        for engine in self.engines[1:]:
            assert_kv_clean(engine)


def test_anti_entropy_survives_sigkill(params):
    scenario = ChaosScenario(
        "anti-entropy",
        [FaultSpec("kill_replica", at_s=0.1, target=0)],
        seed=3, sessions=4, budget=6,
    )
    matrix = ChaosMatrix([scenario])
    matrix.run(lambda s: _AntiEntropyFixture(s, params),
               join_timeout_s=300)


@pytest.mark.slow
def test_anti_entropy_sigkill_soak(params):
    scenario = ChaosScenario(
        "anti-entropy-soak",
        [FaultSpec("kill_replica", at_s=("uniform", 0.05, 0.5), target=0)],
        seed=17, sessions=8, budget=10,
    )
    ChaosMatrix([scenario]).run(
        lambda s: _AntiEntropyFixture(s, params), join_timeout_s=600,
    )


# -- speculative decoding under cancel/preempt chaos ------------------------

class _SpecChaosFixture:
    """Spec-enabled engine under cancel + priority-preemption churn with
    drafts in flight.  The verify tick writes k+1 KV positions per pass
    and rejected positions rewind by POINTER (garbage stays inside the
    lane's own reservation, overwritten before it can be attended) — so
    whatever the churn interrupts, retire/preempt releases whole
    reservations and the pool must end fully free.  The preempted lane
    resumes byte-exact with a fresh LaneSpec (drafter state rebuilt from
    the prompt), which is the swap/recompute guarantee extended to the
    draft/verify path."""

    def __init__(self, scenario, params):
        self.scenario = scenario
        self.params = params
        # pool of 12 blocks.  A (12-token prompt + 60 budget) reserves
        # 9; B (+12 budget) reserves 3 — together they fill the pool.
        # B cancelling mid-stream frees its LANE but leaves A holding 9
        # blocks, so the gold admission (needs 7) exhausts the pool and
        # MUST preempt A: preemption is pool-driven in this engine, not
        # lane-driven.
        self.engine = LmEngine(
            params, CFG, max_slots=2, lane_counts=(2,),
            block_size=8, prefill_chunk=16, min_bucket=4,
            pool_tokens=96, speculative={"k": 4},
            tenant_priority={"gold": 10.0}, registry=Registry(),
        )
        self.prompts = {
            "a": [5, 6] * 6,   # periodic: the n-gram drafter fires
            "b": [7, 8] * 6,
            "gold": [9, 7] * 6,
        }
        self.outputs = {}
        self.started_a = threading.Event()

    def apply_fault(self, fault):
        dispatch_fault(fault)

    def drivers(self):
        def stream_a():
            q, _ = self.engine.submit(self.prompts["a"], 60, tenant="free")
            first = q.get(timeout=120)
            assert first is not CLOSE
            self.started_a.set()
            out = [first]
            while True:
                tok = q.get(timeout=120)
                if tok is CLOSE:
                    break
                out.append(tok)
            self.outputs["a"] = out

        def cancel_then_gold():
            self.started_a.wait(timeout=120)
            # B streams a couple of spec-delivered tokens, then cancels
            # with drafts in flight — its lane and blocks must come back
            q, handle = self.engine.submit(
                self.prompts["b"], 12, tenant="free"
            )
            for _ in range(2):
                if q.get(timeout=120) is CLOSE:
                    break
            self.engine.cancel(handle)
            while q.get(timeout=120) is not CLOSE:
                pass
            # now the pool can't fit gold beside A: admission preempts A
            # (possibly mid-verify round — verify never spans a pass
            # boundary, so the swap sees a consistent lane)
            q, _ = self.engine.submit(
                self.prompts["gold"], 40, tenant="gold"
            )
            self.outputs["gold"] = _collect(q)

        return [stream_a, cancel_then_gold]

    def check(self, result):
        result.assert_clean()
        assert self.engine.preempt_stats()["preemptions"] >= 1, (
            "gold admission never preempted the free-tier lane"
        )
        stats = self.engine.spec_stats()
        assert stats["accepted"] > 0, "speculation never engaged"
        # survivors byte-exact: the gold stream throughout, and stream A
        # across its preempt/resume (fresh LaneSpec on swap-in)
        assert_byte_exact(
            self.outputs.get("gold"),
            _serial(self.params, self.prompts["gold"], 40), label="gold",
        )
        assert_byte_exact(
            self.outputs.get("a"),
            _serial(self.params, self.prompts["a"], 60), label="stream a",
        )

    def close(self):
        self.engine.close()
        assert_kv_clean(self.engine)


def test_spec_cancel_preempt_round_never_leaks(params):
    from client_tpu.analysis.witness import ResourceWitness

    scenario = ChaosScenario("spec-cancel-preempt", seed=5)
    witness = ResourceWitness()
    # the leak checkpoint is AFTER the matrix round closes the engine:
    # mid-round the prefix cache legitimately holds retired prompt
    # blocks, so an in-round assert_no_leaked_resources invariant would
    # flag working-as-intended cache retention
    with witness.installed():
        ChaosMatrix([scenario]).run(
            lambda s: _SpecChaosFixture(s, params), join_timeout_s=300
        )
    assert witness.assert_clean() > 0  # KV reservations WERE witnessed


# -- acceptance 3: network partition vs the write quorum --------------------

class _QuorumPartitionFixture:
    """Three engine replicas with majority-quorum durable sequences; a
    network partition isolates replica 0 from both peers mid-run, then
    heals.  Minority-side steps REFUSE (retryable 503, retried by the
    driver) until the heal; majority-side steps keep acking straight
    through the partition.  After the run replica 0 dies WITHOUT drain
    and every minority sequence resumes byte-exact on a survivor —
    possible only because no 200 was ever returned for a step whose
    snapshot had not reached a peer (never acks-then-loses)."""

    MINORITY = 4   # sequences driven on (to-be-partitioned) replica 0
    MAJORITY = 2   # sequences driven on replica 1

    def __init__(self, scenario):
        self.scenario = scenario
        self.ledger = StepLedger()
        self.steps = int(scenario.params.get("steps", 8))
        self.think_s = float(scenario.params.get("think_s", 0.1))
        rng = scenario.rng()
        self.n = self.MINORITY + self.MAJORITY
        self.values = [
            [rng.randrange(1, 9) for _ in range(self.steps)]
            for _ in range(self.n)
        ]
        self.refusals = []
        self.tiers = [
            _tier(replicate_k=1, quorum="majority", fan_out=2,
                  lookup_timeout_s=0.3, failure_threshold=2,
                  reset_timeout_s=0.25)
            for _ in range(3)
        ]
        _peer_up(self.tiers)
        self.engines = [
            InferenceEngine(models=[_seq_model(self.ledger, f"r{i}")],
                            fleet=tier)
            for i, tier in enumerate(self.tiers)
        ]
        self.killed = False

    def apply_fault(self, fault):
        dispatch_fault(fault, tiers=self.tiers)

    def drivers(self):
        from client_tpu.utils import InferenceServerException

        def driver(index):
            sid = 500 + index
            engine = self.engines[0 if index < self.MINORITY else 1]
            expected = 0
            for step in range(1, self.steps + 1):
                value = self.values[index][step - 1]
                expected += value
                start = step == 1
                deadline = time.monotonic() + 60
                while True:
                    try:
                        response, _ = engine.execute(
                            "chaos_sequence", "",
                            _seq_request(value, sid, step, start=start),
                            b"",
                        )
                        break
                    except InferenceServerException as exc:
                        # quorum unreachable: retryable 503.  The retry
                        # declares the SAME step WITHOUT re-declaring
                        # start (the step stayed applied locally; a
                        # restart would fork a fresh incarnation)
                        assert exc.status() == "503", exc
                        assert "quorum" in str(exc)
                        start = False
                        self.refusals.append((sid, step))
                        if time.monotonic() > deadline:
                            raise
                        time.sleep(0.05)
                assert _out_value(response) == expected, (sid, step)
                time.sleep(self.think_s)

        return [(lambda i=i: driver(i)) for i in range(self.n)]

    def check(self, result):
        result.assert_clean()  # every refused step eventually acked
        if self.scenario.params.get("require_refusal", True):
            assert self.refusals, "the partition never refused a step"
        # only the minority side ever refused: the majority side kept
        # its quorum (1 reachable peer) straight through the partition
        refused_sids = {sid for sid, _step in self.refusals}
        assert refused_sids <= {
            500 + i for i in range(self.MINORITY)
        }, f"majority-side sequences refused: {refused_sids}"
        stats = self.tiers[0].stats()
        assert stats["seq_quorum_refusals"] >= len(self.refusals)
        assert stats["seq_quorum_acks"] >= self.MINORITY * self.steps
        # replica 0 dies UNPLANNED (no drain).  Every step it ever acked
        # is on a survivor by the quorum contract — resume each minority
        # sequence there and apply one more step, byte-exact
        self.engines[0].close()
        self.tiers[0].close()
        self.killed = True
        for index in range(self.MINORITY):
            sid = 500 + index
            total = int(np.sum(self.values[index]))
            response, _ = self.engines[1].execute(
                "chaos_sequence", "",
                _seq_request(7, sid, self.steps + 1), b"",
            )
            assert _out_value(response) == total + 7, (
                f"sequence {sid} resumed with lost acked steps"
            )
        # no (sequence, step) applied twice anywhere: refused steps were
        # never re-applied (the replay path re-published instead), and
        # the resumes continued from the replicated snapshots
        self.ledger.assert_exactly_once()

    def close(self):
        for engine in self.engines:
            engine.close()
        for tier in self.tiers:
            tier.close()


def test_partitioned_quorum_never_acks_then_loses():
    scenario = ChaosScenario(
        "quorum-partition",
        [FaultSpec("partition", at_s=0.25, groups=[[0], [1, 2]]),
         FaultSpec("heal", at_s=0.7)],
        seed=13, steps=8, think_s=0.1,
    )
    results = ChaosMatrix([scenario]).run(
        _QuorumPartitionFixture, join_timeout_s=180,
    )
    assert results[0].fired, "the partition never fired"


@pytest.mark.slow
def test_partitioned_quorum_soak():
    """Scaled matrix for `make soak`: randomized partition windows over
    seeds — the refusal/heal/retry races live in the window edges."""
    matrix = ChaosMatrix([
        ChaosScenario(
            f"quorum-partition-{seed}",
            [FaultSpec("partition", at_s=("uniform", 0.1, 0.4),
                       groups=[[0], [1, 2]]),
             FaultSpec("heal", at_s=("uniform", 0.6, 1.1))],
            seed=seed, steps=12, think_s=0.12, require_refusal=False,
        )
        for seed in (5, 29)
    ])
    matrix.run(_QuorumPartitionFixture, join_timeout_s=300)


# -- acceptance 4: diurnal ramp against the elastic fleet -------------------

class _AutoscaleRampFixture:
    """A diurnal load ramp against an elastic fleet: one floor replica,
    an Autoscaler steering real in-process HTTP servers from gossiped
    pressure, a sticky client driving durable sequences.  The burst
    forces scale-up (prefix-aware peer wiring + anti-entropy warm +
    probation ramp before traffic); the quiet tail forces the fleet back
    down THROUGH drain — zero client-visible errors, zero lost
    sequences, and the fleet converges to the floor."""

    MODEL = "chaos_sequence"

    def __init__(self, scenario):
        from client_tpu.balance.pool import EndpointPool
        from client_tpu.serve.autoscale import (
            AutoscalePolicy,
            Autoscaler,
            ServerReplicaLauncher,
        )

        self.scenario = scenario
        self.ledger = StepLedger()
        self.base = int(scenario.params.get("base", 1))
        self.burst = int(scenario.params.get("burst", 6))
        self.tail = int(scenario.params.get("tail", 1))
        self.steps = int(scenario.params.get("steps", 8))
        rng = scenario.rng()
        self.n = self.base + self.burst + self.tail
        self.values = [
            [rng.randrange(1, 9) for _ in range(self.steps)]
            for _ in range(self.n)
        ]
        self.delivered = [[] for _ in range(self.n)]
        self.settled = threading.Event()
        self._lock = threading.Lock()
        self._spawned = 0
        self._load_left = self.n

        def models():
            with self._lock:
                name = f"r{self._spawned}"
                self._spawned += 1
            return [_seq_model(self.ledger, name, busy_s=0.05)]

        self.launcher = ServerReplicaLauncher(
            models,
            fleet_kwargs=dict(gossip_interval_s=0, replicate_k=1,
                              fan_out=2, lookup_timeout_s=0.5),
            drain_timeout_s=30.0,
        )
        floor = self.launcher.spawn()
        self.registry = Registry()
        self.pool = EndpointPool([floor.url])
        self.autoscaler = Autoscaler(
            self.pool, self.launcher,
            policy=AutoscalePolicy(
                min_replicas=1, max_replicas=3, scale_up_at=3.0,
                scale_down_at=1.0, up_after=2, down_after=5,
                cooldown_s=0.8, tick_interval_s=0.1,
            ),
            registry=self.registry,
        ).adopt([floor])
        self.client = ReplicatedClient(
            self.pool, transport="http", policy="sticky",
            probe_interval_s=None,
        )
        assert self.pool.start_probes(self._probe, interval_s=0.15)

    def _probe(self, url):
        """Readiness + gossip in one round trip: the real HTTP health
        verb for state, the replica's fleet peer port for the pressure
        signals the autoscaler steers on."""
        from client_tpu.serve.fleet import fetch_summary
        from client_tpu.utils import SERVER_UNREACHABLE

        handle = next(
            (h for h in self.autoscaler.replicas() if h.url == url), None
        )
        if handle is None:
            return SERVER_UNREACHABLE
        state = self.client.client_for(url).server_state(timeout_s=1.0)
        try:
            summary = fetch_summary(handle.fleet_address, timeout_s=1.0)
        except OSError:
            return state
        return state, summary, summary["pressure"]

    def apply_fault(self, fault):
        dispatch_fault(fault)

    def drivers(self):
        from client_tpu.http import InferInput

        def load(index, delay_s, think_s):
            try:
                sid = 2000 + index
                expected = 0
                time.sleep(delay_s)
                for step in range(1, self.steps + 1):
                    value = self.values[index][step - 1]
                    expected += value
                    inp = InferInput("INPUT", [1], "INT32")
                    inp.set_data_from_numpy(np.array([value], np.int32))
                    result = self.client.infer(
                        self.MODEL, [inp],
                        sequence_id=sid,
                        sequence_start=(step == 1),
                        sequence_end=(step == self.steps),
                        sequence_durable=True,
                        sequence_step=step,
                    )
                    got = int(result.as_numpy("OUTPUT")[0])
                    assert got == expected, (sid, step, got, expected)
                    self.delivered[index].append(got)
                    time.sleep(think_s)
            finally:
                with self._lock:
                    self._load_left -= 1

        def controller():
            # the fixture owns the clock: synchronous ticks make the
            # matrix deterministic-ish and keep the loop single-threaded
            deadline = time.monotonic() + float(
                self.scenario.params.get("settle_timeout_s", 60)
            )
            while time.monotonic() < deadline:
                self.autoscaler.tick()
                status = self.autoscaler.status()
                with self._lock:
                    quiet = self._load_left == 0
                if (quiet and status["scale_ups"] > 0
                        and status["replicas"]
                        == self.autoscaler.policy.min_replicas):
                    self.settled.set()
                    return
                time.sleep(0.1)

        plans = (
            [(i, 0.0, 0.12) for i in range(self.base)]
            + [(self.base + i, 0.5, 0.01) for i in range(self.burst)]
            + [(self.base + self.burst + i, 1.4, 0.1)
               for i in range(self.tail)]
        )
        return [controller] + [
            (lambda p=p: load(*p)) for p in plans
        ]

    def check(self, result):
        result.assert_clean()  # zero client-visible errors, no wedges
        for index in range(self.n):
            want = list(np.cumsum(self.values[index]))
            assert_byte_exact(
                self.delivered[index], want,
                label=f"sequence {2000 + index}",
            )
        status = self.autoscaler.status()
        assert status["scale_ups"] >= 1, "the ramp never scaled up"
        assert self.settled.is_set(), (
            f"fleet never converged back to the floor: {status}"
        )
        assert status["scale_downs"] == status["scale_ups"]
        # every scale-down went through drain (the launcher's only
        # retire path), and nothing was applied twice anywhere —
        # sequences caught on a retiring replica migrated through its
        # tier and resumed, they were not replayed from scratch
        self.ledger.assert_exactly_once()
        assert (
            self.registry.get("ctpu_autoscale_scale_ups_total", None)
            == status["scale_ups"]
        )
        assert self.registry.get("ctpu_autoscale_replicas", None) == 1

    def close(self):
        self.autoscaler.close()
        self.client.close()
        self.pool.close()
        for handle in self.autoscaler.replicas():
            try:
                handle.server.stop()
            except Exception:
                pass
            handle.tier.close()


def test_autoscale_absorbs_diurnal_ramp():
    scenario = ChaosScenario(
        "autoscale-ramp", [], seed=31,
        base=1, burst=6, tail=1, steps=10,
    )
    ChaosMatrix([scenario]).run(_AutoscaleRampFixture, join_timeout_s=180)


@pytest.mark.slow
def test_autoscale_diurnal_ramp_soak():
    """Scaled ramp for `make soak`: a 10x burst over more sessions and
    longer sequences — repetition is what finds the drain/retire vs
    sticky-lease races."""
    matrix = ChaosMatrix([
        ChaosScenario(
            f"autoscale-ramp-{seed}", [], seed=seed,
            base=2, burst=10, tail=3, steps=12, settle_timeout_s=120,
        )
        for seed in (7, 19)
    ])
    matrix.run(_AutoscaleRampFixture, join_timeout_s=600)
