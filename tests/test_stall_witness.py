"""The stall's witness (serve/prof.py, PR 37): the process's pulse times a
held interpreter and nothing else, a tick's dispatch is an upload and a call,
the one rule names what held a tick or a phase (each synthetic cause to its
own name), the record reaches every surface once, no family's tick uploads
anything, and the benchmark's new reader and metric files read a run.

CPU, tiny sizes, fakes that sleep or hold the interpreter: the numbers here
are orderings and thresholds, never a device's time.  A check that NOTHING
is recorded is tried up to three times: a loaded machine can take the CPU
from the whole process for 50 ms, which is a host pause and no fault.
"""

import ctypes
import io
import json
import logging
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import client_tpu.http as httpclient
import client_tpu.serve.lm.engine as engine_mod
from client_tpu import profview
from client_tpu.serve import Server
from client_tpu.serve import prof as prof_mod
from client_tpu.serve.flight import FlightRecorder
from client_tpu.serve.lm import LmEngine
from client_tpu.serve.metrics import Registry
from client_tpu.serve.models import axk1, cohere2moe, sambay
from client_tpu.serve.models import transformer as tfm
from client_tpu.serve.prof import (
    PAUSE_S,
    PULSE_S,
    STALL_S,
    PhaseProfiler,
    stall_cause,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
TINY = os.path.join(BENCH, "tests", "tiny")
PULSE = prof_mod._PULSE
_LIBC = ctypes.PyDLL(None)  # PyDLL keeps the GIL across the call


def hold(seconds):
    """Sleep WITH the interpreter held: what a C call that keeps the GIL
    does to every other thread."""
    _LIBC.usleep(int(seconds * 1e6))


def until(predicate, timeout=2.0):
    deadline = time.monotonic() + timeout
    while not predicate() and time.monotonic() < deadline:
        time.sleep(0.01)
    return predicate()


def quiet(attempt, tries=3):
    """*attempt* returns what it found where nothing should be: passes
    once one try finds nothing, fails with the last findings."""
    for _ in range(tries):
        found = attempt()
        if not found:
            return
    pytest.fail(f"never quiet in {tries} tries: {found}")


def thrice(test):
    """A test of a timed length, tried up to three times: what it times
    is exact on a quiet machine, and a loaded one adds its own pauses."""
    def tried(*args, **kwargs):
        for left in (2, 1, 0):
            try:
                return test(*args, **kwargs)
            except AssertionError:
                if not left:
                    raise
    tried.__name__, tried.__doc__ = test.__name__, test.__doc__
    return tried


@pytest.fixture(autouse=True)
def fresh_log_limit():
    """The log's one-a-second limit is the process's: a test starts with
    it open."""
    PULSE._logged_at, PULSE._not_logged = float("-inf"), 0
    PULSE._longest = None


# -- the pulse ----------------------------------------------------------------

def test_the_constants_stand_in_their_order():
    # a pulse well inside a pause, a pause inside a stall, and a pause far
    # over the switch interval within which a waiting thread is served
    import sys

    assert PULSE_S < PAUSE_S < STALL_S
    assert PAUSE_S >= 10 * sys.getswitchinterval()


@thrice
def test_the_pulse_times_a_held_interpreter():
    registry = Registry()
    prof = PhaseProfiler(name="t", registry=registry)
    assert until(lambda: PULSE.live and PULSE.due is not None)
    time.sleep(2 * PULSE_S)
    n_before = len(PULSE.pauses)
    t_a = time.monotonic()
    hold(0.3)
    t_b = time.monotonic()
    # asked at once, before the pulse has been given the interpreter: the
    # pause it is still held in counts
    assert 0.2 <= prof.pauses(t_a, t_b) <= 0.4
    assert until(lambda: len(PULSE.pauses) > n_before)
    t_begin, seconds = PULSE.pauses[-1]
    assert 0.25 <= seconds <= 0.4
    assert t_a - PULSE_S <= t_begin <= t_a + 2 * PULSE_S
    # recorded, it counts once; an interval takes its part of it
    assert prof.pauses(t_a - 1, t_b + 1) == pytest.approx(seconds)
    half = prof.pauses(t_a + 0.15, t_b + 1)
    assert 0.1 <= half <= 0.2
    assert prof.pauses(t_b + 0.1, t_b + 1) == 0.0
    assert registry.get("ctpu_prof_host_pauses_total") >= 1
    assert registry.get("ctpu_prof_host_pause_seconds_total") >= 0.25
    report = prof.report(window_s=0)
    assert report["host_pauses"]["count"] >= 1


@pytest.mark.parametrize("busy", [False, True], ids=["idle", "busy"])
def test_the_pulse_records_nothing_in_a_sound_process(busy):
    """Five seconds of an idle process, or of one whose other thread runs
    pure Python without a break: a waiting pulse is served within the
    switch interval, far inside ``PAUSE_S``."""
    prof = PhaseProfiler(name="t")
    assert prof.armed and until(lambda: PULSE.live)

    def attempt():
        stop = threading.Event()

        def spin():
            n = 0
            while not stop.is_set():
                n += 1

        worker = threading.Thread(target=spin, daemon=True)
        if busy:
            worker.start()
        t_a = time.monotonic()
        time.sleep(5.0)
        t_b = time.monotonic()
        stop.set()
        if busy:
            worker.join()
        return [p for p in list(PULSE.pauses) if t_a <= p[0] <= t_b]

    quiet(attempt)


def test_one_pulse_a_process_however_many_profilers():
    profs = [PhaseProfiler(name=f"p{i}") for i in range(5)]
    with Server(http_port=0):
        names = [t.name for t in threading.enumerate()]
    assert names.count("prof-pulse") == 1 and all(p.armed for p in profs)


def test_with_every_profiler_disarmed_the_pulse_records_nothing():
    prof = PhaseProfiler(name="t")
    with PULSE._lock:
        armed = list(PULSE._profilers)
    try:
        for p in armed:
            p.arm(False)
        assert until(lambda: not PULSE.live and PULSE.due is None)
        n_before, t_a = len(PULSE.pauses), time.monotonic()
        hold(0.2)
        time.sleep(3 * PULSE_S)
        assert len(PULSE.pauses) == n_before
        assert prof.pauses(t_a, time.monotonic()) == 0.0
    finally:
        for p in armed:
            p.arm(True)
    assert until(lambda: PULSE.live)


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("kwargs, expected", [
    (dict(spans=(("upload", 0.3), ("call", 0.001))), ("upload", 0.3)),
    # an upload that held the interpreter is still an upload
    (dict(spans=(("upload", 0.3), ("call", 0.2)), host_pause_s=0.3),
     ("upload", 0.3)),
    (dict(spans=(("upload", 0.001), ("call", 0.2))), ("call", 0.2)),
    (dict(spans=(("upload", 0.001), ("call", 2.0)), compile_s=1.5),
     ("compile", 2.0)),
    (dict(spans=(("upload", 0.001), ("call", 2.0)), compile_s=0.5),
     ("call", 2.0)),
    (dict(spans=(("host", 0.4),)), ("host", 0.4)),
    (dict(spans=(("host", 0.4),), host_pause_s=0.3), ("host_pause", 0.4)),
    (dict(spans=(("host", 9.0),), compile_s=8.0, host_pause_s=5.0),
     ("compile", 9.0)),
    (dict(spans=(("upload", 0.001), ("call", 0.001)), device_s=2.02,
          median_s=0.014), ("device", 2.006)),
    (dict(spans=(("upload", 0.001), ("call", 0.001)), device_s=2.02,
          median_s=0.014, host_pause_s=1.9), ("host_pause", 2.006)),
    (dict(spans=(("upload", 0.001), ("call", 0.001)), device_s=2.02,
          median_s=0.014, host_pause_s=0.9), ("device", 2.006)),
    # four times a median of 40 ms is 160 ms: 150 is no stall
    (dict(spans=(), device_s=0.15, median_s=0.04), None),
    # under STALL_S nothing is, whatever the median
    (dict(spans=(("upload", 0.09), ("call", 0.09)), device_s=0.09,
          median_s=0.001), None),
    # a kind's first entries have no median: device time is not judged
    (dict(spans=(("upload", None), ("call", None)), device_s=5.0), None),
], ids=["upload", "upload-with-the-gil", "call", "compile", "call-that-"
        "compiled-a-little", "host", "host-held", "host-compile", "device",
        "host-pause", "pause-under-half", "under-four-medians",
        "under-stall-s", "no-median"])
def test_stall_cause(kwargs, expected):
    verdict = stall_cause(**kwargs)
    if expected is None:
        assert verdict is None
    else:
        assert verdict[0] == expected[0]
        assert verdict[1] == pytest.approx(expected[1])


# -- a tick's dispatch, and what held it --------------------------------------

CFG = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    max_seq=96,
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(0), CFG)


def _engine(params):
    # no dispatch ahead: a tick is read back before the next is built, so
    # what holds one tick touches no other
    return LmEngine(params, CFG, max_slots=2, lane_counts=(2,), block_size=8,
                    prefill_chunk=16, min_bucket=4, readback_depth=0)


def _stream(engine, prompt, n):
    q, _ = engine.submit(prompt, n)
    out = []
    while True:
        tok = q.get(timeout=120)
        if tok is LmEngine.CLOSE:
            return out
        out.append(tok)


class _Late:
    """A tick's tokens whose completion comes *seconds* after the dispatch,
    the waiter asleep (the interpreter free: a slow device) or holding the
    interpreter (the completion is there, nobody can be told)."""

    def __init__(self, inner, seconds, held):
        self.inner, self.held = inner, held
        self.t_ready = time.monotonic() + seconds

    def _wait(self):
        left = self.t_ready - time.monotonic()
        if left > 0:
            (hold if self.held else time.sleep)(left)

    def block_until_ready(self):
        self._wait()
        return self

    def copy_to_host_async(self):
        self.inner.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self._wait()
        return np.asarray(self.inner)


class _SlowJnp:
    """``jax.numpy`` for the engine's module, whose next ``asarray`` after
    ``slow_s`` is set sleeps so long: an upload that takes its time."""

    slow_s = 0.0

    def __getattr__(self, name):
        return getattr(jnp, name)

    def asarray(self, value):
        seconds, self.slow_s = self.slow_s, 0.0
        if seconds:
            self.napping_in_upload(seconds)
        return jnp.asarray(value)

    @staticmethod
    def napping_in_upload(seconds):
        time.sleep(seconds)


def _script(engine, shim, at, upload_s=0.0, call_s=0.0, late=None):
    """The engine's family with a fault in decode tick number *at* (from
    now, counted from 1): its upload or its call sleeps, or its result
    completes late."""
    real, calls = engine._programs.tick, [0]

    def tick(fn, params, kv, tokens, *rest):
        tokens = getattr(tokens, "inner", tokens)
        calls[0] += 1
        if calls[0] == at and call_s:
            time.sleep(call_s)
        tokens, *out = real(fn, params, kv, tokens, *rest)
        if calls[0] == at and late:
            tokens = _Late(tokens, *late)
        if calls[0] == at - 1:
            shim.slow_s = upload_s  # the NEXT tick's first upload
        return (tokens, *out)

    engine._programs.tick = tick


@pytest.fixture
def warm_engine(params, monkeypatch):
    """(engine, jnp shim): every shape compiled and the decode ticks'
    median there (30 ticks), so that what a test injects is all there is
    to find."""
    shim = _SlowJnp()
    monkeypatch.setattr(engine_mod, "jnp", shim)
    engine = _engine(params)
    _stream(engine, [3, 4, 5, 6, 7], 30)
    yield engine, shim
    engine.close()


def _decodes_since(engine, t):
    return [e for e in engine.tick_trace()
            if e["kind"] == "decode" and e["t0"] >= t]


@pytest.mark.parametrize("fault, cause", [
    (dict(upload_s=0.3), "upload"),
    (dict(call_s=0.3), "call"),
    (dict(late=(0.3, False)), "device"),
    (dict(late=(0.3, True)), "host_pause"),
])
def test_a_faulty_tick_is_marked_with_its_cause_and_no_other(
        warm_engine, fault, cause):
    engine, shim = warm_engine
    flight = engine.prof.flight = FlightRecorder()
    t = time.monotonic()
    _script(engine, shim, at=6, **fault)
    _stream(engine, [3, 4, 5, 6, 7], 12)
    engine.close()  # joins the observer: every completion is in
    ticks = _decodes_since(engine, t)
    entry = ticks[5]
    assert entry.get("stall") == cause, (entry, ticks)
    assert 0.25 <= entry["stall_s"] <= 0.6
    if cause == "upload":
        assert entry["upload_s"] >= 0.3 > entry["call_s"]
    elif cause == "call":
        assert entry["call_s"] >= 0.3 > entry["upload_s"]
    else:
        # the device's time reads the same in both: what differs is
        # whether the interpreter was held while it ran
        assert 0.29 <= entry["device_s"] <= 0.6
        assert entry["upload_s"] < STALL_S > entry["call_s"]
    if cause == "host_pause":
        assert entry["host_pause_s"] >= 0.5 * entry["device_s"]
    elif cause == "device":
        assert entry["host_pause_s"] < 0.5 * entry["stall_s"]
    # ONE record of it, with the tick it belongs to (a loaded machine
    # may hold another tick for real: that one is not this one)
    record, = [r for r in flight.snapshot()
               if r["kind"] == "stall" and r["tick"] is not None
               and r["cause"] == cause and r["seconds"] == entry["stall_s"]]
    assert record["tick"] == {"kind": "decode", "width": 2, "lanes": 1,
                              "start": None}
    assert record["seconds"] == pytest.approx(entry["stall_s"])
    assert record["engine"] == "lm" and record["gc_s"] >= 0.0
    assert record["phase"] == {"upload": "upload",
                               "call": "decode_dispatch"}.get(cause)
    if cause == "upload":
        # the interpreter was free under the sleeping upload: the pulse
        # could look, and the frames say where the thread was
        assert any("napping_in_upload" in row
                   for thread in record["frames"] for row in thread["frames"])
    rollup = engine.prof.rollup(window_s=0)["stalls"]
    assert rollup["by_cause"][cause]["count"] >= 1
    assert [r for r in rollup["last"] if r["t0"] == record["t0"]] == [
        {"kind": "stall", **{k: v for k, v in record.items()
                             if k not in ("kind", "ts")}}]


def test_a_sound_run_marks_nothing_and_its_spans_add_up(warm_engine):
    engine, _ = warm_engine

    def attempt():
        t = time.monotonic()
        _stream(engine, [9] * 20, 12)
        until(lambda: all("t_done" in e for e in engine.tick_trace()))
        ticks = [e for e in engine.tick_trace() if e["t0"] >= t]
        assert {e["kind"] for e in ticks} == {"decode", "prefill_chunk"}
        for e in ticks:
            assert e["upload_s"] > 0 and e["call_s"] > 0
            assert e["upload_s"] + e["call_s"] <= e["t1"] - e["t0"]
        return [e for e in ticks if "stall" in e or "stall_s" in e
                or e["host_pause_s"] != 0.0]

    quiet(attempt)
    phases = engine.prof.rollup(window_s=0)["phases"]
    assert {"build", "upload", "decode_dispatch", "prefill_dispatch",
            "record"} <= set(phases)


def test_disarmed_an_entry_has_none_of_the_witness_fields(params):
    engine = _engine(params)
    engine.prof.arm(False)
    try:
        _stream(engine, [3, 4, 5], 6)
    finally:
        engine.close()
    ticks = engine.tick_trace()
    assert ticks and all("device_s" in e for e in ticks)
    for e in ticks:
        assert not {"upload_s", "call_s", "host_pause_s", "stall",
                    "stall_s"} & set(e)
    assert engine.prof.rollup(window_s=0)["stalls"] == {
        "by_cause": {}, "last": []}


# -- a phase that belongs to no tick ------------------------------------------

def napping(seconds):
    time.sleep(seconds)


def test_a_phase_open_past_stall_s_carries_the_frames_of_who_slept():
    prof = PhaseProfiler(name="t")
    with prof.start_tick("unary") as tick, tick.phase("execute"):
        napping(STALL_S + 0.15)
    stalls = prof.rollup(window_s=0)["stalls"]
    assert stalls["by_cause"] == {
        "host": {"count": 1, "seconds": pytest.approx(0.25, abs=0.1)}}
    record, = stalls["last"]
    assert (record["phase"], record["tick"], record["cause"]) == (
        "execute", None, "host")
    mine = [t for t in record["frames"] if t["thread"] == "MainThread"]
    assert mine and any(row.startswith("tests/test_stall_witness.py:")
                        and row.endswith(" napping")
                        for row in mine[0]["frames"])
    assert all(len(t["frames"]) <= 8 for t in record["frames"])
    assert prof.snapshot()[-1]["phases"]["execute"] >= STALL_S


@pytest.mark.parametrize("name", ["idle", "device_wait", "device_queue",
                                  "batch.gather", "wait"])
def test_a_wait_by_design_is_never_a_stall(name):
    prof = PhaseProfiler(name="t")
    with prof.start_tick("sched") as tick:
        bracket = prof.span(name) if "." in name else tick.phase(name)
        with bracket as phase:
            napping(STALL_S + 0.1)
    assert phase.seconds >= STALL_S and phase.frames is None
    assert prof.rollup(window_s=0)["stalls"] == {"by_cause": {}, "last": []}


def test_a_held_phase_closes_unjudged_for_its_tick_to_report():
    prof = PhaseProfiler(name="t")
    with prof.start_tick("sched") as tick:
        with tick.phase("upload", held=True) as phase:
            napping(STALL_S + 0.1)
    assert phase.seconds >= STALL_S and phase.frames
    assert prof.rollup(window_s=0)["stalls"]["last"] == []


# -- the record's surfaces ----------------------------------------------------

def _infer_simple(client, n=1):
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    inputs = [httpclient.InferInput("INPUT0", [1, 16], "INT32"),
              httpclient.InferInput("INPUT1", [1, 16], "INT32")]
    inputs[0].set_data_from_numpy(a)
    inputs[1].set_data_from_numpy(a)
    for _ in range(n):
        client.infer("simple", inputs)


def test_a_stall_leaves_one_record_on_every_surface(caplog, tmp_path, capsys):
    with Server(http_port=0) as server:
        with httpclient.InferenceServerClient(server.http_address) as c:
            _infer_simple(c, n=2)
        with caplog.at_level(logging.INFO, logger="client_tpu.serve.prof"):
            # the batcher's thread brackets its loop so
            with server.engine.prof.span("batch.dispatch"):
                napping(STALL_S + 0.05)
        base = f"http://{server.http_address}"
        report = json.loads(
            urllib.request.urlopen(base + "/v2/debug/prof?window=0").read())
        dump = urllib.request.urlopen(
            base + "/v2/debug/flight").read().decode()
        metrics = urllib.request.urlopen(base + "/metrics").read().decode()
    # the rollup and its endpoint
    serve = {e["engine"]: e for e in report["engines"]}["serve"]
    assert serve["stalls"]["by_cause"] == {
        "host": {"count": 1, "seconds": pytest.approx(0.15, abs=0.08)}}
    record, = serve["stalls"]["last"]
    assert set(record) == {
        "kind", "cause", "engine", "t0", "seconds", "phase", "tick",
        "host_pause_s", "gc_s", "inflight", "frames"}
    assert (record["kind"], record["phase"]) == ("stall", "dispatch")
    assert "host_pauses" in report
    # the flight ring, with the passes before it
    lines = [json.loads(line) for line in dump.splitlines()]
    noted = [r for r in lines if r["kind"] == "stall"]
    assert len(noted) == 1 and noted[0]["t0"] == record["t0"]
    assert any(r["kind"] == "prof_tick" for r in lines)
    # the counter
    assert 'ctpu_prof_stalls_total{cause="host",engine="serve"} 1' in metrics
    # the log: one WARNING line, the whole record in it
    logged = [r for r in caplog.records if r.name == "client_tpu.serve.prof"]
    assert len(logged) == 1 and logged[0].levelno == logging.WARNING
    message = logged[0].getMessage()
    assert message.startswith("stall: ")
    assert json.loads(message[len("stall: "):])["t0"] == record["t0"]
    # profview, from the report and from the flight dump
    for name, text in (("prof.json", json.dumps(report)),
                       ("flight.jsonl", dump)):
        path = tmp_path / name
        path.write_text(text)
        assert profview.main([str(path), "--engine", "serve"]) == 0
        out = capsys.readouterr().out
        assert "stalls: host 1 (0.1" in out
        assert "stall host 0.1" in out and "phase=dispatch" in out


def test_a_burst_of_stalls_logs_one_line_a_second(caplog):
    prof = PhaseProfiler(name="t")
    out = io.StringIO()
    with caplog.at_level(logging.WARNING, logger="client_tpu.serve.prof"):
        for _ in range(4):
            with prof.span("batch.dispatch"):
                napping(STALL_S)
        assert len(caplog.records) == 1
        time.sleep(1.0)
        with prof.span("batch.handoff"):
            napping(STALL_S)
        assert len(caplog.records) == 2
        said = caplog.records[1].getMessage()
        assert "(and 3 before it, not logged; the longest: {" in said
        # what the limit holds back after the last line is said at exit
        with prof.span("batch.dispatch"):
            napping(STALL_S + 0.05)
        assert len(caplog.records) == 2
        PULSE.last_words()
        assert len(caplog.records) == 3
        assert '"seconds": 0.1' in caplog.records[2].getMessage()
        PULSE.last_words()  # nothing held back: nothing said
    assert len(caplog.records) == 3
    # every one is in the rollup, logged or not
    stalls = prof.rollup(window_s=0)["stalls"]
    assert stalls["by_cause"]["host"]["count"] == 6
    profview.render_engine(prof.rollup(window_s=0), out)
    assert "stalls: host 6" in out.getvalue()


def test_a_compile_is_said_at_info_and_warns_nobody(caplog):
    prof = PhaseProfiler(name="t")
    assert until(lambda: PULSE._compiles_heard)

    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 + jnp.cumsum(x)

    with caplog.at_level(logging.INFO, logger="client_tpu.serve.prof"):
        with prof.start_tick("unary") as tick, tick.phase("execute"):
            t0 = time.monotonic()
            fresh(jnp.ones((7,))).block_until_ready()
            compile_s = time.monotonic() - t0
            napping(max(STALL_S - compile_s, 0.0) + 0.01)
    stalls = prof.rollup(window_s=0)["stalls"]
    if compile_s >= 0.06:  # the compile is most of the phase
        assert list(stalls["by_cause"]) == ["compile"]
        assert [r.levelno for r in caplog.records] == [logging.INFO]
    else:
        assert list(stalls["by_cause"]) == ["host"]


# -- no family's tick uploads -------------------------------------------------

BLOCK = 4
FAMILIES = {
    "decoder": tfm.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=96, dtype="float32"),
    "sambay": sambay.SambaYConfig(
        vocab_size=97, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, max_seq=64, window=8, d_inner=128, d_state=4,
        d_conv=4, dt_rank=4, dtype="float32"),
    "cohere2moe": cohere2moe.Cohere2MoeConfig(
        vocab_size=97, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=32, n_experts=8, top_k=2, experts_held=(1, 2, 5, 6),
        n_shared=2, window=8, max_seq=64, dtype="float32"),
    "axk1": axk1.AxK1Config(
        vocab_size=97, d_model=32, n_layers=4, n_heads=4, q_lora_rank=16,
        kv_lora_rank=24, nope_dim=8, rope_dim=8, v_dim=8, first_dense=1,
        d_dense=48, d_ff=16, n_experts=8, top_k=2, experts_held=(1, 2, 5, 6),
        n_shared=1, rope_factor=4.0, rope_original=32, beta_fast=4.0,
        max_seq=64, dtype="float32"),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_no_familys_tick_uploads_anything(family):
    """With device arguments a family's ``tick`` runs where the host may
    send the device nothing; with a host ``live`` it is refused: so a
    tick's every upload is the engine's, inside ``lm.upload``."""
    cfg = FAMILIES[family]
    params = cfg.family.init_params(jax.random.PRNGKey(0), cfg)
    engine = LmEngine(params, cfg, max_slots=2, lane_counts=(2,),
                      block_size=BLOCK, prefill_chunk=8, min_bucket=4)
    try:
        # a stream through the engine itself: the pools are there, the
        # tick is compiled, and every upload it made went through the
        # engine's own span
        assert len(_stream(engine, [3, 4, 5, 6, 7], 3)) == 3
    finally:
        engine.close()
    ticks = [e for e in engine.tick_trace() if e["kind"] == "decode"]
    assert ticks and all(e["upload_s"] > 0 for e in ticks)
    width = engine._table_width
    live = np.array([True, False])
    host = (np.zeros((2, width), np.int32), np.zeros((2,), np.int32), live,
            np.zeros((2,), np.float32), np.zeros((2,), np.int32))
    on_device = tuple(jnp.asarray(a) for a in host)
    fn = engine._tick_for(2)
    with jax.transfer_guard_host_to_device("disallow"):
        tokens, keys, *_ = engine._programs.tick(
            fn, engine.params, engine.kv, engine._tokens, *on_device,
            engine._keys)
        jax.block_until_ready((tokens, keys))
        with pytest.raises(Exception, match="[Dd]isallowed host-to-device"):
            engine._programs.tick(
                fn, engine.params, engine.kv, tokens, *on_device[:2], live,
                *on_device[3:], keys)


# -- the benchmark's reader and metric files ----------------------------------

def _tick_sum(params, ticks):
    from benchmark.readers import tick_sum

    return tick_sum.read(params, {"window": {"ticks": ticks}})


HAND_MADE = [
    {"kind": "decode", "host_pause_s": 0.0},
    {"kind": "decode", "host_pause_s": 0.07, "stall": "host_pause",
     "stall_s": 0.25},
    {"kind": "decode"},                      # in flight: no field yet
    {"kind": "prefill_chunk", "host_pause_s": 0.03},
    {"kind": "verify", "host_pause_s": 0.5, "stall_s": 2.0},
    {"kind": "draft", "host_pause_s": 9.0},  # no kind the metric names
]


@pytest.mark.parametrize("params, ticks, expected", [
    ({"kinds": ["decode", "prefill_chunk"], "field": "host_pause_s",
      "scale": 1e3}, HAND_MADE, 100.0),
    ({"kinds": ["decode", "prefill_chunk", "verify"], "field": "stall_s"},
     HAND_MADE, 2.25),
    # a sound run of this program: the field is there, and sums to nothing
    ({"kinds": ["decode"], "field": "host_pause_s"}, HAND_MADE[:1], 0.0),
    # an earlier program's ticks, or a disarmed profiler's: not reported
    ({"kinds": ["decode", "prefill_chunk"], "field": "host_pause_s"},
     [{"kind": "decode", "t0": 1.0, "t1": 1.1, "device_s": 0.01}], None),
    ({"kinds": ["decode"], "field": "stall_s"}, HAND_MADE[:1], None),
    ({"kinds": ["decode"], "field": "stall_s"}, [], None),
], ids=["sums-and-scales", "skips-entries-without-the-field", "zero-is-a-"
        "reading", "an-earlier-program", "no-tick-was-marked", "no-ticks"])
def test_tick_sum(params, ticks, expected):
    value = _tick_sum(params, ticks)
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected)


METRICS = ("tick_upload_ms", "tick_call_ms", "host_pause_ms", "tick_stall_ms")


@pytest.fixture(scope="module")
def overlay_run(tmp_path_factory):
    """One traced run of the tiny chat cell whose workload file, laid over
    the benchmark's, names the four new metrics: (result line's metrics,
    the window's ticks).  A cell's file is the one place ``run.load_cell``
    finds metrics, and a PR of this kind edits none.  There is no chip
    here, so the trace's reduction is stubbed: the four read the ticks."""
    from benchmark import run
    from benchmark import trace as trace_reader
    from benchmark.drivers import lm_stream

    overlay = tmp_path_factory.mktemp("overlay")
    os.makedirs(overlay / "workloads")
    with open(os.path.join(TINY, "workloads", "lm-tiny.chat.json")) as f:
        cell = json.load(f)
    # a name of its own: the run's scratch directory is the cell's
    cell["name"], cell["metrics"] = "lm-tiny.witness", list(METRICS)
    with open(overlay / "workloads" / "lm-tiny.witness.json", "w") as f:
        json.dump(cell, f)
    windows = []
    patch = pytest.MonkeyPatch()
    patch.setattr(trace_reader, "read", lambda path: {
        "busy_s": 0.5, "window_s": 1.0, "device_ops": [], "idle_gaps": [],
        "modules": {}})
    measure = lm_stream.Run.measure

    def keeping(self, seconds, tracer):
        windows.append(measure(self, seconds, tracer))
        return windows[-1]

    patch.setattr(lm_stream.Run, "measure", keeping)
    try:
        run.build_native()
        result = run.main(
            ["--workload", "lm-tiny.witness", "--seed", "2147483999",
             "--seconds", "2", "--trace", "1"],
            require_tpu=False, roots=(str(overlay), TINY, BENCH))
    finally:
        patch.undo()
    assert result["correct"] is True and result["failed"] == 0
    return result["metrics"], windows[0]["ticks"]


@pytest.mark.parametrize("name", METRICS)
def test_metric_file_reads_a_finished_run(name, overlay_run):
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        metric = json.load(f)
    assert metric["name"] == name and metric["better"] == "lower"
    assert (metric["source"], metric["unit"], metric["moves"]) == (
        "program_span", "ms", "tokens_per_s")
    assert metric["layer"] == ("device" if name == "tick_stall_ms"
                               else "engine")
    reported, ticks = overlay_run
    decodes = [t for t in ticks if t["kind"] == "decode"]
    assert decodes and all("host_pause_s" in t for t in decodes)
    if name in ("tick_upload_ms", "tick_call_ms"):
        field = metric["params"]["field"]
        values = sorted(1e3 * t[field] for t in decodes)
        assert reported[name]["unit"] == "ms"
        assert values[0] <= reported[name]["value"] <= values[-1]
        assert 0 < reported[name]["value"] < 1e3
    elif name == "host_pause_ms":
        total = 1e3 * sum(t["host_pause_s"] for t in ticks)
        assert reported[name]["value"] == pytest.approx(total, abs=1e-6)
    else:
        # reported where a tick of the window was marked, and then their sum
        marked = [t["stall_s"] for t in ticks if "stall_s" in t]
        if marked:
            assert reported[name]["value"] == pytest.approx(1e3 * sum(marked))
        else:
            assert name not in reported
