"""Device time as the program reports it (serve/_completion.py).

The completion observer stamps the instant at which each watched result
was complete, item by item in watch order, and everything called device
time derives from those instants:

- the observer itself: one instant per item, dispatch-order device time
  and device queue, failed items still reported and called back;
- the batcher's TPU-shm path: counts at hand-off, ``compute_infer_ns``
  and the profiler's ``compute``/``device_queue`` phases at completion;
- ``LmEngine.tick_trace()``: ``t_done``/``device_s`` on every tick that
  dispatched device work, the first token's wait on the chunk that ends
  a prompt;
- ``benchmark/readers/tick_field.py`` and the metric files that read
  those fields.

CPU, tiny sizes, fakes that sleep: the numbers here are orderings and
bounds, never a device's times.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.readers import tick_field
from client_tpu.serve._completion import CompletionObserver
from client_tpu.serve.dynamic_batcher import ModelBatcher
from client_tpu.serve.lm import LmEngine
from client_tpu.serve.model_runtime import Model, ModelStats, TensorSpec
from client_tpu.serve.models import transformer as tfm
from client_tpu.serve.prof import PhaseProfiler, annotation, attribute_phases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_S = 0.04


class SlowResult:
    """What the observer waits on: complete ``delay_s`` after the wait
    begins, as a device that runs one step at a time would be."""

    def __init__(self, delay_s=STEP_S, error=None, shape=(2, 4)):
        self.delay_s, self.error, self.shape = delay_s, error, shape

    def block_until_ready(self):
        time.sleep(self.delay_s)
        if self.error is not None:
            raise self.error
        return self


def _watch_all(obs, results, t_dispatch_ns, on_error=None):
    """Watch *results* in order, all dispatched at *t_dispatch_ns*; the
    callbacks' arguments once every one has run."""
    seen, every = [], threading.Event()

    def done(i, t_done_ns, device_ns, queue_ns):
        seen.append((i, t_done_ns, device_ns, queue_ns))
        if len(seen) == len(results):
            every.set()

    for i, result in enumerate(results):
        obs.watch(result, lambda *a, i=i: done(i, *a),
                  on_error=None if on_error is None
                  else (lambda exc, i=i: on_error(i, exc)),
                  t_dispatch_ns=t_dispatch_ns)
    assert every.wait(timeout=10)
    return seen


# -- the observer ------------------------------------------------------------

def test_each_watched_item_gets_its_own_instant_in_watch_order():
    obs = CompletionObserver(name="t-watch")
    try:
        t0 = time.monotonic_ns()
        seen = _watch_all(obs, [SlowResult() for _ in range(4)], t0)
    finally:
        obs.close()
    assert [i for i, *_ in seen] == [0, 1, 2, 3]
    instants = [t for _, t, _, _ in seen]
    step_ns = STEP_S * 1e9
    for a, b in zip([t0] + instants, instants):
        assert b - a >= 0.9 * step_ns  # an instant each, a step apart
    for i, (_, t_done, device_ns, queue_ns) in enumerate(seen):
        # the device worked on item i from the completion before it
        assert 0.9 * step_ns <= device_ns < 3 * step_ns
        assert queue_ns >= 0.9 * i * step_ns
        assert t0 + queue_ns + device_ns == t_done


def test_an_idle_device_starts_at_dispatch_not_at_the_last_completion():
    obs = CompletionObserver(name="t-watch")
    try:
        _watch_all(obs, [SlowResult(0.0)], time.monotonic_ns())
        time.sleep(3 * STEP_S)  # nothing dispatched meanwhile
        t1 = time.monotonic_ns()
        (_, t_done, device_ns, queue_ns), = _watch_all(
            obs, [SlowResult()], t1)
    finally:
        obs.close()
    assert queue_ns == 0
    assert device_ns == t_done - t1
    assert device_ns < 3 * STEP_S * 1e9


def test_a_failed_item_is_reported_called_back_and_stamped(caplog):
    obs = CompletionObserver(name="t-watch")
    failed = []
    results = [SlowResult(), SlowResult(error=RuntimeError("HBM fell over")),
               SlowResult()]
    try:
        with caplog.at_level("ERROR", logger="client_tpu.serve._completion"):
            seen = _watch_all(obs, results, time.monotonic_ns(),
                              on_error=lambda i, exc: failed.append((i, exc)))
    finally:
        obs.close()
    assert [i for i, *_ in seen] == [0, 1, 2]  # the failed one's too
    assert [i for i, _ in failed] == [1]
    assert "HBM fell over" in caplog.text
    instants = [t for _, t, _, _ in seen]
    assert instants == sorted(instants) and len(set(instants)) == 3


def test_host_results_run_inline_and_observe_no_device_work():
    obs = CompletionObserver(name="t-watch")
    seen = []
    obs.watch({"OUT": np.zeros(3)}, lambda *a: seen.append(a))
    (t_done, device_ns, queue_ns), = seen
    obs.close()
    assert device_ns is None and queue_ns is None
    assert t_done <= time.monotonic_ns()


# -- the batcher's TPU-shm path ---------------------------------------------

def test_tpushm_batch_adds_compute_infer_at_completion_not_at_handoff():
    slow_s = 0.25

    def fn(inputs, params, ctx):
        return {"OUT": SlowResult(slow_s, shape=inputs["IN"].shape)}

    model = Model(
        "slow", inputs=[TensorSpec("IN", "FP32", [-1, 4])],
        outputs=[TensorSpec("OUT", "FP32", [-1, 4])], fn=fn,
        max_batch_size=8, dynamic_batching=True, batch_device_inputs=True,
        flops_per_item=1e6,
    )
    stats, prof = ModelStats(), PhaseProfiler(name="serve")
    batcher = ModelBatcher(model, stats, prof=prof)
    rows = jnp.ones((2, 4), jnp.float32).block_until_ready()
    try:
        t0 = time.monotonic()
        out = batcher.submit({"IN": rows})
        acked_s = time.monotonic() - t0
        # the ack is the hand-off: counts and queue time are in, the
        # device has hardly begun, and no compute time is claimed yet
        assert acked_s < slow_s / 2
        assert isinstance(out["OUT"], SlowResult)
        assert stats.execution_count == 1 and stats.inference_count == 2
        assert stats.compute_infer_ns == 0
        assert not prof.snapshot()
        deadline = time.monotonic() + 10
        while stats.compute_infer_ns == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert 0.9 * slow_s * 1e9 <= stats.compute_infer_ns < 3 * slow_s * 1e9
        assert stats.execution_count == 1  # time added, nothing recounted
    finally:
        batcher.close()
    tick, = prof.snapshot()
    assert tick["kind"] == "batch" and tick["items"] == 2
    assert tick["phases"]["compute"] == pytest.approx(
        stats.compute_infer_ns / 1e9)
    assert tick["device_s"] == tick["phases"]["compute"]
    roll = prof.rollup(window_s=0)
    assert roll["models"]["slow"]["device_s"] == pytest.approx(
        stats.compute_infer_ns / 1e9, abs=1e-5)


def test_a_step_behind_another_reports_its_device_queue():
    """Two groups dispatched back to back: the second's device time
    starts at the first's completion, and the wait is its
    ``device_queue`` phase."""
    def fn(inputs, params, ctx):
        return {"OUT": SlowResult(0.1, shape=inputs["IN"].shape)}

    model = Model(
        "slow", inputs=[TensorSpec("IN", "FP32", [-1, 4])],
        outputs=[TensorSpec("OUT", "FP32", [-1, 4])], fn=fn,
        max_batch_size=2, dynamic_batching=True, batch_device_inputs=True,
    )
    stats, prof = ModelStats(), PhaseProfiler(name="serve")
    batcher = ModelBatcher(model, stats, prof=prof, max_queue_delay_s=0.0)
    try:
        for _ in range(2):  # each fills the batch: two groups, two steps
            batcher.submit({"IN": jnp.ones((2, 4), jnp.float32)})
        deadline = time.monotonic() + 10
        while len(prof.snapshot()) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        batcher.close()
    first, second = prof.snapshot()
    assert "device_queue" not in first["phases"]
    assert second["phases"]["device_queue"] >= 0.05
    for tick in (first, second):
        assert 0.09 <= tick["phases"]["compute"] < 0.3
    assert stats.compute_infer_ns == pytest.approx(
        1e9 * (first["phases"]["compute"] + second["phases"]["compute"]))
    split = prof.rollup(window_s=0)["attribution"]
    assert split["device_wait_pct"] > 0 and split["compute_pct"] > 0


# -- the profiler's grouping and annotations --------------------------------

def test_dispatch_and_waits_are_not_compute():
    split = attribute_phases({
        "compute": 0.4, "decode_dispatch": 0.05, "prefill_dispatch": 0.05,
        "device_wait": 0.2, "device_queue": 0.1, "deliver": 0.2,
    })
    assert split == {"compute_pct": 40.0, "dispatch_pct": 10.0,
                     "device_wait_pct": 30.0, "host_pct": 20.0,
                     "idle_pct": 0.0}


def test_phase_annotations_land_in_a_profiler_trace(tmp_path):
    """A tick's phases and the batcher's brackets are spans named
    ``<profiler>.<phase>`` on the host plane of the profiler's own trace;
    outside a session they are inert."""
    from benchmark import trace as trace_reader

    prof = PhaseProfiler(name="lm")
    with prof.start_tick("sched") as tick, tick.phase("schedule"):
        pass  # no session: nothing to record, nothing raised
    jax.profiler.start_trace(str(tmp_path))
    try:
        with prof.start_tick("sched") as tick:
            with tick.phase("decode_dispatch"):
                jnp.ones((8,)).block_until_ready()
            with tick.phase("device_wait"):
                time.sleep(0.002)
        with annotation("batch.gather"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        trace_reader.newest_xplane(str(tmp_path)))
    names = {e.name for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"lm.decode_dispatch", "lm.device_wait", "batch.gather"} <= names
    assert "lm.schedule" not in names
    phases = prof.snapshot()[-1]["phases"]
    assert set(phases) == {"decode_dispatch", "device_wait"}


# -- LmEngine.tick_trace() ---------------------------------------------------

CFG = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    max_seq=96,
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(0), CFG)


def _stream(engine, prompt, n):
    """(tokens, the instant the first one was taken off the queue)."""
    q, _ = engine.submit(prompt, n)
    out, t_first = [], None
    while True:
        tok = q.get(timeout=120)
        if tok is LmEngine.CLOSE:
            return out, t_first
        if t_first is None:
            t_first = time.monotonic()
        out.append(tok)


def _finished_run(params):
    """(ticks, wall seconds, {client: (sent, first token taken)}) of
    three streams through a two-lane engine, closed."""
    eng = LmEngine(params, CFG, max_slots=2, lane_counts=(2,), block_size=8,
                   prefill_chunk=16, min_bucket=4)
    t_begin = time.monotonic()
    firsts = {}

    def client(i, prompt):
        firsts[i] = (time.monotonic(), _stream(eng, prompt, 12)[1])

    try:
        # 40 tokens: three chunks, of which only the last ends the prompt
        threads = [threading.Thread(target=client, args=(i, p))
                   for i, p in enumerate(([3] * 40, [5, 6, 7], [9] * 20))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        eng.close()  # joins the observer: every instant is in
    return eng.tick_trace(), time.monotonic() - t_begin, firsts


@pytest.fixture(scope="module")
def finished_run(params):
    return _finished_run(params)


def test_every_tick_of_a_finished_run_has_its_device_time(finished_run):
    ticks, wall_s, firsts = finished_run
    kinds = {t["kind"] for t in ticks}
    assert kinds == {"decode", "prefill_chunk"}
    for t in ticks:
        assert t["t_done"] >= t["t0"], t
        assert t["device_s"] >= 0, t
        assert t["device_s"] <= t["t_done"] - t["t0"] + 1e-6
    # device time never overlaps itself: a tick's begins where the one
    # before it ended
    assert sum(t["device_s"] for t in ticks) <= wall_s
    done = [t["t_done"] for t in ticks]
    assert done == sorted(done)
    ends = [t for t in ticks if "t_submit" in t]
    chunks = [t for t in ticks if t["kind"] == "prefill_chunk"]
    assert len(ends) == 3 and len(chunks) > len(ends)
    for t in ends:
        assert t["kind"] == "prefill_chunk"
        assert t["t_submit"] <= t["t_admit"] <= t["t_done"] <= t["t_delivered"]
    # the engine's share of a first token lies inside the client's
    for (t_sent, t_first), t in zip(
            sorted(firsts.values()), sorted(ends, key=lambda t: t["t_submit"])):
        assert t_sent <= t["t_submit"] and t["t_delivered"] <= t_first


def test_a_draft_runs_on_the_host_and_has_no_device_time(params):
    eng = LmEngine(params, CFG, max_slots=2, lane_counts=(2,), block_size=8,
                   prefill_chunk=16, min_bucket=4, speculative={"k": 4})
    try:
        _stream(eng, [5, 6] * 6, 24)
    finally:
        eng.close()
    ticks = eng.tick_trace()
    assert {"draft", "verify", "prefill_chunk"} <= {t["kind"] for t in ticks}
    for t in ticks:
        if t["kind"] == "draft":
            assert "t_done" not in t and "device_s" not in t
        else:
            assert t["t_done"] >= t["t0"] and t["device_s"] >= 0


def test_the_lm_profiler_takes_device_time_from_the_ticks(params):
    eng = LmEngine(params, CFG, max_slots=2, lane_counts=(2,), block_size=8,
                   prefill_chunk=16, min_bucket=4)
    try:
        _stream(eng, [1, 2, 3], 12)
    finally:
        eng.close()
    ticked = sum(t["device_s"] for t in eng.tick_trace())
    roll = eng.prof.rollup(window_s=0)
    # what the scheduler had taken over by its last pass; never more
    assert 0 < roll["models"]["lm"]["device_s"] <= ticked + 1e-6
    assert roll["attribution"]["compute_pct"] == 0.0  # no phase of a pass


# -- the reader and its metric files ----------------------------------------

def _read(params, ticks):
    return tick_field.read(params, {"window": {"ticks": ticks}})


HAND_MADE = [
    {"kind": "decode", "device_s": 0.050},
    {"kind": "decode", "device_s": 0.060},
    {"kind": "decode", "device_s": 0.010},
    {"kind": "decode"},                      # in flight: no field yet
    {"kind": "draft", "t0": 1.0},
    {"kind": "prefill_chunk", "device_s": 0.2, "t_done": 3.0},
    {"kind": "prefill_chunk", "device_s": 0.4, "t_submit": 1.0,
     "t_admit": 1.5, "t_done": 4.0, "t_delivered": 4.25},
    {"kind": "prefill_chunk", "device_s": 0.3, "t_submit": 2.0,
     "t_admit": 2.1, "t_done": 5.0},         # not delivered: cancelled
]


@pytest.mark.parametrize("params, expected", [
    ({"kind": "decode", "field": "device_s", "stat": "p50", "scale": 1e3},
     50.0),
    ({"kind": "decode", "field": "device_s", "stat": "mean"}, 0.040),
    ({"kind": "prefill_chunk", "field": "device_s", "stat": "mean"}, 0.3),
    ({"kind": "prefill_chunk", "from": "t_submit", "to": "t_admit",
      "stat": "mean", "scale": 1e3}, 300.0),
    ({"kind": "prefill_chunk", "from": "t_done", "to": "t_delivered",
      "stat": "p50"}, 0.25),
    ({"kind": "draft", "field": "device_s", "stat": "p50"}, None),
    ({"kind": "verify", "field": "device_s", "stat": "p50"}, None),
    ({"kind": "decode", "from": "t_done", "to": "t_delivered",
      "stat": "mean"}, None),
], ids=["p50", "mean", "mean-of-another-kind", "difference",
        "difference-where-one-end-is-missing", "no-tick-has-the-field",
        "no-tick-of-the-kind", "no-tick-has-either-end"])
def test_tick_field(params, expected):
    value = _read(params, HAND_MADE)
    if expected is None:
        assert value is None
    else:
        assert value == pytest.approx(expected)


def test_tick_field_finds_nothing_in_a_program_without_the_fields():
    bare = [{"kind": "decode", "t0": 1.0, "t1": 1.1, "lanes": (0,),
             "n_lanes": 2}]
    assert _read({"kind": "decode", "field": "device_s", "stat": "p50"},
                 bare) is None
    assert _read({"kind": "decode", "field": "device_s", "stat": "p50"},
                 []) is None


def test_tick_field_refuses_a_statistic_it_does_not_have():
    with pytest.raises(KeyError):
        _read({"kind": "decode", "field": "device_s", "stat": "p99"},
              HAND_MADE)


@pytest.mark.parametrize("name", [
    "decode_tick_device_ms", "prefill_chunk_device_ms",
    "first_token_admit_wait_ms", "first_token_prefill_ms",
    "first_token_readback_ms",
])
def test_metric_file_reads_a_finished_run(name, finished_run):
    """Each metric file of this PR, through the reader it names, finds a
    number in a real engine's ticks, in milliseconds."""
    with open(os.path.join(ROOT, "benchmark", "metrics", f"{name}.json")) as f:
        metric = json.load(f)
    assert metric["name"] == name and metric["reader"] == "tick_field"
    assert metric["source"] == "program_span" and metric["unit"] == "ms"
    value = _read(metric["params"], finished_run[0])
    assert value is not None and 0 <= value < 120e3
