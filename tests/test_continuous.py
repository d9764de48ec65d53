"""Continuous-batching LM decode (serve/lm: LmEngine, BatchedLmRunner): batched
lanes must reproduce serial greedy decoding exactly, reuse slots, survive
cancels, and scale the serving path over concurrent streams."""

import queue
import threading
import time

import numpy as np
import pytest

import jax

from client_tpu.serve.lm import BatchedLmRunner, LmEngine
from client_tpu.serve.models import transformer as tfm

CFG = tfm.TransformerConfig(
    vocab_size=128,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    max_seq=48,
    dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(0), CFG)


def _serial(params, prompt, n):
    return list(tfm.generate(params, CFG, prompt, n, readback_depth=0))


def _collect(q):
    out = []
    while True:
        tok = q.get(timeout=60)
        if tok is LmEngine.CLOSE:
            return out
        out.append(tok)


def test_concurrent_streams_match_serial(params):
    """Lanes with different prompts and lengths decode EXACTLY the serial
    greedy streams — heterogeneous positions share one batched tick."""
    sched = LmEngine(params, CFG, max_slots=4)
    try:
        prompts = [[1, 2, 3], [7, 9], [5], [11, 3, 2, 8]]
        lengths = [6, 9, 4, 7]
        queues = [
            sched.submit(p, n)[0] for p, n in zip(prompts, lengths)
        ]
        got = [_collect(q) for q in queues]
        for p, n, tokens in zip(prompts, lengths, got):
            assert tokens == _serial(params, p, n), (p, n)
    finally:
        sched.close()


def test_slot_reuse_more_requests_than_lanes(params):
    sched = LmEngine(params, CFG, max_slots=2)
    try:
        prompts = [[i + 1, i + 2] for i in range(5)]
        queues = [sched.submit(p, 5)[0] for p in prompts]
        for p, q in zip(prompts, queues):
            assert _collect(q) == _serial(params, p, 5)
    finally:
        sched.close()


def test_cancel_frees_lane(params):
    sched = LmEngine(params, CFG, max_slots=1)
    try:
        q1, h1 = sched.submit([1, 2, 3], 30)
        assert q1.get(timeout=60) is not LmEngine.CLOSE
        sched.cancel(h1)
        # the single lane must come free for the next request
        q2, _ = sched.submit([4, 5], 4)
        assert _collect(q2) == _serial(params, [4, 5], 4)
    finally:
        sched.close()


def test_cancel_with_pending_queue(params):
    """cancel() must work by identity while other requests are PENDING —
    entry lists hold numpy prompts, so naive `in`/`remove` membership would
    raise numpy's ambiguous-truth ValueError (regression)."""
    sched = LmEngine(params, CFG, max_slots=1)
    try:
        q1, h1 = sched.submit([1, 2, 3], 20)
        q2, h2 = sched.submit([1, 2, 3], 6)  # same-shape prompt, queued
        q3, h3 = sched.submit([9], 6)        # different-shape prompt, queued
        assert q1.get(timeout=60) is not LmEngine.CLOSE
        sched.cancel(h1)   # active lane, pending entries present
        sched.cancel(h3)   # pending entry, removed by identity
        assert _collect(q2) == _serial(params, [1, 2, 3], 6)
    finally:
        sched.close()


def test_cancel_active_slot_closes_queue(params):
    """cancel() on an ADMITTED request must enqueue CLOSE on the slot's
    queue: a public-API consumer reading the queue directly (not the
    abandoning BatchedLmRunner generator) must never hang on get()."""
    sched = LmEngine(params, CFG, max_slots=1)
    try:
        q, h = sched.submit([1, 2, 3], 30)
        assert q.get(timeout=60) is not LmEngine.CLOSE
        sched.cancel(h)
        # drain whatever was in flight; the stream MUST terminate
        while True:
            tok = q.get(timeout=10)  # pre-fix: hangs forever here
            if tok is LmEngine.CLOSE:
                break
        sched.cancel(h)  # idempotent: double-cancel of a released lane
    finally:
        sched.close()


class _GatedPrefill:
    """Wraps a scheduler's jitted prefill so tests can hold the dispatch
    open and observe what the scheduler lock does meanwhile."""

    def __init__(self, real):
        self.real = real
        self.entered = threading.Event()
        self.release = threading.Event()

    def __call__(self, *args, **kwargs):
        self.entered.set()
        assert self.release.wait(timeout=60)
        return self.real(*args, **kwargs)


def test_submit_not_blocked_by_slow_prefill(params):
    """A slow (cold-compile) prefill must not head-of-line-block submit():
    the admission dispatch runs outside _cv (regression for the pre-fix
    _admit_locked, which held the condition lock across the compile)."""
    sched = LmEngine(params, CFG, max_slots=2)
    gate = _GatedPrefill(sched._prefill)
    sched._prefill = gate
    try:
        q1, _ = sched.submit([1, 2, 3], 4)
        assert gate.entered.wait(timeout=60)
        # scheduler thread is inside the prefill dispatch right now; the
        # lock must be free for new submissions and cancels
        t0 = time.monotonic()
        q2, h2 = sched.submit([4, 5], 3)
        sched.cancel(None)
        submit_latency = time.monotonic() - t0
        gate.release.set()
        assert submit_latency < 1.0, submit_latency
        assert _collect(q1) == _serial(params, [1, 2, 3], 4)
        assert _collect(q2) == _serial(params, [4, 5], 3)
    finally:
        gate.release.set()
        sched.close()


def test_cancel_during_prefill_closes_stream(params):
    """cancel() racing the (unlocked) prefill dispatch: the stream still
    terminates with CLOSE and the lane comes back free."""
    sched = LmEngine(params, CFG, max_slots=1)
    gate = _GatedPrefill(sched._prefill)
    sched._prefill = gate
    try:
        q1, h1 = sched.submit([1, 2, 3], 8)
        assert gate.entered.wait(timeout=60)
        sched.cancel(h1)  # mid-admission: entry popped, not yet placed
        gate.release.set()
        assert _collect(q1) == []  # closed without tokens, reader released
        q2, _ = sched.submit([4, 5], 3)
        assert _collect(q2) == _serial(params, [4, 5], 3)
    finally:
        gate.release.set()
        sched.close()


def test_cancel_twice_during_prefill_is_idempotent(params):
    """Double-cancel racing the unlocked prefill dispatch (round-5 audit):
    the first cancel marks the handle _CANCELLED, the second must be a
    no-op — and the lane still comes back free once _admit observes the
    marker and closes the stream."""
    sched = LmEngine(params, CFG, max_slots=1)
    gate = _GatedPrefill(sched._prefill)
    sched._prefill = gate
    try:
        q1, h1 = sched.submit([1, 2, 3], 8)
        assert gate.entered.wait(timeout=60)
        sched.cancel(h1)  # entry popped, not yet placed: marks _CANCELLED
        sched.cancel(h1)  # second cancel sees the marker: no-op, no crash
        gate.release.set()
        assert _collect(q1) == []
        sched.cancel(h1)  # post-close cancel of the marked handle: no-op
        q2, _ = sched.submit([4, 5], 3)
        assert _collect(q2) == _serial(params, [4, 5], 3)
    finally:
        gate.release.set()
        sched.close()


def test_submit_after_close_returns_closed_stream(params):
    """submit() on a closed scheduler must hand back an already-closed
    queue (reader gets CLOSE immediately) instead of queueing work no
    scheduler thread will ever admit."""
    sched = LmEngine(params, CFG, max_slots=1)
    sched.close()
    q, handle = sched.submit([1, 2, 3], 4)
    assert handle is None
    assert q.get(timeout=10) is LmEngine.CLOSE
    sched.cancel(handle)  # cancel of a rejected submit: no-op


def test_failing_prefill_does_not_strand_reader(params):
    """If the admission dispatch itself dies (device OOM / XLA failure on
    a cold compile), the popped entry's reader must still get CLOSE — it
    is in neither _pending nor a slot when the crash handler runs."""
    sched = LmEngine(params, CFG, max_slots=1)

    def exploding_prefill(*a, **kw):
        raise RuntimeError("XLA compile failed")

    sched._prefill = exploding_prefill
    try:
        q, _ = sched.submit([1, 2, 3], 4)
        assert _collect(q) == []  # stream closed, no tokens, no hang
    finally:
        sched.close()


def test_eos_stops_stream(params):
    """An eos_id token terminates the stream (still yielded) and frees
    the lane."""
    # find a token the model actually emits early for this prompt
    serial = _serial(params, [1, 2, 3], 4)
    eos = serial[1]
    sched = LmEngine(params, CFG, max_slots=1, eos_id=eos)
    try:
        q, _ = sched.submit([1, 2, 3], 10)
        got = _collect(q)
        assert got == serial[: serial.index(eos) + 1]
    finally:
        sched.close()


def test_batched_runner_stream(params):
    runner = BatchedLmRunner(params, CFG, max_slots=2)
    try:
        toks = list(runner.stream([3, 1], 5))
        assert toks == _serial(params, [3, 1], 5)
        # abandoning a stream mid-flight must not wedge the lane
        gen = runner.stream([2, 2], 20)
        next(gen)
        gen.close()
        toks = list(runner.stream([3, 1], 5))
        assert toks == _serial(params, [3, 1], 5)
    finally:
        runner.scheduler.close()


def test_grpc_batched_model_concurrent(params):
    """lm_streaming_batched over real gRPC: concurrent streams produce the
    same tokens as the serial lm_streaming model (same float weights —
    the batched model serves the shared float runner; int8 lives on as
    lm_streaming_int8)."""
    import client_tpu.grpc as grpcclient
    from client_tpu.serve import Server
    from client_tpu.serve.models import language_models

    with Server(
        models=language_models(), grpc_port=0, with_default_models=False
    ) as server:
        def run_stream(model, prompt, n):
            results = queue.Queue()
            client = grpcclient.InferenceServerClient(server.grpc_address)
            client.start_stream(
                callback=lambda result, error: results.put((result, error))
            )
            t_in = grpcclient.InferInput("TOKENS", [len(prompt)], "INT32")
            t_in.set_data_from_numpy(np.asarray(prompt, dtype=np.int32))
            m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
            m_in.set_data_from_numpy(np.array([n], dtype=np.int32))
            client.async_stream_infer(
                model, [t_in, m_in], enable_empty_final_response=True
            )
            toks = []
            while True:
                r, e = results.get(timeout=120)
                assert e is None, e
                if r.get_response().parameters[
                    "triton_final_response"
                ].bool_param:
                    break
                toks.append(int(r.as_numpy("TOKEN")[0]))
            client.stop_stream()
            client.close()
            return toks

        prompts = [[1, 2, 3], [9, 9], [4, 5, 6, 7]]
        expected = [run_stream("lm_streaming", p, 5) for p in prompts]

        got = [None] * len(prompts)
        threads = [
            threading.Thread(
                target=lambda i=i, p=p: got.__setitem__(
                    i, run_stream("lm_streaming_batched", p, 5)
                )
            )
            for i, p in enumerate(prompts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert got == expected
