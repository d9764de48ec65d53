"""Dynamic micro-batching: concurrent requests fuse into one padded forward.

The server-side analog of the batching the reference's model configs opt into
via ``dynamic_batching`` (normalized by model_parser.h:59-193); here it is a
first-class engine feature (client_tpu/serve/dynamic_batcher.py).
"""

import threading

import numpy as np
import pytest

from client_tpu.serve.dynamic_batcher import _bucket, _buckets_up_to, batchable_request
from client_tpu.serve.model_runtime import InferenceEngine, Model, TensorSpec
from client_tpu.utils import to_wire_bytes


def _echo_model(record, **kwargs):
    """Model that doubles its input and records every executed batch size."""

    def fn(inputs, params, ctx):
        record.append(int(inputs["IN"].shape[0]))
        return {"OUT": inputs["IN"] * 2.0}

    defaults = dict(
        max_batch_size=8,
        dynamic_batching=True,
        max_queue_delay_us=20000,
    )
    defaults.update(kwargs)
    return Model(
        "echo2x",
        inputs=[TensorSpec("IN", "FP32", [-1, 4])],
        outputs=[TensorSpec("OUT", "FP32", [-1, 4])],
        fn=fn,
        **defaults,
    )


def _request(arr, shm_output=None):
    raw = to_wire_bytes(arr, "FP32")
    req = {
        "id": "",
        "parameters": {},
        "inputs": [
            {
                "name": "IN",
                "datatype": "FP32",
                "shape": list(arr.shape),
                "parameters": {"binary_data_size": len(raw)},
            }
        ],
        "outputs": [{"name": "OUT", "parameters": {"binary_data": True}}],
    }
    if shm_output:
        req["outputs"][0]["parameters"] = {
            "shared_memory_region": shm_output,
            "shared_memory_byte_size": arr.nbytes,
        }
    return req, raw


def test_bucket_shapes():
    assert [_bucket(n, 64) for n in (1, 2, 3, 5, 7, 9, 13, 20, 40, 50)] == [
        1, 2, 3, 6, 8, 12, 16, 24, 48, 64,
    ]
    assert _bucket(100, 64) == 64
    buckets = _buckets_up_to(64)
    assert buckets == [1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64]
    # every _bucket output is a warmed bucket
    for n in range(1, 65):
        assert _bucket(n, 64) in buckets


def test_concurrent_requests_fuse_and_split_correctly():
    record = []
    engine = InferenceEngine(models=[_echo_model(record)])
    n_threads = 8
    arrays = [
        np.full((1, 4), float(i), dtype=np.float32) for i in range(n_threads)
    ]
    results = [None] * n_threads
    barrier = threading.Barrier(n_threads)

    def run(i):
        req, raw = _request(arrays[i])
        barrier.wait()
        response, blobs = engine.execute("echo2x", "", req, raw)
        results[i] = np.frombuffer(blobs[0], dtype=np.float32).reshape(1, 4)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(n_threads):
        np.testing.assert_array_equal(results[i], arrays[i] * 2.0)
    # fewer executions than requests proves fusion happened
    assert sum(record) >= n_threads  # padded rows included
    assert len(record) < n_threads
    # every executed batch size is a warmable bucket (padding applied)
    for b in record:
        assert b in _buckets_up_to(8)
    stats = engine.statistics("echo2x")[0]["inference_stats"]
    assert stats["success"]["count"] == n_threads
    engine.close()


def test_batched_model_response_parameters_replicate():
    """A batched model's reserved "__parameters__" result key is
    batch-wide: the split replicates it to every request instead of
    row-slicing the dict (which raised and 500'd the whole group)."""
    record = []

    def fn(inputs, params, ctx):
        record.append(int(inputs["IN"].shape[0]))
        return {
            "OUT": inputs["IN"] * 2.0,
            "__parameters__": {"engine_pass": 1, "batched": True},
        }

    model = Model(
        "echo2x",
        inputs=[TensorSpec("IN", "FP32", [-1, 4])],
        outputs=[TensorSpec("OUT", "FP32", [-1, 4])],
        fn=fn,
        max_batch_size=8,
        dynamic_batching=True,
        max_queue_delay_us=20000,
    )
    engine = InferenceEngine(models=[model])
    n_threads = 4
    arrays = [
        np.full((1, 4), float(i), dtype=np.float32) for i in range(n_threads)
    ]
    responses = [None] * n_threads
    blobs_out = [None] * n_threads
    barrier = threading.Barrier(n_threads)

    def run(i):
        req, raw = _request(arrays[i])
        barrier.wait()
        responses[i], blobs_out[i] = engine.execute("echo2x", "", req, raw)

    threads = [
        threading.Thread(target=run, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(n_threads):
        got = np.frombuffer(blobs_out[i][0], dtype=np.float32).reshape(1, 4)
        np.testing.assert_array_equal(got, arrays[i] * 2.0)
        assert responses[i]["parameters"] == {
            "engine_pass": 1, "batched": True,
        }
        # the reserved key never leaks as an output tensor
        assert [o["name"] for o in responses[i]["outputs"]] == ["OUT"]
    engine.close()


def test_fused_group_fn_drops_response_parameters():
    """fused_batching traces the model fn, so a "__parameters__" dict
    would be a trace-time constant; the fused splitter drops it instead
    of crashing the whole group in jnp.split."""
    import jax.numpy as jnp

    from client_tpu.serve.dynamic_batcher import _fused_group_fn

    def fn(inputs, params, ctx):
        return {"OUT": inputs["IN"] * 2.0, "__parameters__": {"n": 1}}

    fused = _fused_group_fn(fn)
    parts = {"IN": (jnp.ones((1, 4)), jnp.full((1, 4), 2.0))}
    out = fused(parts)
    assert set(out) == {"OUT"}
    np.testing.assert_array_equal(np.asarray(out["OUT"][0]), np.full((1, 4), 2.0))
    np.testing.assert_array_equal(np.asarray(out["OUT"][1]), np.full((1, 4), 4.0))


def test_multi_row_requests_batch():
    record = []
    engine = InferenceEngine(models=[_echo_model(record)])
    arr = np.arange(12, dtype=np.float32).reshape(3, 4)
    req, raw = _request(arr)
    response, blobs = engine.execute("echo2x", "", req, raw)
    out = np.frombuffer(blobs[0], dtype=np.float32).reshape(3, 4)
    np.testing.assert_array_equal(out, arr * 2.0)
    assert response["outputs"][0]["shape"] == [3, 4]
    engine.close()


def test_oversize_request_takes_direct_path():
    record = []
    engine = InferenceEngine(models=[_echo_model(record)])
    arr = np.zeros((9, 4), dtype=np.float32)  # > max_batch_size=8
    req, raw = _request(arr)
    response, blobs = engine.execute("echo2x", "", req, raw)
    assert np.frombuffer(blobs[0], dtype=np.float32).size == 36
    assert record == [9]  # executed unbatched, unpadded
    engine.close()


def test_shm_output_bypasses_batcher():
    model = _echo_model([])
    arr = np.zeros((1, 4), dtype=np.float32)
    req, _ = _request(arr, shm_output="region0")
    inputs = {"IN": arr}
    assert not batchable_request(model, inputs, {}, None, req)


def test_sequence_and_device_inputs_bypass_batcher():
    model = _echo_model([])
    arr = np.zeros((1, 4), dtype=np.float32)
    req, _ = _request(arr)
    assert not batchable_request(
        model, {"IN": arr}, {"sequence_id": 7}, None, req
    )

    class FakeDeviceArray:
        ndim = 2
        shape = (1, 4)

    assert not batchable_request(model, {"IN": FakeDeviceArray()}, {}, None, req)
    # plain numpy wire request IS batchable
    assert batchable_request(model, {"IN": arr}, {}, None, req)


def test_device_requests_fuse_on_device_with_shm_outputs():
    """TPU-shm requests (device-resident inputs, shm outputs) batch on the
    device path: one fused forward, outputs split as live device slices and
    written to regions without any D2H on the dispatch path."""
    from client_tpu.utils import tpu_shared_memory as tpushm

    record = []
    engine = InferenceEngine(
        models=[_echo_model(record, batch_device_inputs=True)]
    )
    n_threads = 4
    handles = []
    try:
        for i in range(n_threads):
            h_in = tpushm.create_shared_memory_region(f"dev_in{i}", 16)
            tpushm.set_shared_memory_region(
                h_in, [np.full((1, 4), float(i + 1), dtype=np.float32)]
            )
            h_out = tpushm.create_shared_memory_region(f"dev_out{i}", 16)
            engine.shm.register_tpu(
                f"dev_in{i}", tpushm.get_raw_handle(h_in), 0, 16
            )
            engine.shm.register_tpu(
                f"dev_out{i}", tpushm.get_raw_handle(h_out), 0, 16
            )
            handles.append((h_in, h_out))

        barrier = threading.Barrier(n_threads)
        errors = []

        def run(i):
            req = {
                "id": "",
                "parameters": {},
                "inputs": [
                    {
                        "name": "IN",
                        "datatype": "FP32",
                        "shape": [1, 4],
                        "parameters": {
                            "shared_memory_region": f"dev_in{i}",
                            "shared_memory_byte_size": 16,
                        },
                    }
                ],
                "outputs": [
                    {
                        "name": "OUT",
                        "parameters": {
                            "shared_memory_region": f"dev_out{i}",
                            "shared_memory_byte_size": 16,
                        },
                    }
                ],
            }
            barrier.wait()
            try:
                engine.execute("echo2x", "", req, b"")
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=run, args=(i,)) for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        # fewer executions than requests proves device-side fusion happened
        assert len(record) < n_threads
        for i, (h_in, h_out) in enumerate(handles):
            got = tpushm.get_contents_as_numpy(h_out, np.float32, [1, 4])
            np.testing.assert_array_equal(
                got, np.full((1, 4), 2.0 * (i + 1), dtype=np.float32)
            )
    finally:
        engine.close()
        for h_in, h_out in handles:
            tpushm.destroy_shared_memory_region(h_in)
            tpushm.destroy_shared_memory_region(h_out)


def test_fused_device_groups_one_dispatch_correct_splits():
    """fused_batching: a device group runs concat+forward+split inside ONE
    jitted call — per-request outputs come back already split, values exact."""
    from client_tpu.serve.dynamic_batcher import ModelBatcher
    import jax

    record = []
    model = _echo_model(
        record, batch_device_inputs=True, fused_batching=True
    )

    class _Stats:
        def record_batched(self, **kw):
            record.append(("batched", kw["rows"]))

    batcher = ModelBatcher(model, _Stats(), max_queue_delay_s=0.05)
    try:
        results = [None] * 4
        def run(i):
            x = jax.device_put(
                np.full((1, 4), float(i + 1), dtype=np.float32)
            )
            results[i] = batcher.submit({"IN": x})
        threads = [
            threading.Thread(target=run, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, res in enumerate(results):
            np.testing.assert_array_equal(
                np.asarray(res["OUT"]),
                np.full((1, 4), 2.0 * (i + 1), dtype=np.float32),
            )
        rows = [r[1] for r in record if isinstance(r, tuple)]
        assert sum(rows) == 4 and len(rows) < 4  # fused, not per-request
        # mixed row counts retrace but stay correct
        a = jax.device_put(np.ones((2, 4), dtype=np.float32))
        out = batcher.submit({"IN": a})
        np.testing.assert_array_equal(
            np.asarray(out["OUT"]), 2.0 * np.ones((2, 4), dtype=np.float32)
        )
    finally:
        batcher.close()


def test_device_request_batchable_and_mixed_rejected():
    import jax

    model = _echo_model([], batch_device_inputs=True)
    req_shm_out = {
        "outputs": [
            {
                "name": "OUT",
                "parameters": {
                    "shared_memory_region": "r",
                    "shared_memory_byte_size": 16,
                },
            }
        ]
    }
    dev = jax.device_put(np.zeros((1, 4), dtype=np.float32))
    host = np.zeros((1, 4), dtype=np.float32)
    # all-device inputs batch, even with shm outputs
    assert batchable_request(model, {"IN": dev}, {}, None, req_shm_out)
    # ... but only when the model opts in: by default device-resident
    # requests dispatch directly (zero-copy, no assemble/split overhead)
    assert not batchable_request(
        _echo_model([]), {"IN": dev}, {}, None, req_shm_out
    )
    # host inputs with shm outputs keep the direct path
    assert not batchable_request(model, {"IN": host}, {}, None, req_shm_out)
    # mixed host/device inputs keep the direct path
    model2 = Model(
        "echo2",
        inputs=[TensorSpec("A", "FP32", [-1, 4]), TensorSpec("B", "FP32", [-1, 4])],
        outputs=[TensorSpec("OUT", "FP32", [-1, 4])],
        fn=lambda i, p, c: {"OUT": i["A"]},
        max_batch_size=8,
        dynamic_batching=True,
    )
    assert not batchable_request(
        model2, {"A": dev, "B": host}, {}, None, {"outputs": []}
    )


def test_batcher_error_propagates_per_request():
    def fn(inputs, params, ctx):
        raise ValueError("boom")

    model = Model(
        "boom",
        inputs=[TensorSpec("IN", "FP32", [-1, 4])],
        outputs=[TensorSpec("OUT", "FP32", [-1, 4])],
        fn=fn,
        max_batch_size=8,
        dynamic_batching=True,
    )
    engine = InferenceEngine(models=[model])
    req, raw = _request(np.zeros((1, 4), dtype=np.float32))
    from client_tpu.utils import InferenceServerException

    with pytest.raises(InferenceServerException, match="boom"):
        engine.execute("boom", "", req, raw)
    stats = engine.statistics("boom")[0]["inference_stats"]
    assert stats["fail"]["count"] == 1
    engine.close()


def test_device_failure_after_ack_reaches_log_and_stats(caplog):
    """TPU-shm acks at dispatch, so a device failure after the ack is seen
    only by the completion observer: it must log the error and count it
    in the model's failure statistics, for the failing watch alone, and
    still run every completion callback."""
    from client_tpu.serve._completion import CompletionObserver
    from client_tpu.serve.model_runtime import ModelStats

    class Result:
        def __init__(self, error=None):
            self.error = error

        def block_until_ready(self):
            if self.error is not None:
                raise self.error
            return self

    stats_ok, stats_bad = ModelStats(), ModelStats()
    done = []
    both = threading.Event()

    def finish(tag):
        done.append(tag)
        if len(done) == 2:
            both.set()

    obs = CompletionObserver(name="batcher-m-watch")
    with caplog.at_level("ERROR", logger="client_tpu.serve._completion"):
        obs.watch(Result(), lambda *instant: finish("ok"),
                  on_error=lambda exc: stats_ok.record_device_failure())
        obs.watch(Result(RuntimeError("HBM fell over")),
                  lambda *instant: finish("bad"),
                  on_error=lambda exc: stats_bad.record_device_failure(3))
        assert both.wait(timeout=10)
        obs.close()
    assert sorted(done) == ["bad", "ok"]
    assert stats_ok.fail_count == 0
    assert stats_bad.fail_count == 3
    assert "batcher-m-watch" in caplog.text
    assert "HBM fell over" in caplog.text


def test_unload_closes_batcher_and_reload_works():
    record = []
    engine = InferenceEngine(models=[_echo_model(record)])
    arr = np.ones((1, 4), dtype=np.float32)
    req, raw = _request(arr)
    engine.execute("echo2x", "", req, raw)
    engine.unload_model("echo2x")
    engine.load_model("echo2x")
    response, blobs = engine.execute("echo2x", "", req, raw)
    np.testing.assert_array_equal(
        np.frombuffer(blobs[0], dtype=np.float32).reshape(1, 4), arr * 2.0
    )
    engine.close()


def test_request_parameters_bypass_batcher():
    model = _echo_model([])
    arr = np.zeros((1, 4), dtype=np.float32)
    req, _ = _request(arr)
    # a custom parameter must reach model.fn, so it takes the direct path
    assert not batchable_request(model, {"IN": arr}, {"top_k": 5}, None, req)
    assert batchable_request(
        model, {"IN": arr}, {"binary_data_output": True}, None, req
    )


def test_replacing_model_replaces_batcher():
    record_v1, record_v2 = [], []
    engine = InferenceEngine(models=[_echo_model(record_v1)])
    arr = np.ones((1, 4), dtype=np.float32)
    req, raw = _request(arr)
    engine.execute("echo2x", "", req, raw)
    assert record_v1  # v1 batcher served it

    v2 = _echo_model(record_v2)
    engine.add_model(v2)
    engine.execute("echo2x", "", req, raw)
    assert record_v2  # new batcher bound to the new model fn
    assert len(record_v1) == 1
    engine.close()


def test_warmup_compiles_all_buckets():
    record = []
    engine = InferenceEngine(models=[_echo_model(record, warmup=True)])
    assert sorted(set(record)) == _buckets_up_to(8)
    engine.close()


def test_dynamic_batching_in_model_config():
    model = _echo_model([])
    cfg = model.config()
    assert cfg["dynamic_batching"]["max_queue_delay_microseconds"] == 20000
