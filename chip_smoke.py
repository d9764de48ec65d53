#!/usr/bin/env python3
"""Chip smoke: the served path, once, on the TPU.

    python chip_smoke.py        (no options; `make smoke`)

The quickest proof that the system still starts on the chip.  One process —
a chip belongs to one process at a time — drives the main path through the
entry points a user would call, at the full width of the one real
architecture the repo serves (resnet50 at 3x224x224, 1000 classes), plus the
LM engine every later LM cell will sit on:

  build    `make native` from the tracked sources (the shm transports)
  device   names jax / jaxlib / libtpu and the device; refuses anything but
           a TPU before doing any work
  vision   in-process Server <- gRPC on the socket <- TPU-shm regions ->
           fused dynamic batcher -> device; every output read back and
           matched against the classifier called directly; load workers in
           other processes (which must not open the chip); the same rows
           over system shm and over HTTP wire tensors
  perf     client_tpu.perf's main() in this process against that server
  lm       ModelStreamInfer streams through LmEngine (paged KV with donated
           pools, chunked prefill, prefix cache), the int8 engine, one
           speculative stream, one priority preemption; every token matched
           against a teacher-forced reference forward
  kernels  every int8_matmul shape the engine issues and the flash-attention
           forward, compiled (interpret=False) with the Pallas call present

Any phase that raises makes the exit code non-zero; there is no catch.  Times
printed here are facts about set-up against steady state, not benchmark
numbers.  The last line of standard output is one JSON object,
{"ok": true, "device": {...}}, printed only when every phase passed.

tests/test_chip_smoke.py runs the same phase functions on the CPU at tiny
size, so the control flow is proven before chip time is spent.
"""

import concurrent.futures
import contextlib
import faulthandler
import functools
import importlib.metadata
import json
import os
import queue
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
N_CLASSES = 1000
# generated tokens are accepted when the reference forward scores them
# within this many logit units of its own argmax: bf16 logits carry 8 bits,
# so batch shape and chunk plan move near-ties by a few 1e-2 (a wrong KV
# block moves them by ~1, measured as the margin of a random token)
LM_MARGIN = 0.125


# -- accounting ---------------------------------------------------------------

class Compiles:
    """What JAX compiled, counted from its own monitoring events: one
    backend-compile event per executable built or fetched, and the
    persistent cache's hit and miss events."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.executables = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.executables += 1
                self.seconds += seconds

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def snapshot(self):
        with self._lock:
            return (self.executables, self.seconds, self.cache_hits,
                    self.cache_misses)


@contextlib.contextmanager
def phase(name, compiles, summary):
    """Time one phase and count what it compiled.  The body fills
    ``facts``; ``facts["first_s"]`` is the wall time of the phase's first
    call (set-up: compile included), the rest is steady state."""
    print(f"\n--- {name}", flush=True)
    before, t0 = compiles.snapshot(), time.monotonic()
    facts = {}
    yield facts
    wall = time.monotonic() - t0
    after = compiles.snapshot()
    first = facts.pop("first_s", wall)
    row = {
        "wall_s": round(wall, 1),
        "first_call_s": round(first, 1),
        "rest_s": round(wall - first, 1),
        "executables": after[0] - before[0],
        "compile_s": round(after[1] - before[1], 1),
        "cache_hits": after[2] - before[2],
        "cache_misses": after[3] - before[3],
    }
    summary[name] = row
    for key, value in facts.items():
        print(f"    {key}: {value}")
    print(f"PASS {name}: {json.dumps(row)}", flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(what)


# -- build + device -----------------------------------------------------------

def build_native():
    """The two shm transport libraries are git-ignored build products:
    build them from the tracked sources, or fail with the compiler's
    output."""
    done = subprocess.run(
        ["make", "-C", REPO, "native"], capture_output=True, text=True
    )
    if done.returncode != 0:
        raise RuntimeError(
            f"`make native` failed ({done.returncode}):\n"
            f"{done.stdout}{done.stderr}"
        )


def name_device():
    """Print what is installed and what JAX found; the device record."""
    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {libtpu}  python {sys.version.split()[0]}")
    print(f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '<unset>')}")
    devices = jax.devices()
    first = devices[0]
    print(f"platform {first.platform}  device_kind {first.device_kind!r}  "
          f"devices {len(devices)}  (the served path uses device 0 only)",
          flush=True)
    return {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": len(devices),
    }


# -- vision: gRPC + TPU-shm + fused batcher -----------------------------------

def _scores_match(got, want, what):
    """Finite, the expected shape, and equal within bf16 tolerance (two
    ulps of the largest score): the model computes in bf16 end to end, and
    the batch a row is fused into may change the rounding of a layer."""
    check(got.shape == want.shape, f"{what}: shape {got.shape}")
    check(np.isfinite(got).all(), f"{what}: non-finite scores")
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    check(err <= 0.01 * scale, f"{what}: max |diff| {err:.4g} against "
                               f"max |score| {scale:.4g}")
    return err / scale


def _model_stats(client, model_name):
    stats = client.get_inference_statistics(model_name).model_stats[0]
    return {
        "success": stats.inference_stats.success.count,
        "fail": stats.inference_stats.fail.count,
        "executions": stats.execution_count,
        "rows": stats.inference_count,
        "compute_infer_ns": stats.inference_stats.compute_infer.ns,
    }


def _batcher(server, model_name):
    engine = server.engine
    return engine._batcher_for(engine.get_model(model_name))


def _blocked_step_s(server, model_name, part, repeats=5):
    """The batcher's own fused program on one request's rows, dispatched
    and blocked on: (the dispatch call, the wait for its result), the
    medians of ``repeats``, in seconds."""
    import jax

    fused = _batcher(server, model_name)._fused_jit()
    parts = {name: (rows,) for name, rows in part.items()}
    jax.block_until_ready(fused(parts))
    calls, waits = [], []
    for _ in range(repeats):
        t0 = time.monotonic()
        out = fused(parts)
        t1 = time.monotonic()
        jax.block_until_ready(out)
        calls.append(t1 - t0)
        waits.append(time.monotonic() - t1)
    return sorted(calls)[repeats // 2], sorted(waits)[repeats // 2]


def _device_time_checks(server, control, model_name, platform, facts,
                        send_one, part, steps=8):
    """What the program calls device time, against the device.  The
    phase's traffic has just run at a concurrency at which no step waits
    behind another: ``/v2/debug/prof`` must read the model an ``mfu_pct``
    in (0, 100].  Then ``steps`` requests one after the other, each a step
    of its own on the same rows: the statistics endpoint's
    ``compute_infer_ns`` a step must agree within a tenth with that step
    dispatched here and blocked on.  Device time runs from the return of
    the dispatch call (the batcher's ``t_in``; the call may compile) to
    completion, so the blocked step is timed from there too, and the call
    is reported beside it: of a step this small it is a tenth and more.
    Off the TPU there is no MFU, and the host's clock decides nothing
    about a device: the path runs, the bounds are the chip's."""
    with urllib.request.urlopen(
            f"http://{server.http_address}/v2/debug/prof?window=0") as r:
        report = json.load(r)
    row = next(e for e in report["engines"]
               if e["engine"] == "serve")["models"][model_name]
    check(row["device_s"] > 0, f"no device time for {model_name}: {row}")
    before = _model_stats(control, model_name)
    for _ in range(steps):
        send_one()
    # an execution's time is added when the observer sees it complete,
    # just before the batcher counts it out of flight
    _wait_for(lambda: _batcher(server, model_name)._inflight == 0,
              "the observer to see the last step complete")
    after = _model_stats(control, model_name)
    check(after["executions"] - before["executions"] == steps,
          f"{steps} requests in turn were not {steps} steps")
    reported_s = ((after["compute_infer_ns"] - before["compute_infer_ns"])
                  / steps / 1e9)
    call_s, blocked_s = _blocked_step_s(server, model_name, part)
    facts["device_time"] = {
        "mfu_pct": row.get("mfu_pct"), "device_s": row["device_s"],
        "compute_infer_ms_a_step": round(1e3 * reported_s, 4),
        "blocked_step_ms": round(1e3 * blocked_s, 4),
        "dispatch_call_ms": round(1e3 * call_s, 4),
    }
    check(reported_s > 0 and blocked_s > 0, facts["device_time"])
    if platform == "tpu":
        check(0 < row.get("mfu_pct", 0) <= 100,
              f"mfu_pct of {model_name} outside (0, 100]: {row}")
        check(abs(reported_s / blocked_s - 1) <= 0.10,
              f"compute_infer_ns a step against a blocked step: "
              f"{facts['device_time']}")


def vision_phase(server, model_name, reference, image_size, platform, facts,
                 requests=32, rows=8, concurrency=4, mp_window_s=2.0):
    """``requests`` requests of ``rows`` rows at ``concurrency`` over gRPC
    with inputs and outputs in TPU-shm regions, every output read back to
    the host and compared with ``reference(rows)``; then the same rows from
    load workers in other processes, over system shm, and over HTTP."""
    import jax

    import client_tpu.grpc as grpcclient
    from client_tpu.perf.procpool import run_completion_multiproc
    from client_tpu.utils import tpu_shared_memory as tpushm

    shape = [rows, 3, image_size, image_size]
    in_bytes = int(np.prod(shape)) * 4
    out_bytes = rows * N_CLASSES * 4
    url = server.grpc_address

    def rows_of(i):
        rng = np.random.default_rng(i)
        return rng.standard_normal(shape, dtype=np.float32)

    def request_io(in_region, out_region):
        inp = grpcclient.InferInput("INPUT0", shape, "FP32")
        inp.set_shared_memory(in_region, in_bytes)
        out = grpcclient.InferRequestedOutput("OUTPUT0")
        out.set_shared_memory(out_region, out_bytes)
        return [inp], [out]

    def worker(w):
        (in_name, in_h), (out_name, out_h) = regions[w]
        got = {}
        with grpcclient.InferenceServerClient(url) as client:
            for i in range(w, requests, concurrency):
                tpushm.set_shared_memory_region(in_h, [rows_of(i)])
                inputs, outputs = request_io(in_name, out_name)
                client.infer(model_name, inputs, outputs=outputs)
                for slot in (tpushm.get_contents_as_jax(in_h),
                             tpushm.get_contents_as_jax(out_h)):
                    check(isinstance(slot, jax.Array), f"slot {type(slot)}")
                    check({d.platform for d in slot.devices()} == {platform},
                          f"slot on {slot.devices()}, want {platform}")
                # the read-back is the completion: the ack came at dispatch,
                # and a device-side failure after it surfaces only here
                got[i] = tpushm.get_contents_as_numpy(
                    out_h, "FP32", [rows, N_CLASSES]
                ).copy()
                done_at.append(time.monotonic())
        return got

    with grpcclient.InferenceServerClient(url) as control:
        before = _model_stats(control, model_name)
        regions = []  # per worker: ((in name, handle), (out name, handle))
        try:
            for w in range(concurrency):
                pair = []
                for kind, nbytes in (("in", in_bytes), ("out", out_bytes)):
                    name = f"smoke_{kind}_{w}"
                    handle = tpushm.create_shared_memory_region(name, nbytes)
                    pair.append((name, handle))
                    control.register_tpu_shared_memory(
                        name, tpushm.get_raw_handle(handle), 0, nbytes
                    )
                regions.append(pair)

            t_start, done_at, got = time.monotonic(), [], {}
            with concurrent.futures.ThreadPoolExecutor(concurrency) as pool:
                for part in pool.map(worker, range(concurrency)):
                    got.update(part)
            facts["first_s"] = min(done_at) - t_start
            check(len(got) == requests, f"{len(got)} of {requests} answered")
            worst = max(
                _scores_match(got[i], reference(rows_of(i)),
                              f"tpu-shm request {i}")
                for i in range(requests)
            )
            facts["tpu_shm"] = (
                f"{requests} x {rows} rows at concurrency {concurrency}: "
                f"worst |diff|/|score| {worst:.4f}"
            )

            after = _model_stats(control, model_name)
            delta = {k: after[k] - before[k] for k in after}
            facts["server_statistics"] = delta
            check(delta["success"] == requests and delta["fail"] == 0, delta)
            check(delta["rows"] == requests * rows, delta)
            # fewer executions than requests: the fused batcher path
            # (dynamic_batcher._fused_group_fn) served several at once
            check(delta["executions"] < requests, f"no fused batch: {delta}")

            (in_name, in_h), (out_name, out_h) = regions[0]

            def send_one():
                inputs, outputs = request_io(in_name, out_name)
                control.infer(model_name, inputs, outputs=outputs)
                tpushm.get_contents_as_numpy(out_h, "FP32", [rows, N_CLASSES])

            _device_time_checks(
                server, control, model_name, platform, facts, send_one,
                {"INPUT0": tpushm.get_contents_as_jax(in_h)})

            # load workers in OTHER processes reference worker 0's regions
            # by name; they must never open the chip this process holds
            (in_name, _), (out_name, out_h) = regions[0]
            load = run_completion_multiproc(
                url, model_name, processes=2, concurrency=concurrency,
                window_s=mp_window_s, warmup_s=0.5,
                spec={
                    "mode": "shm_ref", "num_streams": 1,
                    "steps_per_stream": [1],
                    "input_specs": {
                        (0, 0): [("INPUT0", shape, "FP32", in_name, in_bytes)],
                    },
                    "output_specs": [("OUTPUT0", out_name, out_bytes)],
                },
                sync_outputs=lambda: tpushm.get_contents_as_numpy(
                    out_h, "FP32", [rows, N_CLASSES]
                ),
            )
            facts["load_workers"] = (
                f"{load.processes} processes, {load.completed_requests} "
                f"requests, {load.error_count} errors, JAX backends opened: "
                f"{load.worker_backends}"
            )
            check(load.completed_requests > 0, "load workers sent nothing")
            check(load.error_count == 0, "load worker errors")
            check(not any(load.worker_backends),
                  f"a load worker opened {load.worker_backends}")
        finally:
            control.unregister_tpu_shared_memory()
            for pair in regions:
                for _, handle in pair:
                    tpushm.destroy_shared_memory_region(handle)

        over_sys, over_http = _other_transports(
            control, server.http_address, model_name, rows_of(0), request_io
        )
    facts["system_shm"] = "|diff|/|score| %.4f" % _scores_match(
        over_sys, got[0], "system shm")
    facts["http_wire"] = "|diff|/|score| %.4f" % _scores_match(
        over_http, got[0], "http wire")


def _other_transports(control, http_address, model_name, x, request_io):
    """The rows ``x`` once through system-shm regions (over gRPC) and once
    as plain wire tensors over HTTP: (scores, scores)."""
    import client_tpu.http as httpclient
    from client_tpu.utils import shared_memory as sysshm

    out_bytes = x.shape[0] * N_CLASSES * 4
    key_in, key_out = "/chip_smoke_in", "/chip_smoke_out"
    sys_in = sysshm.create_shared_memory_region("sys_in", key_in, x.nbytes)
    sys_out = sysshm.create_shared_memory_region("sys_out", key_out, out_bytes)
    try:
        sysshm.set_shared_memory_region(sys_in, [x])
        control.register_system_shared_memory("sys_in", key_in, x.nbytes)
        control.register_system_shared_memory("sys_out", key_out, out_bytes)
        inputs, outputs = request_io("sys_in", "sys_out")
        control.infer(model_name, inputs, outputs=outputs)
        over_sys = sysshm.get_contents_as_numpy(
            sys_out, np.float32, [x.shape[0], N_CLASSES]
        ).copy()
    finally:
        control.unregister_system_shared_memory()
        sysshm.destroy_shared_memory_region(sys_in)
        sysshm.destroy_shared_memory_region(sys_out)
    with httpclient.InferenceServerClient(http_address) as http:
        inp = httpclient.InferInput("INPUT0", list(x.shape), "FP32")
        inp.set_data_from_numpy(x, binary_data=True)
        over_http = http.infer(model_name, [inp]).as_numpy("OUTPUT0")
    return over_sys, over_http


# -- perf: the harness's own main() -------------------------------------------

def perf_phase(server, model_name, device, facts, rows=8, concurrency=4,
               window_ms=2000):
    """The perf CLI's ``main()`` in THIS process against the live server:
    a child ``python -m client_tpu.perf --shared-memory tpu`` could not
    open the chip its parent holds."""
    from client_tpu.perf.__main__ import main as perf_main

    with tempfile.TemporaryDirectory() as tmp:
        report_path = os.path.join(tmp, "perf.json")
        rc = perf_main([
            "-m", model_name, "-u", server.grpc_address, "-i", "grpc",
            "--shared-memory", "tpu", "-b", str(rows),
            "--concurrency-range", str(concurrency),
            "--measurement-interval", str(window_ms), "--max-trials", "3",
            "--json-export", report_path,
        ])
        check(rc == 0, f"perf main() returned {rc}")
        with open(report_path) as f:
            report = json.load(f)
    level = report["results"][0]
    facts["report"] = {
        "device": report["device"],
        "infer_per_sec": round(level["throughput_infer_per_sec"], 1),
        "completed": level["completed_requests"],
        "errors": level["error_count"],
    }
    check(report["device"] == device, f"report names {report['device']}")
    check(level["throughput_infer_per_sec"] > 0, "zero throughput")
    check(level["error_count"] == 0, f"{level['error_count']} errors")


# -- lm: streams through LmEngine ---------------------------------------------

def _stream(url, model_name, prompt, max_tokens, timeout_s=900):
    """One ModelStreamInfer request: (token ids, seconds to first token)."""
    import client_tpu.grpc as grpcclient

    results = queue.Queue()
    tokens, first_s = [], None
    t0 = time.monotonic()
    with grpcclient.InferenceServerClient(url) as client:
        client.start_stream(
            callback=lambda result, error: results.put((result, error))
        )
        t_in = grpcclient.InferInput("TOKENS", [len(prompt)], "INT32")
        t_in.set_data_from_numpy(np.asarray(prompt, np.int32))
        m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        m_in.set_data_from_numpy(np.array([max_tokens], np.int32))
        client.async_stream_infer(
            model_name, [t_in, m_in], enable_empty_final_response=True
        )
        while True:
            result, error = results.get(timeout=timeout_s)
            if error is not None:
                raise RuntimeError(f"{model_name}: stream error: {error}")
            final = result.get_response().parameters["triton_final_response"]
            if final.bool_param:
                break
            tokens.append(int(result.as_numpy("TOKEN")[0]))
            if first_s is None:
                first_s = time.monotonic() - t0
        client.stop_stream()
    return tokens, first_s


def _dequantized(params, dtype):
    """Quantized {"q", "s"} leaves back to dense *dtype* weights: the plain
    reference the int8 path is compared against, ``x @ (q * s)``."""
    from client_tpu.ops.quant import is_quantized

    if is_quantized(params):
        return (params["q"].astype(np.float32) * params["s"]).astype(dtype)
    if isinstance(params, dict):
        return {k: _dequantized(v, dtype) for k, v in params.items()}
    if isinstance(params, list):
        return [_dequantized(v, dtype) for v in params]
    return params


@functools.lru_cache(maxsize=None)
def _jitted_forward(cfg):
    """One full-sequence forward per config, shared by every reference."""
    import jax

    from client_tpu.serve.models import transformer as tfm

    return jax.jit(lambda params, tokens: tfm.forward(params, tokens, cfg))


class LmReference:
    """Teacher-forced check of a generated stream against the plain
    full-sequence forward (``transformer.forward``, no KV cache, no
    paging): each generated token must score within LM_MARGIN of the
    reference's own argmax at its position."""

    def __init__(self, params, cfg):
        self.cfg = cfg
        self._params = _dequantized(params, cfg.jdtype)
        self._forward = _jitted_forward(cfg)

    def check(self, what, prompt, tokens, max_tokens, eos_id=None):
        """The stream ran its budget (or stopped at *eos_id*), stayed in
        the vocab, and tracks the reference; the worst margin."""
        check(tokens, f"{what}: no tokens")
        check(all(0 <= t < self.cfg.vocab_size for t in tokens),
              f"{what}: id outside the vocab")
        ended = len(tokens) == max_tokens or tokens[-1] == eos_id
        check(ended and eos_id not in tokens[:-1],
              f"{what}: {len(tokens)} tokens of {max_tokens}, no EOS")
        seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])
        row = np.zeros((1, self.cfg.max_seq), np.int32)
        row[0, :len(seq) - 1] = seq[:-1]  # the causal mask hides the padding
        logits = np.asarray(self._forward(self._params, row))[0]
        at = logits[len(prompt) - 1:len(seq) - 1]
        margin = at.max(-1) - at[np.arange(len(tokens)), tokens]
        check(margin.max() <= LM_MARGIN,
              f"{what}: token {int(margin.argmax())} scores "
              f"{margin.max():.3f} under the reference argmax")
        return float(margin.max())


def _wait_for(condition, what, timeout_s=30):
    deadline = time.monotonic() + timeout_s
    while not condition():
        check(time.monotonic() < deadline, f"timed out waiting for {what}")
        time.sleep(0.05)


def lm_phase(server, models, facts, prompt_lens=(40, 90, 150, 200),
             max_tokens=32, int8_tokens=16, spec_tokens=48):
    """Concurrent greedy streams on ``lm_streaming_batched``, a repeat
    that must hit the prefix cache, a clean pool and bounded executable
    counts afterwards; then one ``lm_streaming_int8`` stream, one
    speculative stream on ``lm_spec_smoke``, one priority preemption."""
    from client_tpu.serve.models.language import _EOS, encode_text

    url = server.grpc_address
    registry = server.engine.metrics
    for name in ("lm_streaming_batched", "lm_streaming_int8",
                 "lm_spec_smoke"):
        runner = models[name].runner
        engine = getattr(runner, "scheduler", None)
        facts[f"runner[{name}]"] = type(runner).__name__ + (
            f" -> {type(engine).__name__}" if engine is not None
            else " (serial generate, no engine)"
        )

    engine = models["lm_streaming_batched"].runner.scheduler
    reference = LmReference(engine.params, engine.cfg)
    rng = np.random.default_rng(0)
    prompts = [
        np.concatenate([[256], rng.integers(0, 256, size=n - 1)])
        .astype(np.int32) for n in prompt_lens
    ]

    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        cold = list(pool.map(
            lambda p: _stream(url, "lm_streaming_batched", p, max_tokens),
            prompts,
        ))
    facts["first_s"] = min(first for _, first in cold)
    margins = [
        reference.check(f"stream {i}", p, tokens, max_tokens, _EOS)
        for i, (p, (tokens, _)) in enumerate(zip(prompts, cold))
    ]
    facts["streams"] = (f"{len(prompts)} concurrent, prompts {prompt_lens}, "
                        f"{max_tokens} tokens each; worst reference margin "
                        f"{max(margins):.3f}")

    # the longest prompt again: its full blocks are in the prefix cache,
    # so prefill starts at the first miss (a different chunk plan, hence
    # "within the margin" against the cold stream, not "equal") — and a
    # third time, now the same program on the same inputs: equal
    hits0 = registry.get("ctpu_lm_prefix_hits_total") or 0
    warm, _ = _stream(url, "lm_streaming_batched", prompts[-1], max_tokens)
    again, _ = _stream(url, "lm_streaming_batched", prompts[-1], max_tokens)
    hits = (registry.get("ctpu_lm_prefix_hits_total") or 0) - hits0
    reference.check("prefix-hit stream", prompts[-1], warm, max_tokens, _EOS)
    check(hits > 0, "the repeated prompt moved no ctpu_lm_prefix_hits_total")
    check(again == warm, "two prefix-hit streams of one prompt differ")
    same = sum(a == b for a, b in zip(warm, cold[-1][0]))
    facts["prefix_cache"] = (
        f"{hits} blocks adopted over two repeats; repeats identical; "
        f"{same}/{len(warm)} ids equal to the cold stream "
        f"(engine {engine.prefix_stats()})"
    )

    # all streams closed: only the prefix cache may still hold blocks
    kv = engine.kv
    _wait_for(lambda: kv.used_blocks == engine.prefix_stats()["cached_blocks"],
              "lanes to return their blocks")
    refs = kv.ref_counts()
    check(all(count == 1 for count in refs.values()), f"leaked refs {refs}")
    check(len(refs) == engine.prefix_stats()["cached_blocks"], refs)
    check(kv.free_blocks + kv.used_blocks == kv.n_blocks, "pool accounting")
    decode, prefill = engine.decode_executables(), engine.prefill_executables()
    facts["executables"] = (
        f"decode {decode} <= {len(engine.lane_counts)} lane counts "
        f"{engine.lane_counts}; prefill {prefill} <= {len(engine.buckets)} "
        f"buckets {engine.buckets}"
    )
    check(decode <= len(engine.lane_counts), "decode executables unbounded")
    check(2 <= prefill <= len(engine.buckets), f"prefill executables {prefill}")

    # int8: on a TPU this name is the engine with the compiled Pallas kernel
    int8_runner = models["lm_streaming_int8"].runner
    int8_params = getattr(int8_runner, "scheduler", int8_runner).params
    tokens, _ = _stream(url, "lm_streaming_int8", prompts[0], int8_tokens)
    margin = LmReference(int8_params, engine.cfg).check(
        "int8 stream", prompts[0], tokens, int8_tokens, _EOS
    )
    facts["int8"] = (f"{len(tokens)} tokens, reference (dequantised "
                     f"weights) margin {margin:.3f}")

    # speculative: a repeating prompt, so the n-gram drafter has matches
    spec_engine = models["lm_spec_smoke"].runner.scheduler
    prompt = encode_text("chip smoke. " * (engine.cfg.max_seq // 36))
    tokens, _ = _stream(url, "lm_spec_smoke", prompt, spec_tokens)
    reference.check("speculative stream", prompt, tokens, spec_tokens, _EOS)
    counts = {
        k: int(registry.get(f"ctpu_lm_spec_{k}_tokens_total") or 0)
        for k in ("proposed", "accepted", "rejected")
    }
    facts["speculative"] = (
        f"{len(tokens)} tokens, {counts}, "
        f"{spec_engine.verify_executables()} verify executables"
    )
    check(counts["proposed"] > 0, "the drafter proposed nothing")
    check(counts["accepted"] + counts["rejected"] == counts["proposed"],
          counts)

    facts["preemption"] = _preemption(engine.params, engine.cfg, reference)


def _preemption(params, cfg, reference):
    """One priority preemption on an engine of its own (the stock server
    configures no tenant priorities): a pool too small for both streams, so
    admitting "hi" swaps "lo" out to the host and back.  The swap gathers
    from, and the resume scatters into, KV pools that every tick donates."""
    from client_tpu.serve.lm.engine import LmEngine

    block = 16
    width = -(-cfg.max_seq // block)  # the pool's minimum: one full table
    lo_prompt = np.arange(1, cfg.max_seq // 5, dtype=np.int32)
    hi_prompt = np.arange(3, cfg.max_seq // 8, dtype=np.int32)
    lo_tokens = (width * 3 // 4) * block - len(lo_prompt)
    hi_tokens = (width // 2) * block - len(hi_prompt)
    engine = LmEngine(
        params, cfg, max_slots=2, lane_counts=(2,), block_size=block,
        pool_tokens=width * block, eos_id=None,
        tenant_priority={"hi": 1.0},
    )
    try:
        lo_q, _ = engine.submit(lo_prompt, lo_tokens, tenant="lo")
        lo = [lo_q.get(timeout=900)]
        hi_q, _ = engine.submit(hi_prompt, hi_tokens, tenant="hi")

        def drain(q, into):
            while (token := q.get(timeout=900)) is not LmEngine.CLOSE:
                into.append(token)
            return into

        hi = drain(hi_q, [])
        lo = drain(lo_q, lo)
        stats = engine.preempt_stats()
        check(stats["preemptions"] >= 1, f"no preemption: {stats}")
        check(stats["resumes"] == stats["preemptions"], stats)
        check(stats["swapped_streams"] == 0, stats)
    finally:
        engine.close()
    check(engine.kv.used_blocks == 0, engine.kv.ref_counts())
    reference.check("preempted stream", lo_prompt, lo, lo_tokens)
    reference.check("preempting stream", hi_prompt, hi, hi_tokens)
    return (f"{stats['preemptions']} swap-out(s) and resume(s), "
            f"{len(lo)} + {len(hi)} tokens within the reference margin")


# -- kernels: compiled, with the Pallas call present --------------------------

def _compile_kernel(what, fn, *args, interpret):
    """Compile ``fn(*args)`` and return the executable, having checked
    that the kernel is in the program and no reference stood in for it: a
    ``pallas_call`` in the jaxpr and, unless interpreted, Mosaic's custom
    call in the lowered module."""
    import jax

    check("pallas_call" in str(jax.make_jaxpr(fn)(*args)),
          f"{what}: no Pallas call lowered")
    lowered = jax.jit(fn).lower(*args)
    check(interpret or "tpu_custom_call" in lowered.as_text(),
          f"{what}: no Mosaic custom call in the lowered module")
    return lowered.compile()


def kernel_phase(cfg, lane_counts, buckets, facts, interpret=False,
                 flash_shapes=((1, 512, 8, 32), (1, 2048, 8, 128)),
                 spec_k=4):
    """``int8_matmul`` at every (M, K, N) the engine issues for ``cfg`` —
    M from the lane counts, 1, the prefill chunk widths and lanes x verify
    widths; (K, N) from the projections — against ``x @ (q * s)``, and the
    ``flash_attention`` forward against ``plain_attention``.  Interpret
    mode is for the CPU test only."""
    from client_tpu.ops import flash_attention
    from client_tpu.ops.quant import int8_matmul, quantize_int8
    from client_tpu.parallel.ring_attention import plain_attention
    from client_tpu.serve.lm.policy import verify_widths

    d, hd = cfg.d_model, cfg.head_dim
    kn = sorted({
        (d, cfg.n_heads * hd), (d, cfg.n_kv_heads * hd), (d, cfg.d_ff),
        (cfg.d_ff, d), (cfg.n_heads * hd, d),
    })
    ms = sorted(
        {1, *lane_counts, *buckets}
        | {n * w for n in lane_counts for w in verify_widths(spec_k)}
    )
    # inputs and references are made with numpy, so that what this phase
    # compiles is the kernels
    rng = np.random.default_rng(0)

    def normal(*shape):
        return rng.standard_normal(shape, dtype=np.float32).astype(cfg.jdtype)

    first = None
    t0 = time.monotonic()
    for k, n in kn:
        qw = quantize_int8(normal(k, n) * k ** -0.5)
        dense = np.asarray(qw["q"], np.float32) * np.asarray(qw["s"])
        for m in ms:
            x = normal(m, k)

            def mm(x):
                return int8_matmul(x, qw, interpret=interpret)

            kernel = _compile_kernel(f"int8_matmul M{m} K{k} N{n}", mm, x,
                                     interpret=interpret)
            got = np.asarray(kernel(x), np.float32)
            first = first or time.monotonic() - t0
            want = x.astype(np.float32) @ dense
            err = float(np.max(np.abs(got - want)))
            check(err <= 0.01 * float(np.max(np.abs(want))),
                  f"int8_matmul M{m} K{k} N{n}: max |diff| {err:.4g}")
    facts["first_s"] = first
    facts["int8_matmul"] = (f"M {ms} x (K, N) {kn}, {cfg.dtype}: "
                            f"{len(ms) * len(kn)} shapes compiled and equal")

    for b, t, h, hd in flash_shapes:
        q, k, v = (normal(b, t, h, hd) for _ in range(3))

        def fa(q, k, v):
            return flash_attention(q, k, v, interpret=interpret)

        kernel = _compile_kernel(f"flash_attention T{t} D{hd}", fa, q, k, v,
                                 interpret=interpret)
        got = np.asarray(kernel(q, k, v), np.float32)
        want = np.asarray(plain_attention(q, k, v), np.float32)
        err = float(np.max(np.abs(got - want)))
        check(np.isfinite(got).all() and err <= 0.02,
              f"flash_attention T{t} D{hd}: max |diff| {err:.4g}")
    facts["flash_attention"] = (f"forward at (B, T, H, D) {flash_shapes}: "
                                "compiled and equal to plain_attention")


# -- main ---------------------------------------------------------------------

def smoke_models():
    """What ``python -m client_tpu.serve --models resnet,language`` serves,
    plus one speculative instance of the batched LM sharing its weights."""
    from client_tpu.serve.models import model_sets
    from client_tpu.serve.models.language import lm_streaming_batched_model

    models = {m.name: m for m in model_sets("resnet,language")}
    spec = lm_streaming_batched_model(
        name="lm_spec_smoke", runner=models["lm_streaming"].runner,
        speculative={"k": 4, "drafter": "ngram"},
    )
    models[spec.name] = spec
    return models


def main():
    t_start = time.monotonic()
    # a hung phase must end as a failure with every thread's stack, inside
    # the driver's 1200 s
    faulthandler.dump_traceback_later(1140, exit=True)
    from client_tpu._compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()  # before jax is imported
    device = name_device()
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax found platform "
                 f"'{device['platform']}'; nothing was run")

    def cache_entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    entries_before = cache_entries()
    print(f"compile cache: {cache_dir} ({entries_before} entries)")
    compiles, summary = Compiles(), {}

    with phase("build", compiles, summary):
        build_native()

    from client_tpu.serve import Server
    from client_tpu.serve.models.language import DEFAULT_LM_CONFIG

    t0 = time.monotonic()
    models = smoke_models()
    server = Server(models=list(models.values()), http_port=0, grpc_port=0,
                    with_default_models=False)
    print(f"\nserver: {len(models)} models built in "
          f"{time.monotonic() - t0:.1f}s (resnet50 warmup=False: the "
          "smoke's own traffic compiles what it uses)")
    with server:
        classifier = models["resnet50"].fn  # the ResNet50Classifier served

        def direct(rows):
            out = classifier({"INPUT0": rows}, {}, None)["OUTPUT0"]
            return np.asarray(out)

        with phase("vision", compiles, summary) as facts:
            vision_phase(server, "resnet50", direct, classifier.image_size,
                         device["platform"], facts)
        with phase("perf", compiles, summary) as facts:
            perf_phase(server, "resnet50", device, facts)
        with phase("lm", compiles, summary) as facts:
            lm_phase(server, models, facts)
            check(type(models["lm_streaming_int8"].runner).__name__
                  == "BatchedLmRunner", "int8 did not resolve to the engine")
        with phase("kernels", compiles, summary) as facts:
            engine = models["lm_streaming_batched"].runner.scheduler
            kernel_phase(DEFAULT_LM_CONFIG, engine.lane_counts,
                         engine.buckets, facts)

    total = compiles.snapshot()
    print(f"\ncompile cache: {cache_dir} ({entries_before} -> "
          f"{cache_entries()} entries); {total[0]} executables, "
          f"{total[2]} cache hits, {total[3]} misses, "
          f"{total[1]:.1f}s compiling or fetching, of "
          f"{time.monotonic() - t_start:.1f}s in all")
    print("summary (smoke, not a benchmark): " + json.dumps(summary))
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
