"""Driver for the native C++ load-generation worker (build/cpp/perf_worker).

The binary is the harness's C++ engine — the reference perf_analyzer's
native load path (perf_analyzer.cc:56-424): N async InferContexts
multiplexed on one HTTP/2 connection, completed by its reactor thread.  No
GIL anywhere near the measurement; the Python side only assembles arguments
and parses the one-line JSON report.

TPU-shm loads compose with region-by-name referencing exactly like
procpool: the coordinator (Python, owns jax) creates and registers the
regions; the native worker sends requests that reference them by name.
"""

import json
import os
import subprocess

from client_tpu.utils import InferenceServerException

_DEFAULT_BINARY = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "build", "cpp", "perf_worker",
)


def native_worker_available(binary=None):
    return os.path.exists(binary or _DEFAULT_BINARY)


def native_windows_stable(windows, threshold, window_count=3):
    """DetermineStability over trailing native windows (reference
    inference_profiler.h:365-399): throughput and p99 latency of the last
    ``window_count`` windows each within ±threshold of their mean: the
    perf CLI sweep's stability rule."""
    if len(windows) < window_count:
        return False
    tail = windows[-window_count:]
    for key in ("throughput", "p99_us"):
        vals = [w[key] for w in tail]
        avg = sum(vals) / len(vals)
        if avg <= 0 or any(abs(v - avg) > threshold * avg for v in vals):
            return False
    return True


def run_native_worker(url, model_name, *, concurrency, duration_s,
                      warmup_s=1.0, wire_inputs=(), shm_inputs=(),
                      shm_outputs=(), binary=None, timeout_s=None,
                      request_rate=0.0, distribution="constant",
                      window_interval_s=0.0, completion_sync=False,
                      sequences=0, seq_steps=8, decoupled=False):
    """One native measurement (fixed concurrency, request-rate schedule, or
    bidi sequence streaming).

    wire_inputs: [(name, datatype, shape)] — random bytes generated in the
    worker.  shm_inputs: [(name, datatype, shape, region, nbytes)].
    shm_outputs: [(name, region, nbytes)].

    request_rate > 0 switches the worker to an open-loop schedule
    (constant or poisson inter-arrivals) with `concurrency` capping the
    outstanding requests; the report then carries a ``delayed`` count.
    completion_sync requests wire outputs instead of shm outputs, so every
    recorded latency covers device compute + D2H (completion, not ack).
    sequences > 0 drives that many stateful sequences of seq_steps over the
    bidi stream instead of unary AsyncInfer.  decoupled drives
    N-responses-per-request streaming (the LLM token-stream shape): latency
    samples are time-to-first-response, completion rides the
    triton_final_response marker, and the report carries the total content
    ``responses`` count.  wire_inputs entries may carry a constant fill as
    a 4th element (name, datatype, shape, value) — required for decoupled
    models whose input encodes the response count.

    Returns the worker's final report dict (ok/errors/delayed/elapsed_s/
    throughput/p50_us/.../avg_us/mode); with window_interval_s > 0 the
    report also carries the per-window records under ``windows`` — the
    feed for the profiler's stability loop over native load.
    """
    binary = binary or _DEFAULT_BINARY
    if not os.path.exists(binary):
        raise InferenceServerException(
            f"native perf worker not built: {binary} (run `make`)"
        )
    cmd = [binary, "-u", url, "-m", model_name, "-c", str(concurrency),
           "-d", str(duration_s), "-w", str(warmup_s)]
    if request_rate > 0:
        cmd += ["-r", str(request_rate), "--distribution", distribution]
    if window_interval_s > 0:
        cmd += ["--window-interval", str(window_interval_s)]
    if completion_sync:
        cmd += ["--completion-sync"]
    if sequences > 0:
        cmd += ["--sequences", str(sequences), "--seq-steps", str(seq_steps)]
    if decoupled:
        cmd += ["--decoupled"]
    for entry in wire_inputs:
        name, datatype, shape = entry[0], entry[1], entry[2]
        dims = ",".join(str(int(d)) for d in shape)
        fill = f"={int(entry[3])}" if len(entry) > 3 else ""
        cmd += ["--wire-input", f"{name}:{datatype}:{dims}{fill}"]
    for name, datatype, shape, region, nbytes in shm_inputs:
        dims = ",".join(str(int(d)) for d in shape)
        cmd += ["--shm-input", f"{name}:{datatype}:{dims}:{region}:{nbytes}"]
    for name, region, nbytes in shm_outputs:
        cmd += ["--shm-output", f"{name}:{region}:{nbytes}"]
    proc = subprocess.run(
        cmd, capture_output=True, text=True,
        timeout=timeout_s or (warmup_s + duration_s + 90),
    )
    if proc.returncode != 0:
        raise InferenceServerException(
            f"native perf worker failed ({proc.returncode}): "
            f"{proc.stderr.strip() or proc.stdout.strip()}"
        )
    try:
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
        report = json.loads(lines[-1])
        windows = []
        for ln in lines[:-1]:
            try:
                doc = json.loads(ln)
            except json.JSONDecodeError:
                continue
            if "window" in doc:
                windows.append(doc)
        if windows:
            report["windows"] = windows
        return report
    except (json.JSONDecodeError, IndexError) as e:
        raise InferenceServerException(
            f"malformed native worker report: {proc.stdout!r}"
        ) from e
