#!/usr/bin/env python3
"""The benchmark's one command:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell by its name alone (``workloads/<cell>.json`` -> its config ->
the metric files it names -> driver and reader modules), refuses anything
but a TPU with the chips the cell asks for, sets up, warms the cell's own
shapes, measures for ``--seconds``, frees the program's state, checks the
window's answers against the plain reference, and prints one JSON object as
the last line of standard output.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = ROOT  # run as a script: not this directory (its trace.py
else:                   # would stand before the library's), but the checkout
    sys.path.insert(0, ROOT)


def log(message):
    print(f"[{time.monotonic() - T_PROCESS:7.2f}s] {message}",
          file=sys.stderr, flush=True)


def load_json(roots, *parts):
    for root in roots:
        path = os.path.join(root, *parts)
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise SystemExit(f"benchmark: no {os.path.join(*parts)} under {roots}")


def load_cell(name, roots=(HERE,)):
    """The cell, its configuration, its driver module and its metrics, from
    files found by name.  A metric has to move an end-to-end metric that the
    cell's driver reports."""
    cell = load_json(roots, "workloads", f"{name}.json")
    config = load_json(roots, "configs", f"{cell['config']}.json")
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    metrics = []
    for metric_name in cell["metrics"]:
        metric = load_json(roots, "metrics", f"{metric_name}.json")
        if metric["moves"] not in driver.END_TO_END:
            raise SystemExit(
                f"{name}: metric {metric_name} moves {metric['moves']}, which "
                f"driver {cell['driver']} does not report")
        metric["read"] = importlib.import_module(
            f"benchmark.readers.{metric['reader']}").read
        metrics.append(metric)
    manifest = os.path.join(ROOT, "BENCHMARK.json")
    chips = 1
    if os.path.exists(manifest):
        with open(manifest) as f:
            chips = next((w["chips"] for w in json.load(f)["workloads"]
                          if w["name"] == name), 1)
    return cell, config, driver, metrics, chips


class Compiles:
    """What JAX compiled or fetched, from its own monitoring events, so that
    a run can say that nothing compiled inside its window."""

    def __init__(self):
        import jax.monitoring

        self.executables = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.executables += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"{self.executables} executables built or fetched in "
                f"{self.seconds:.1f}s, {self.hits} cache hits, "
                f"{self.misses} misses")


class Tracer:
    """Traces a few seconds in the middle of the window, from a thread of its
    own, and notes the host-clock instants just inside the trace."""

    def __init__(self, seconds, log_dir):
        self.seconds, self.log_dir = seconds, log_dir
        self.span = self.error = self.thread = None

    def arm(self, t_start, window_seconds):
        length = min(self.seconds, window_seconds / 2)
        begin = t_start + (window_seconds - length) / 2
        self.thread = threading.Thread(target=self._run, args=(begin, length))
        self.thread.start()

    def _run(self, begin, length):
        import jax

        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            time.sleep(max(begin - time.monotonic(), 0))
            jax.profiler.start_trace(self.log_dir, profiler_options=options)
            t_a = time.monotonic()
            time.sleep(length)
            t_b = time.monotonic()
            jax.profiler.stop_trace()
            self.span = (t_a, t_b)
        except Exception as e:  # noqa: BLE001 - reported by join()
            self.error = e

    def join(self):
        self.thread.join()
        if self.error is not None:
            raise self.error
        return self.span


def build_native():
    """The shm transport libraries are build products: ``make native``."""
    done = subprocess.run(["make", "-C", ROOT, "native"], capture_output=True,
                          text=True)
    if done.returncode != 0:
        raise SystemExit(f"`make native` failed:\n{done.stdout}{done.stderr}")


def main(argv=None, require_tpu=True, roots=(HERE,)):
    """One run.  ``require_tpu=False`` and other ``roots`` are for the tests,
    which drive everything but the look for a chip at tiny sizes."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for setting the limits, never given by the driver: the reference in the
    # nearest lower precision, put in the program's place on the same sample
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    build_native()
    cell, config, driver, metrics, chips = load_cell(args.workload, roots)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if require_tpu and (device["platform"] != "tpu" or len(devices) < chips):
        raise SystemExit(f"benchmark: {args.workload} needs {chips} TPU "
                         f"chip(s); jax found {device}; nothing was run")
    from benchmark import trace as trace_reader
    from benchmark import work

    if require_tpu:
        work.peaks(device["kind"])  # an unlisted device is an error, now
    log(f"{args.workload} seed {args.seed} on {device}; compile cache "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')}")

    compiles = Compiles()
    run = driver.Run(cell, config, args.seed, log)
    run.setup()
    log(f"set up and warm: {compiles}")
    before = compiles.executables
    tracer = None
    trace_dir = os.path.join(ROOT, ".bench_tmp", cell["name"], "trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = Tracer(cell["trace_seconds"], trace_dir)
    window = run.measure(args.seconds, tracer)
    setup_s = window["t_start"] - T_PROCESS
    log(f"window closed: {window['attempted']} attempted, "
        f"{window['failed']} failed, {compiles.executables - before} "
        "executables built inside it")
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices[:chips])
    run.close()

    ctx = {"window": window, "trace": None, "config": config, "cell": cell,
           "device_kind": device["kind"], "chips": chips}
    result_metrics, breakdown = {}, None
    if args.trace:
        summary = trace_reader.read(trace_reader.newest_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = summary
        device["busy_s"], device["window_s"] = (summary["busy_s"],
                                                summary["window_s"])
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        log("modules: " + json.dumps(summary["modules"]))
        for metric in metrics:
            value = metric["read"](metric["params"], ctx)
            if value is not None:
                result_metrics[metric["name"]] = {"value": value,
                                                  "unit": metric["unit"]}
    else:
        for name, (value, unit) in run.end_to_end(window).items():
            result_metrics[name] = {"value": value, "unit": unit}
        result_metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    checked = run.check(window)
    for c in checked.values():  # JSON has no infinity
        c["value"] = min(c["value"], 1e30)
    correct = bool(checked) and all(
        c["value"] <= c["limit"] for c in checked.values())
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": result_metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.control:
        from benchmark import reference

        result["control"] = run.check(window, quant=reference.fp8)
        log(f"control: {result['control']}")
    result["checked"] = checked
    log(f"done; set-up {setup_s:.1f}s")
    for name, c in checked.items():
        print(f"checked {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    faulthandler.dump_traceback_later(1150, exit=True)  # never hang a check
    from client_tpu._compile_cache import enable_compile_cache

    # before jax is imported: JAX_COMPILATION_CACHE_DIR if the machine sets
    # it, else .jax_cache at the root of this checkout
    enable_compile_cache()
    # A machine may cap the cache's size (the chip machines do, at 192 MiB,
    # with JAX_COMPILATION_CACHE_MAX_SIZE).  The program compiles resnet50's
    # weights into each fused forward as constants, 51 MB an executable and
    # eight to the cell: under that cap they evict one another, and every
    # run compiles and writes them anew.  Uncapped they are written once.
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    main()
    faulthandler.cancel_dump_traceback_later()
