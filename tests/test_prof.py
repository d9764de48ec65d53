"""The continuous profiler (serve/prof.py) and its surfaces.

Covers:
- ring semantics: bounded capacity, idle-run coalescing, disarmed
  no-op handles, and thread-safety under the Eraser race witness,
- reconciliation: bracketed phase sums stay within the tick wall time
  and the compute/dispatch/device_wait/host/idle attribution sums to
  ~100,
- the ctpu_prof_* series reaching a Registry through the batched
  flush path (and the metrics-manager prefix whitelist),
- the server surfaces: GET /v2/debug/prof, prof_tick records in
  flight dumps, and the profview CLI (text / json / exit codes),
- the always-on budget: one armed commit costs <= 2% of a headline
  in-process request.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import client_tpu.http as httpclient
from client_tpu import profview
from client_tpu.serve import Model, Server, TensorSpec
from client_tpu.serve.metrics import Registry
from client_tpu.serve.prof import (
    NULL_TICK,
    PhaseProfiler,
    attribute_phases,
    device_peak_tflops,
)


def _commit_n(prof, n, kind="unary", model=None):
    for i in range(n):
        prof.commit(
            kind, 1e-3,
            phases={"host": 2e-4, "compute": 6e-4, "render": 2e-4},
            model=model, items=1 if model else 0,
        )


class TestRing:
    def test_ring_is_bounded(self):
        p = PhaseProfiler(name="t", capacity=8)
        _commit_n(p, 50)
        assert len(p.snapshot()) == 8
        assert p.ticks_noted == 50  # lifetime counters keep counting

    def test_idle_runs_coalesce_in_place(self):
        p = PhaseProfiler(name="t", capacity=8)
        p.commit("unary", 1e-3, phases={"compute": 1e-3})
        for _ in range(20):
            p.commit("idle", 5e-2, phases={"idle": 5e-2})
        records = p.snapshot()
        assert len(records) == 2  # the idle run is ONE record
        idle = records[-1]
        assert idle["kind"] == "idle" and idle["ticks"] == 20
        assert idle["dur_s"] == pytest.approx(20 * 5e-2)
        # ...but the rollup still counts every coalesced tick
        assert p.rollup(window_s=0)["kinds"]["idle"] == 20

    def test_disarmed_is_a_no_op(self):
        p = PhaseProfiler(name="t")
        p.arm(False)
        assert p.start_tick("sched") is NULL_TICK
        with p.start_tick("sched") as tick:
            with tick.phase("schedule"):
                pass
            tick.relabel("idle")
            tick.compute("m", 1, 1e6)
        p.commit("unary", 1e-3, phases={"compute": 1e-3})
        assert p.snapshot() == [] and p.ticks_noted == 0
        p.arm(True)
        p.commit("unary", 1e-3, phases={"compute": 1e-3})
        assert p.ticks_noted == 1

    def test_commits_are_race_free_under_witness(self):
        """Concurrent commits, snapshots, and rollups on one profiler:
        the Eraser witness instruments @witness_shared(PhaseProfiler)
        and must stay green."""
        from client_tpu.analysis.witness import RaceWitness

        w = RaceWitness()
        with w.installed():
            p = PhaseProfiler(name="t", capacity=64)
            errors = []

            def writer():
                try:
                    _commit_n(p, 200, model="m")
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            def reader():
                try:
                    for _ in range(50):
                        p.snapshot()
                        p.rollup(window_s=0)
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            threads = [threading.Thread(target=fn)
                       for fn in (writer, writer, reader)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert not errors
        assert p.ticks_noted == 400
        assert w.assert_race_free() > 0  # it watched, and stayed green


class TestReconciliation:
    def test_phase_sum_stays_within_wall(self):
        p = PhaseProfiler(name="t")
        tick = p.start_tick("sched")
        try:
            with tick.phase("schedule"):
                time.sleep(0.005)
            with tick.phase("decode_dispatch"):
                time.sleep(0.01)
        finally:
            p.finish(tick)
        roll = p.rollup(window_s=0)
        assert roll["ticks"] == 1
        # bracketed phases can never exceed the tick's wall time...
        assert roll["covered_s"] <= roll["wall_s"]
        # ...and here they bracket nearly all of it
        assert roll["covered_s"] >= 0.8 * roll["wall_s"]

    def test_attribution_sums_to_100(self):
        # launching a program is dispatch and blocking on its result a
        # wait: only the device time the completion observer measured
        # (or a host model's own run time) is compute
        split = attribute_phases(
            {"compute": 0.4, "schedule": 0.05, "decode_dispatch": 0.05,
             "device_wait": 0.15, "device_queue": 0.05, "host": 0.2},
            wall_s=1.0,  # 0.1s uncovered -> idle
        )
        assert split["compute_pct"] == pytest.approx(40.0, abs=0.1)
        assert split["dispatch_pct"] == pytest.approx(10.0, abs=0.1)
        assert split["device_wait_pct"] == pytest.approx(20.0, abs=0.1)
        assert split["host_pct"] == pytest.approx(20.0, abs=0.1)
        assert split["idle_pct"] == pytest.approx(10.0, abs=0.1)
        assert sum(split.values()) == pytest.approx(100.0, abs=0.5)

    def test_attribution_empty_is_none(self):
        assert attribute_phases({}) is None

    def test_report_covers_adopted_children(self):
        parent = PhaseProfiler(name="serve")
        child = PhaseProfiler(name="lm")
        parent.adopt(child)
        _commit_n(parent, 2)
        _commit_n(child, 3, kind="decode")
        report = parent.report(window_s=0)
        assert report["kind"] == "prof_report"
        by_name = {e["engine"]: e for e in report["engines"]}
        assert by_name["serve"]["ticks"] == 2
        assert by_name["lm"]["ticks"] == 3
        # recent() tags each record with its engine for flight dumps
        engines = {r["engine"] for r in parent.recent(last=8)}
        assert engines == {"serve", "lm"}


class TestMetricsExport:
    def test_batched_flush_reaches_registry(self):
        reg = Registry()
        p = PhaseProfiler(name="t", registry=reg)
        _commit_n(p, 10, model="m")
        p.flush_metrics()
        lines = []
        reg.render_into(lines)
        text = "\n".join(lines)
        assert 'ctpu_prof_ticks_total{engine="t",kind="unary"} 10' in text
        assert "ctpu_prof_phase_seconds_total" in text
        assert "ctpu_prof_compute_share_pct" in text

    def test_no_mfu_off_tpu(self):
        import jax

        jax.devices()  # the profiler only looks at a backend already up
        reg = Registry()
        p = PhaseProfiler(name="t", registry=reg)
        p.commit("unary", 1e-3, phases={"compute": 1e-3},
                 model="m", items=1, flops_per_item=1e6)
        p.flush_metrics()
        assert device_peak_tflops() == (None, "cpu")
        roll = p.rollup(window_s=0)
        assert roll["peak_tflops"] is None
        assert roll["device_kind"] == "cpu"
        assert "mfu_pct" not in roll["models"]["m"]
        lines = []
        reg.render_into(lines)
        assert not any("ctpu_prof_mfu_pct" in line for line in lines)

    def test_peak_is_exact_key_and_unlisted_tpu_raises(self, monkeypatch):
        from client_tpu.serve import metrics

        class Dev:
            platform = "tpu"

            def __init__(self, kind):
                self.device_kind = kind

        monkeypatch.setattr(
            metrics, "initialized_devices", lambda: [Dev("TPU v5 lite")]
        )
        assert device_peak_tflops() == (197.0, "TPU v5 lite")
        p = PhaseProfiler(name="t")
        p.commit("unary", 1e-3, phases={"compute": 1e-3},
                 model="m", items=1, flops_per_item=1e6)
        assert p.rollup(window_s=0)["models"]["m"]["mfu_pct"] > 0
        # a substring match would hand this kind a neighbour's peak
        monkeypatch.setattr(
            metrics, "initialized_devices", lambda: [Dev("TPU v5 lite pod")]
        )
        with pytest.raises(ValueError, match="TPU v5 lite pod"):
            device_peak_tflops()

    def test_peak_lookup_never_initialises_a_backend(self, monkeypatch):
        import jax
        from jax._src import xla_bridge

        from client_tpu.serve.metrics import initialized_devices

        def opened():
            raise AssertionError("jax.devices() would open the chip")

        monkeypatch.setattr(
            xla_bridge, "backends_are_initialized", lambda: False
        )
        monkeypatch.setattr(jax, "devices", opened)
        assert initialized_devices() == []
        assert device_peak_tflops() == (None, None)

    def test_prof_prefix_is_whitelisted(self):
        from client_tpu.perf.metrics_manager import MetricsManager

        assert "ctpu_prof_" in MetricsManager.SERIES_PREFIXES


def _infer_simple(client, n=1):
    inputs = [
        httpclient.InferInput("INPUT0", [1, 16], "INT32"),
        httpclient.InferInput("INPUT1", [1, 16], "INT32"),
    ]
    inputs[0].set_data_from_numpy(np.ones((1, 16), np.int32))
    inputs[1].set_data_from_numpy(np.ones((1, 16), np.int32))
    for _ in range(n):
        client.infer("simple", inputs)


class TestServerSurfaces:
    def test_debug_prof_endpoint(self):
        with Server(http_port=0) as server:
            with httpclient.InferenceServerClient(server.http_address) as c:
                _infer_simple(c, n=3)
            body = urllib.request.urlopen(
                f"http://{server.http_address}/v2/debug/prof?window=0"
            ).read()
            report = json.loads(body)
            assert report["kind"] == "prof_report"
            by_name = {e["engine"]: e for e in report["engines"]}
            serve = by_name["serve"]
            assert serve["kinds"]["unary"] == 3
            split = serve["attribution"]
            assert sum(split.values()) == pytest.approx(100.0, abs=0.5)
            # the HTTP frontend's wire ticks land in the wire engine
            wire = by_name["wire"]
            assert wire["kinds"]["http"] == 3
            for phase in ("deserialize", "wait", "serialize", "send"):
                assert phase in wire["phases"]

    def test_flight_dump_carries_prof_ticks(self):
        with Server(http_port=0) as server:
            with httpclient.InferenceServerClient(server.http_address) as c:
                _infer_simple(c, n=2)
            body = urllib.request.urlopen(
                f"http://{server.http_address}/v2/debug/flight"
            ).read().decode()
            lines = [json.loads(line) for line in body.splitlines()]
            prof_ticks = [r for r in lines if r["kind"] == "prof_tick"]
            assert any(r.get("tick_kind") == "unary" for r in prof_ticks)
            assert all("engine" in r for r in prof_ticks)


class TestProfview:
    def _report_file(self, tmp_path):
        p = PhaseProfiler(name="serve")
        _commit_n(p, 4, model="m")
        path = tmp_path / "prof.json"
        path.write_text(json.dumps(p.report(window_s=0)))
        return path

    def test_text_output(self, tmp_path, capsys):
        path = self._report_file(tmp_path)
        assert profview.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "engine serve" in out and "ticks=4" in out
        assert "attribution:" in out and "compute" in out
        assert "model m" in out

    def test_json_output(self, tmp_path, capsys):
        path = self._report_file(tmp_path)
        assert profview.main([str(path), "--format", "json"]) == 0
        rollups = [json.loads(line)
                   for line in capsys.readouterr().out.splitlines()]
        assert rollups[0]["engine"] == "serve"
        assert rollups[0]["ticks"] == 4

    def test_flight_dump_input_rerolls(self, tmp_path, capsys):
        p = PhaseProfiler(name="serve")
        _commit_n(p, 3, model="m")
        dump = tmp_path / "flight.jsonl"
        lines = []
        for record in p.recent(last=8):
            tagged = dict(record)
            tagged["tick_kind"] = tagged.pop("kind", None)
            tagged["kind"] = "prof_tick"
            lines.append(json.dumps(tagged))
        dump.write_text("\n".join(lines) + "\n")
        assert profview.main([str(dump)]) == 0
        out = capsys.readouterr().out
        assert "engine serve" in out and "ticks=3" in out

    def test_exit_codes(self, tmp_path, capsys):
        assert profview.main([str(tmp_path / "missing.json")]) == 2
        empty = tmp_path / "empty.json"
        empty.write_text(json.dumps(
            PhaseProfiler(name="quiet").report(window_s=0)
        ))
        assert profview.main([str(empty)]) == 1
        err = capsys.readouterr().err
        assert "no prof data found" in err

    def test_engine_filter(self, tmp_path, capsys):
        parent = PhaseProfiler(name="serve")
        child = PhaseProfiler(name="lm")
        parent.adopt(child)
        _commit_n(parent, 1)
        _commit_n(child, 1, kind="decode")
        path = tmp_path / "prof.json"
        path.write_text(json.dumps(parent.report(window_s=0)))
        assert profview.main([str(path), "--engine", "lm"]) == 0
        out = capsys.readouterr().out
        assert "engine lm" in out and "engine serve" not in out


class TestOverheadBudget:
    def test_armed_commit_within_2pct_of_headline_request(self):
        """The always-on budget: one armed commit (the unary path adds
        exactly one per request) costs <= 2% of an in-process headline
        request."""
        work = np.ones((384, 384), np.float32) * 1e-3

        def fn(inputs, params, ctx):
            acc = work
            for _ in range(6):
                acc = acc @ work
            return {"OUT": inputs["IN"] + acc[0, 0]}

        from client_tpu.serve.model_runtime import InferenceEngine
        from client_tpu.utils import to_wire_bytes

        engine = InferenceEngine(models=[Model(
            "probe",
            inputs=[TensorSpec("IN", "FP32", [-1, 8])],
            outputs=[TensorSpec("OUT", "FP32", [-1, 8])],
            fn=fn,
        )])
        try:
            arr = np.zeros((1, 8), np.float32)
            raw = to_wire_bytes(arr, "FP32")
            request = {
                "id": "",
                "inputs": [{
                    "name": "IN", "datatype": "FP32", "shape": [1, 8],
                    "parameters": {"binary_data_size": len(raw)},
                }],
                "outputs": [
                    {"name": "OUT", "parameters": {"binary_data": True}}
                ],
            }

            def run(n=20):
                t0 = time.perf_counter()
                for _ in range(n):
                    engine.execute("probe", "", dict(request), raw)
                return (time.perf_counter() - t0) / n

            run(5)  # warm imports / BLAS threads
            request_s = min(run(), run())

            prof = engine.prof
            phases = {"host": 2e-5, "compute": 9e-3, "render": 1e-5}
            iters = 5000
            t0 = time.perf_counter()
            for _ in range(iters):
                prof.commit("unary", 9.1e-3, phases=phases,
                            model="probe", items=1, flops_per_item=1e6)
            commit_s = (time.perf_counter() - t0) / iters
            overhead_pct = 100.0 * commit_s / request_s
            assert overhead_pct <= 2.0, (
                f"armed commit {commit_s * 1e6:.1f}us on a "
                f"{request_s * 1e3:.2f}ms request = {overhead_pct:.2f}%"
            )
        finally:
            engine.close()
