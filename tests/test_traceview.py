"""traceview joins + critical-path attribution, the flight recorder, and
the SLO watchdog (the observability PR's new surfaces).

Covers:
- join_traces/critical_path on synthetic multi-source records (client,
  server, peer) — attribution math pinned against hand-computed
  figures, overlap-safe server merging;
- the CLI: text timelines, ``--format json`` one-object-per-trace,
  ``--trace`` selection, bad-file exit code;
- FlightRecorder: bounded ring, JSON-lines render/dump, registry counter,
  unwritable-dir best-effort;
- LatencySketch: quantile bounds, exact mergeability;
- SloWatchdog: gauge export, objective breach -> counter + flight dump
  (rate-limited), 4xx-vs-5xx error accounting, window rotation.
"""

import json

import pytest

from client_tpu import traceview
from client_tpu.serve.flight import FlightRecorder
from client_tpu.serve.metrics import Registry
from client_tpu.serve.slo import BOUNDS_MS, LatencySketch, SloWatchdog
from client_tpu.tracing import ClientTracer, append_trace_record

MS = 1_000_000  # ns per ms


def _rec(trace_id, source, model, events, span_id="s", parent=None,
         tags=None):
    record = {
        "trace_id": trace_id,
        "span_id": span_id,
        "source": source,
        "model_name": model,
        "timestamps": [
            dict({"name": n, "ns": ns}, **(extra or {}))
            for n, ns, extra in events
        ],
    }
    if parent:
        record["parent_span_id"] = parent
    if tags:
        record["tags"] = tags
    return record


def _sample_records(t0=1_000 * MS):
    client = _rec("t1", "client", "m", [
        ("CLIENT_REQUEST_START", t0, None),
        ("CLIENT_ATTEMPT_START", t0 + 1 * MS, {"endpoint": "a:1"}),
        ("CLIENT_ATTEMPT_END", t0 + 19 * MS, {"endpoint": "a:1"}),
        ("CLIENT_REQUEST_END", t0 + 20 * MS, None),
    ], span_id="c1")
    server = _rec("t1", "server", "m", [
        ("REQUEST_START", t0 + 2 * MS, None),
        ("QUEUE_START", t0 + 2 * MS, None),
        ("QUEUE_END", t0 + 5 * MS, None),
        ("COMPUTE_START", t0 + 5 * MS, None),
        ("COMPUTE_END", t0 + 15 * MS, None),
        ("RESPONSE_SENT", t0 + 16 * MS, None),
    ], span_id="s1", parent="c1")
    peer = _rec("t1", "server", "__peer_prefix_get__", [
        ("PEER_START", t0 + 6 * MS, None),
        ("PEER_END", t0 + 10 * MS, None),
    ], span_id="p1", parent="s1", tags={"peer": "b:2", "hit": True})
    other = _rec("t2", "server", "n", [
        ("COMPUTE_START", t0, None),
        ("COMPUTE_END", t0 + 3 * MS, None),
    ])
    return [client, server, peer, other]


class TestJoin:
    def test_groups_by_trace_id_sorted_by_start(self):
        traces = traceview.join_traces(_sample_records())
        assert set(traces) == {"t1", "t2"}
        assert [r["span_id"] for r in traces["t1"]] == ["c1", "s1", "p1"]

    def test_drops_recordless_and_idless_spans(self):
        traces = traceview.join_traces([
            {"trace_id": "x", "timestamps": []},
            {"source": "client", "timestamps": [{"name": "A", "ns": 1}]},
        ])
        assert traces == {}

    def test_critical_path_attribution(self):
        traces = traceview.join_traces(_sample_records())
        cp = traceview.critical_path(traces["t1"])
        assert cp["total_ms"] == pytest.approx(20.0)
        assert cp["queue_ms"] == pytest.approx(3.0)
        assert cp["compute_ms"] == pytest.approx(10.0)
        assert cp["peer_ms"] == pytest.approx(4.0)
        # wire = client total (20) - server span extent (2..16 = 14)
        assert cp["wire_ms"] == pytest.approx(6.0)

    def test_overlapping_server_spans_do_not_double_count(self):
        t0 = 0
        spans = [
            _rec("t", "server", "m", [
                ("COMPUTE_START", t0, None),
                ("COMPUTE_END", t0 + 10 * MS, None),
            ]),
            _rec("t", "server", "m2", [
                ("COMPUTE_START", t0 + 5 * MS, None),
                ("COMPUTE_END", t0 + 12 * MS, None),
            ]),
        ]
        cp = traceview.critical_path(spans)
        # no client span: total falls back to the full extent
        assert cp["total_ms"] == pytest.approx(12.0)
        assert cp["wire_ms"] == 0.0

    def test_sequence_trace_sums_per_request_client_spans(self):
        t0 = 0
        spans = [
            _rec("t", "client", "m", [
                ("CLIENT_REQUEST_START", t0, None),
                ("CLIENT_REQUEST_END", t0 + 5 * MS, None),
            ], span_id="c1"),
            _rec("t", "client", "m", [
                ("CLIENT_REQUEST_START", t0 + 100 * MS, None),
                ("CLIENT_REQUEST_END", t0 + 107 * MS, None),
            ], span_id="c2"),
        ]
        cp = traceview.critical_path(spans)
        # the think-time gap between steps is NOT latency
        assert cp["total_ms"] == pytest.approx(12.0)


class TestCli:
    def _write(self, tmp_path, records, name="t.jsonl"):
        path = tmp_path / name
        for record in records:
            append_trace_record(str(path), record)
        return str(path)

    def test_text_timeline(self, tmp_path, capsys):
        path = self._write(tmp_path, _sample_records())
        assert traceview.main([path]) == 0
        out = capsys.readouterr().out
        assert "trace t1" in out and "trace t2" in out
        assert "critical path" in out
        assert "peer=b:2" in out and "hit=True" in out
        assert "QUEUE_END" in out

    def test_json_format_one_object_per_trace(self, tmp_path, capsys):
        path = self._write(tmp_path, _sample_records())
        assert traceview.main(["--format", "json", path]) == 0
        docs = [
            json.loads(line)
            for line in capsys.readouterr().out.strip().splitlines()
        ]
        assert {d["trace_id"] for d in docs} == {"t1", "t2"}
        t1 = next(d for d in docs if d["trace_id"] == "t1")
        assert t1["sources"] == ["client", "server"]
        assert t1["models"] == ["m"]
        assert t1["critical_path"]["peer_ms"] == pytest.approx(4.0)

    def test_trace_prefix_selection_and_min_spans(self, tmp_path, capsys):
        path = self._write(tmp_path, _sample_records())
        assert traceview.main(["--trace", "t2", path]) == 0
        out = capsys.readouterr().out
        assert "trace t2" in out and "trace t1" not in out
        assert traceview.main(["--min-spans", "2", path]) == 0
        out = capsys.readouterr().out
        assert "trace t1" in out and "trace t2" not in out

    def test_multi_file_join(self, tmp_path, capsys):
        records = _sample_records()
        a = self._write(tmp_path, records[:1], "client.jsonl")
        b = self._write(tmp_path, records[1:3], "server.jsonl")
        assert traceview.main(["--trace", "t1", a, b]) == 0
        assert "spans=3" in capsys.readouterr().out

    def test_missing_file_is_exit_2(self, tmp_path, capsys):
        assert traceview.main([str(tmp_path / "absent.jsonl")]) == 2
        assert "traceview:" in capsys.readouterr().err


class TestSequencePinnedSampling:
    def test_all_steps_share_one_trace_id(self):
        tracer = ClientTracer(trace_rate=1)
        traces = [
            tracer.sample("m", context_key=("sequence", 7))
            for _ in range(4)
        ]
        assert all(t is not None for t in traces)
        assert len({t.trace_id for t in traces}) == 1
        assert len({t.span_id for t in traces}) == 4

    def test_sequence_traced_whole_or_not_at_all(self):
        """With trace_rate > 1 the key's FIRST request decides for the
        whole sequence: an unsampled first step pins the key untraced —
        a trace must never start at a random mid-step."""
        tracer = ClientTracer(trace_rate=2)
        # request 0 (sampled slot) -> sequence A traced from step 1
        a = [tracer.sample("m", context_key="A") for _ in range(3)]
        assert all(t is not None for t in a)
        # the next fresh key lands on an unsampled slot: never traced,
        # even though later steps cross sampled slots
        b = [tracer.sample("m", context_key="B") for _ in range(5)]
        assert all(t is None for t in b)
        # release makes a restarted key re-decide
        tracer.release_context("B")
        assert tracer.sample("m", context_key="B") is not None

    def test_release_context_starts_fresh_trace(self):
        tracer = ClientTracer(trace_rate=1)
        # tpulint: disable=SPAN-LEAK -- ids compared only; never exported
        first = tracer.sample("m", context_key="k")
        tracer.release_context("k")
        # tpulint: disable=SPAN-LEAK -- ids compared only; never exported
        second = tracer.sample("m", context_key="k")
        assert first.trace_id != second.trace_id


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        recorder = FlightRecorder(capacity=4)
        for i in range(10):
            recorder.note("e", i=i)
        snapshot = recorder.snapshot()
        assert len(snapshot) == 4
        assert [r["i"] for r in snapshot] == [6, 7, 8, 9]
        assert recorder.events_noted == 10

    def test_render_and_dump(self, tmp_path):
        registry = Registry()
        recorder = FlightRecorder(
            dump_dir=str(tmp_path), registry=registry, name="r1"
        )
        recorder.note("fault", kind_detail="kill")
        path = recorder.dump("unit test!")
        assert path and path in recorder.dumps
        lines = [json.loads(line) for line in open(path)]
        assert lines[0]["kind"] == "flight_dump"
        assert lines[0]["reason"] == "unit test!"
        assert lines[1]["kind"] == "fault"
        assert registry.get(
            "ctpu_flight_dumps_total", {"reason": "unit-test-"}
        ) == 1

    def test_dump_failure_returns_none(self):
        recorder = FlightRecorder(dump_dir="/proc/definitely/not/writable")
        assert recorder.dump("x") is None
        assert recorder.dumps == []

    def test_env_dump_dir(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPU_FLIGHT_DIR", str(tmp_path / "env"))
        recorder = FlightRecorder()
        path = recorder.dump("envtest")
        assert path is not None and str(tmp_path / "env") in path


class TestLatencySketch:
    def test_quantile_is_conservative_bucket_bound(self):
        sketch = LatencySketch()
        for ms in (1.0, 2.0, 3.0, 100.0):
            sketch.observe(ms)
        # p50 lands in the bucket holding 2.0; bound >= the true value
        assert sketch.quantile(0.5) >= 2.0
        assert sketch.quantile(0.5) <= 2.0 * 1.25
        assert sketch.quantile(1.0) >= 100.0

    def test_merge_is_exact(self):
        a, b = LatencySketch(), LatencySketch()
        for ms in (1, 5, 9):
            a.observe(ms)
        for ms in (2, 1000):
            b.observe(ms, error=True)
        merged = a.merged(b)
        assert merged.count == 5
        assert merged.errors == 2
        assert merged.error_rate() == pytest.approx(0.4)
        one_by_one = LatencySketch()
        for ms in (1, 5, 9, 2, 1000):
            one_by_one.observe(ms)
        assert merged.counts == one_by_one.counts

    def test_bounds_cover_serving_range(self):
        assert BOUNDS_MS[0] <= 0.05
        assert BOUNDS_MS[-1] > 10_000  # >10s


class TestSloWatchdog:
    def test_gauges_export_per_model_tenant(self):
        registry = Registry()
        watchdog = SloWatchdog(registry=registry, check_every=1)
        watchdog.observe("m", "gold", 0.010)
        labels = {"model": "m", "tenant": "gold"}
        assert registry.get("ctpu_slo_p99_ms", labels) >= 10.0
        assert registry.get("ctpu_slo_error_rate", labels) == 0.0

    def test_breach_counts_and_dumps_once_per_interval(self, tmp_path):
        registry = Registry()
        flight = FlightRecorder(dump_dir=str(tmp_path))
        watchdog = SloWatchdog(
            objectives={"*": {"p99_ms": 5.0}}, registry=registry,
            flight=flight, min_samples=4, check_every=4,
            dump_interval_s=3600.0,
        )
        for _ in range(16):
            watchdog.observe("m", "", 0.100)  # 100ms >> 5ms objective
        assert watchdog.breaches >= 1
        assert registry.get(
            "ctpu_slo_breaches_total",
            {"model": "m", "tenant": "", "kind": "p99_ms"},
        ) >= 1
        assert len(flight.dumps) == 1  # rate-limited
        breach_notes = [
            r for r in flight.snapshot() if r["kind"] == "slo_breach"
        ]
        assert breach_notes and breach_notes[0]["objective"] == 5.0

    def test_error_rate_objective(self, tmp_path):
        registry = Registry()
        watchdog = SloWatchdog(
            objectives={"m": {"error_rate": 0.05}}, registry=registry,
            min_samples=4, check_every=4,
        )
        for i in range(8):
            watchdog.observe("m", "", 0.001, error=(i % 2 == 0))
        assert registry.get(
            "ctpu_slo_breaches_total",
            {"model": "m", "tenant": "", "kind": "error_rate"},
        ) >= 1

    def test_exact_model_objective_beats_star(self):
        watchdog = SloWatchdog(
            objectives={"*": {"p99_ms": 1.0}, "m": {"p99_ms": 1e9}}
        )
        assert watchdog.objective_for("m") == {"p99_ms": 1e9}
        assert watchdog.objective_for("other") == {"p99_ms": 1.0}

    def test_no_objectives_observe_only(self):
        watchdog = SloWatchdog(registry=Registry(), check_every=1,
                               min_samples=1)
        for _ in range(8):
            watchdog.observe("m", "", 10.0)
        assert watchdog.breaches == 0
        summary = watchdog.summary()
        assert summary["m|"]["count"] == 8
        assert summary["m|"]["breaches"] == 0

    def test_key_cap_bounds_cardinality(self):
        watchdog = SloWatchdog(max_keys=3)
        for i in range(6):
            watchdog.observe(f"m{i}", "", 0.001)
        assert len(watchdog.summary()) == 3
