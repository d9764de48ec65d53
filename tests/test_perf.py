"""perf harness unit tests on the MockClientBackend — the reference's
doctest+mock test design (SURVEY.md §4: MockClientBackend simulates the load
path with injectable latency/error schedules; managers and profiler are
tested with no server).
"""

import time

import numpy as np
import pytest

from client_tpu.perf import (
    BackendKind,
    ClientBackendFactory,
    ConcurrencyManager,
    CustomLoadManager,
    DataLoader,
    InferenceProfiler,
    MockClientBackend,
    MockStats,
    RequestRateManager,
    SequenceManager,
    create_infer_data_manager,
)
from client_tpu.perf.infer_data import InferDataManager
from client_tpu.perf.load_manager import RequestRecord
from client_tpu.utils import InferenceServerException

META = [{"name": "INPUT0", "datatype": "FP32", "shape": [1, 4]}]
OUT_META = [{"name": "OUTPUT0", "datatype": "FP32", "shape": [1, 4]}]


def _write_self_signed_cert(path):
    """Emit a throwaway self-signed cert PEM (openssl CLI ships in-image)."""
    import subprocess

    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(path) + ".key", "-out", str(path),
         "-days", "1", "-subj", "/CN=localhost"],
        check=True, capture_output=True,
    )


def _mk_manager(cls, stats=None, latency_s=0.0, error_schedule=None, **kwargs):
    stats = stats or MockStats()

    def factory():
        return MockClientBackend(
            latency_s=latency_s, error_schedule=error_schedule, stats=stats
        )

    loader = DataLoader(META)
    loader.generate_data()
    dm = create_infer_data_manager(factory(), loader, META, OUT_META)
    dm.init()
    mgr = cls(
        backend_factory=factory,
        data_loader=loader,
        data_manager=dm,
        model_name="mock",
        **kwargs,
    )
    return mgr, stats


class TestDataLoader:
    def test_generate_random(self):
        loader = DataLoader(META)
        loader.generate_data()
        arr = loader.get_input_data(0, 0)["INPUT0"].array
        assert arr.shape == (1, 4) and arr.dtype == np.float32

    def test_generate_zero(self):
        loader = DataLoader(META)
        loader.generate_data(zero_data=True)
        assert not loader.get_input_data(0, 0)["INPUT0"].array.any()

    def test_dynamic_batch_dim_uses_batch_size(self):
        loader = DataLoader(
            [{"name": "X", "datatype": "FP32", "shape": [-1, 4]}], batch_size=3
        )
        loader.generate_data()
        assert loader.get_input_data(0, 0)["X"].array.shape == (3, 4)

    def test_dynamic_non_batch_dim_requires_override(self):
        loader = DataLoader([{"name": "X", "datatype": "FP32", "shape": [1, -1]}])
        with pytest.raises(InferenceServerException, match="dynamic"):
            loader.generate_data()

    def test_shape_override(self):
        loader = DataLoader(
            [{"name": "X", "datatype": "FP32", "shape": [-1, 4]}],
            shape_overrides={"X": [2, 4]},
        )
        loader.generate_data()
        assert loader.get_input_data(0, 0)["X"].array.shape == (2, 4)

    def test_json_streams_and_validation(self):
        doc = {
            "data": [
                [{"INPUT0": [1.0, 2.0, 3.0, 4.0]}],
                [{"INPUT0": {"content": [5.0, 6.0, 7.0, 8.0], "shape": [1, 4]}}],
            ],
            "validation_data": [
                [{"OUTPUT0": [1.0, 2.0, 3.0, 4.0]}],
                [{"OUTPUT0": [5.0, 6.0, 7.0, 8.0]}],
            ],
        }
        loader = DataLoader(META)
        loader.read_data_from_json(doc)
        assert loader.num_streams == 2
        np.testing.assert_allclose(
            loader.get_input_data(0, 0)["INPUT0"].array.flatten(),
            [1, 2, 3, 4],
        )
        assert loader.get_expected_outputs(1, 0)["OUTPUT0"].array.size == 4

    def test_prefix_share_generation(self):
        """--prefix-share workload shape: num_prompts streams whose token
        input shares its leading FRAC with one of shared_pool prefixes,
        scalar INT inputs pinned to a sane budget, values in-vocab."""
        meta = [
            {"name": "TOKENS", "datatype": "INT32", "shape": [32]},
            {"name": "MAX_TOKENS", "datatype": "INT32", "shape": [1]},
        ]
        loader = DataLoader(meta)
        loader.generate_prefix_share(0.75, num_prompts=8, shared_pool=2)
        assert loader.num_streams == 8
        rows = [loader.get_input_data(i, 0)["TOKENS"].array.reshape(-1)
                for i in range(8)]
        prefix_len = int(round(0.75 * 32))
        for i, row in enumerate(rows):
            assert row.shape == (32,)
            assert row.min() >= 1 and row.max() < 256  # byte-vocab safe
            # same pool slot -> identical prefix
            np.testing.assert_array_equal(
                row[:prefix_len], rows[i % 2][:prefix_len]
            )
        # the two pools differ, and tails are (overwhelmingly) unique
        assert not np.array_equal(rows[0][:prefix_len],
                                  rows[1][:prefix_len])
        budgets = {int(loader.get_input_data(i, 0)["MAX_TOKENS"]
                       .array.reshape(-1)[0]) for i in range(8)}
        assert budgets == {16}  # pinned, never a random negative

    def test_prefix_share_needs_token_input_and_valid_share(self):
        loader = DataLoader(META)  # FP32 only: nothing to build prompts in
        with pytest.raises(InferenceServerException):
            loader.generate_prefix_share(0.5)
        loader2 = DataLoader(
            [{"name": "TOKENS", "datatype": "INT32", "shape": [8]}]
        )
        with pytest.raises(InferenceServerException):
            loader2.generate_prefix_share(1.5)

    def test_bytes_generation(self):
        loader = DataLoader([{"name": "S", "datatype": "BYTES", "shape": [2]}])
        loader.generate_data(string_length=5)
        arr = loader.get_input_data(0, 0)["S"].array
        assert arr.dtype == np.object_ and len(arr[0]) == 5


class TestSequenceManager:
    def test_id_allocation_and_wraparound(self):
        sm = SequenceManager(start_sequence_id=10, sequence_id_range=3,
                             sequence_length=2, sequence_length_specified=True)
        ids = [sm.begin_sequence(slot).seq_id for slot in range(4)]
        assert ids == [10, 11, 12, 10]

    def test_advance_flags(self):
        sm = SequenceManager(sequence_length=3, sequence_length_specified=True)
        st = sm.begin_sequence(0)
        flags = [sm.advance(st) for _ in range(3)]
        assert flags == [(True, False), (False, False), (False, True)]

    def test_length_variation_bounds(self):
        sm = SequenceManager(sequence_length=100,
                             sequence_length_variation=20,
                             sequence_length_specified=True)
        lengths = {sm.begin_sequence(i).remaining_queries for i in range(50)}
        assert all(80 <= n <= 120 for n in lengths)
        assert len(lengths) > 1


class TestConcurrencyManager:
    def test_workers_send_requests(self):
        mgr, stats = _mk_manager(ConcurrencyManager)
        try:
            mgr.change_concurrency_level(4)
            time.sleep(0.3)
            records = mgr.swap_timestamps()
            assert len(records) > 50
            assert stats.num_infer_calls > 50
            assert mgr.get_and_reset_num_sent() > 0
        finally:
            mgr.cleanup()

    def test_records_survive_stop_workers(self):
        # profile_completion stops workers (quiescing sends before the output
        # drain) and only then swaps timestamps; stopping must not discard the
        # window's records with the thread list.
        mgr, _ = _mk_manager(ConcurrencyManager)
        try:
            mgr.change_concurrency_level(4)
            time.sleep(0.3)
            mgr.stop_workers()
            records = mgr.swap_timestamps()
            assert len(records) > 50
            assert mgr.swap_timestamps() == []  # drained exactly once
        finally:
            mgr.cleanup()

    def test_reconfigure_threads(self):
        mgr, _ = _mk_manager(ConcurrencyManager)
        try:
            mgr.change_concurrency_level(2)
            assert len(mgr._threads) == 2
            mgr.change_concurrency_level(6)
            assert len(mgr._threads) == 6
        finally:
            mgr.cleanup()

    def test_request_errors_counted_not_fatal(self):
        mgr, _ = _mk_manager(
            ConcurrencyManager, error_schedule=[True] * 500_000
        )
        try:
            mgr.change_concurrency_level(1)
            time.sleep(0.2)
            mgr.check_health()  # per-request failures never abort the run
            records = mgr.swap_timestamps()
            assert records and all(not r.ok for r in records)
        finally:
            mgr.cleanup()

    def test_concurrency_beyond_max_threads_refused(self):
        mgr, _ = _mk_manager(ConcurrencyManager, max_threads=2)
        try:
            with pytest.raises(InferenceServerException, match="max-threads"):
                mgr.change_concurrency_level(3)
        finally:
            mgr.cleanup()

    def test_sequences_have_correlation_ids(self):
        stats = MockStats()
        sm = SequenceManager(sequence_length=4, sequence_length_specified=True)
        mgr, stats = _mk_manager(
            ConcurrencyManager, stats=stats, sequence_manager=sm
        )
        try:
            mgr.change_concurrency_level(2)
            time.sleep(0.3)
        finally:
            mgr.cleanup()
        assert stats.sequence_ids
        # two slots -> at most two distinct live sequences at any moment,
        # and ids keep increasing as sequences retire
        assert len(set(stats.sequence_ids)) >= 2


class TestRequestRateManager:
    def test_constant_rate(self):
        mgr, stats = _mk_manager(RequestRateManager)
        try:
            mgr.change_request_rate(200)
            time.sleep(1.0)
            n = stats.num_infer_calls
            assert 120 <= n <= 280, n
        finally:
            mgr.cleanup()

    def test_poisson_schedule_distribution(self):
        mgr, _ = _mk_manager(RequestRateManager, distribution="poisson")
        gaps = mgr._make_schedule(100, horizon=10000)
        mean = float(np.mean(gaps))
        assert 0.8 * 1e7 < mean < 1.2 * 1e7
        assert np.std(gaps.astype(float)) > 0.5 * mean  # exponential-ish

    def test_delayed_flagging(self):
        # schedule far faster than the mock latency can sustain
        mgr, _ = _mk_manager(RequestRateManager, latency_s=0.05)
        try:
            mgr.change_request_rate(500, num_threads=2)
            time.sleep(0.5)
            records = mgr.swap_timestamps()
            assert any(r.delayed for r in records)
        finally:
            mgr.cleanup()


class TestCustomLoadManager:
    def test_replays_intervals(self, tmp_path):
        path = tmp_path / "intervals.txt"
        path.write_text("\n".join(["5000000"] * 100))  # 5ms gaps
        mgr, stats = _mk_manager(CustomLoadManager, intervals_file=str(path))
        try:
            mgr.start(num_threads=2)
            time.sleep(0.5)
            assert 50 <= stats.num_infer_calls <= 140
        finally:
            mgr.cleanup()


class _FakeManager:
    """Deterministic manager stand-in for profiler-only tests."""

    model_name = "mock"

    def __init__(self, schedule):
        # schedule: list of lists of (latency_ns, ok) generated per window
        self._schedule = list(schedule)
        self._sent = 0

    def get_and_reset_num_sent(self):
        n = self._sent
        self._sent = 0
        return n

    def swap_timestamps(self):
        if not self._schedule:
            return []
        batch = self._schedule.pop(0)
        now = time.monotonic_ns()
        recs = []
        for lat_ns, ok in batch:
            recs.append(RequestRecord(now - lat_ns, now, ok))
        self._sent += len(batch)
        return recs

    def check_health(self):
        pass


class TestProfiler:
    def _profiler(self, schedule, **kwargs):
        kwargs.setdefault("measurement_window_s", 0.02)
        return InferenceProfiler(_FakeManager(schedule), **kwargs)

    def test_stable_after_three_windows(self):
        window = [(1_000_000, True)] * 20
        prof = self._profiler([window] * 5)
        status = prof.profile_level("concurrency", 1)
        assert status.stable
        assert status.completed_requests == 60  # exactly 3 stable windows
        assert abs(status.latency_avg_us - 1000) < 1

    def test_unstable_without_convergence(self):
        # throughput alternates wildly -> never stable
        schedule = [
            [(1_000_000, True)] * (5 if i % 2 else 100) for i in range(10)
        ]
        prof = self._profiler(schedule, max_trials=6)
        status = prof.profile_level("concurrency", 1)
        assert not status.stable

    def test_window_clipping_drops_stale_requests(self):
        prof = self._profiler([])
        mgr = prof.manager
        t0 = time.monotonic_ns()

        class _Mgr(_FakeManager):
            def swap_timestamps(self):
                # one record finished long before the window opened
                return [RequestRecord(t0 - 10**12, t0 - 10**11, True)]

        prof.manager = _Mgr([])
        m = prof.measure()
        assert m.throughput == 0

    def test_errors_counted(self):
        window = [(1_000_000, True)] * 10 + [(1_000_000, False)] * 3
        prof = self._profiler([window] * 10)
        status = prof.profile_level("concurrency", 1)
        assert status.error_count == 9  # 3 per window

    def test_request_rate_binary_probes_start(self):
        """Bisection midpoints never reach lo, so `start` gets its own
        explicit probe: a capacity at/just above start must report start
        as the best passing rate, not 'SLO violated everywhere'."""

        class _RateMgr(_FakeManager):
            def __init__(self):
                super().__init__([])
                self.rate = None

            def change_request_rate(self, r):
                self.rate = r

            def swap_timestamps(self):
                now = time.monotonic_ns()
                lat = 1_000_000 if self.rate <= 60 else 50_000_000
                self._sent += 20
                return [RequestRecord(now - lat, now, True)
                        for _ in range(20)]

        prof = InferenceProfiler(_RateMgr(), measurement_window_s=0.02)
        results, best = prof.profile_request_rate_binary(50, 400, 10_000)
        assert best is not None
        assert best.level_value == 50
        # and an SLO no rate meets still reports None (start probed+failed)
        prof2 = InferenceProfiler(_RateMgr(), measurement_window_s=0.02)
        _, none_best = prof2.profile_request_rate_binary(50, 400, 1)
        assert none_best is None

    def test_percentiles_monotone(self):
        lats = [(int(n), True) for n in np.linspace(1e6, 9e6, 50)]
        prof = self._profiler([lats] * 10)
        status = prof.profile_level("concurrency", 1)
        p = status.percentiles_us
        assert p[50] <= p[90] <= p[95] <= p[99]


class TestEndToEndInprocess:
    """Full harness against the real in-process engine (no sockets)."""

    def test_concurrency_sweep(self, capsys):
        from client_tpu.perf.__main__ import main

        rc = main([
            "-m", "simple", "--hermetic",
            "--concurrency-range", "1:2",
            "--measurement-interval", "100",
            "--max-trials", "4",
            "-s", "50",
        ])
        out = capsys.readouterr().out
        assert "Concurrency: 1" in out
        assert "Concurrency: 2" in out
        assert "infer/sec" in out
        assert rc == 0

    def test_csv_export(self, tmp_path, capsys):
        from client_tpu.perf.__main__ import main

        csv_path = tmp_path / "report.csv"
        rc = main([
            "-m", "simple", "--hermetic",
            "--concurrency-range", "1",
            "--measurement-interval", "100",
            "--max-trials", "3",
            "-s", "90",
            "-f", str(csv_path),
        ])
        assert rc == 0
        lines = csv_path.read_text().strip().splitlines()
        assert len(lines) == 2
        assert lines[0].startswith("Level,Inferences/Second")

    def test_prefix_share_sweep_reports_columns(self, tmp_path, capsys):
        """--prefix-share drives the rotated shared-prefix workload and
        lands the per-sweep prefix columns in summary + CSV + JSON (the
        builtin simple model has no prefix cache, so the numbers are 0 —
        the LM savings themselves are asserted at engine level in
        tests/test_lm.py, where CPU-speed models make it cheap)."""
        import json

        from client_tpu.perf.__main__ import main

        csv_path = tmp_path / "prefix.csv"
        json_path = tmp_path / "prefix.json"
        rc = main([
            "-m", "simple", "--hermetic",
            "--prefix-share", "0.8", "--prefix-pool", "2",
            "--prefix-prompts", "6",
            "--concurrency-range", "2",
            "--measurement-interval", "100",
            "--max-trials", "3",
            "-s", "90",
            "-f", str(csv_path),
            "--json-export", str(json_path),
        ])
        assert rc == 0
        out = capsys.readouterr().out
        assert "prefix cache:" in out
        header = csv_path.read_text().splitlines()[0]
        assert "Prefix Hit %" in header
        assert "Prefill Tokens Saved %" in header
        doc = json.loads(json_path.read_text())
        rec = doc["results"][0]["lm_prefix"]
        assert set(rec) >= {"prefix_hit_pct", "prefill_tokens_saved_pct"}

    def test_prefix_share_rejects_custom_input_data(self):
        from client_tpu.perf.__main__ import main

        with pytest.raises(SystemExit):
            main([
                "-m", "simple", "--hermetic",
                "--prefix-share", "0.5", "--input-data", "zero",
                "--concurrency-range", "1",
            ])

    def test_trace_options_applied_hermetic(self, capsys):
        """--trace-* flags reach the engine's trace-settings control plane."""
        from client_tpu.perf.__main__ import main

        rc = main([
            "-m", "simple", "--hermetic",
            "--concurrency-range", "1",
            "--measurement-interval", "100",
            "--max-trials", "3",
            "-s", "90",
            "--trace-level", "TIMESTAMPS",
            "--trace-rate", "500",
            "--trace-count", "100",
            "--log-frequency", "50",
            "-v",
        ])
        assert rc == 0
        err = capsys.readouterr().err
        assert "trace settings applied" in err
        assert "'trace_rate': '500'" in err

    def test_ssl_options_reach_clients(self, monkeypatch):
        """ssl_options build SSL-configured clients (no connect needed:
        channel/pool construction is lazy)."""
        import grpc as grpc_mod

        from client_tpu.perf.client_backend import (
            BackendKind,
            ClientBackendFactory,
        )

        secure_calls = []
        real_secure = grpc_mod.secure_channel
        monkeypatch.setattr(
            grpc_mod, "secure_channel",
            lambda url, creds, options=None: secure_calls.append(url)
            or real_secure(url, creds, options=options),
        )
        grpc_be = ClientBackendFactory.create(
            BackendKind.TRITON_GRPC, url="localhost:1",
            ssl_options={"use_ssl": True},
        )
        assert secure_calls == ["localhost:1"]  # SSL path, not insecure
        grpc_be.close()

        http_be = ClientBackendFactory.create(
            BackendKind.TRITON_HTTP, url="localhost:1",
            ssl_options={"use_ssl": True, "verify_peer": False},
        )
        assert http_be._client._base_url.startswith("https://")
        http_be.close()

    def test_ssl_http_ca_with_verify_peer_off(self, tmp_path):
        """A CA file + verify_peer=0 must build a non-verifying context, not
        a context urllib3 will reject at connect time."""
        import ssl as ssl_mod

        from client_tpu.perf.client_backend import (
            BackendKind,
            ClientBackendFactory,
        )

        # self-signed CA stand-in: any PEM-loadable cert would do, but the
        # context is built with cafile=... so write a real self-signed cert
        pem = tmp_path / "ca.pem"
        _write_self_signed_cert(pem)
        be = ClientBackendFactory.create(
            BackendKind.TRITON_HTTP, url="localhost:1",
            ssl_options={
                "use_ssl": True,
                "verify_peer": False,
                "ca_certificates_file": str(pem),
            },
        )
        ctx = be._client._pool.connection_pool_kw.get("ssl_context")
        assert ctx is not None
        assert ctx.check_hostname is False
        assert ctx.verify_mode == ssl_mod.CERT_NONE
        be.close()

    def test_trace_unsupported_on_non_kserve(self):
        from client_tpu.perf.client_backend import MockClientBackend
        from client_tpu.utils import InferenceServerException

        with pytest.raises(InferenceServerException, match="trace settings"):
            MockClientBackend().update_trace_settings(settings={"trace_rate": "1"})

    def test_request_rate_mode(self, capsys):
        from client_tpu.perf.__main__ import main

        rc = main([
            "-m", "simple", "--hermetic",
            "--request-rate-range", "100",
            "--request-distribution", "poisson",
            "--measurement-interval", "200",
            "--max-trials", "3",
            "-s", "90",
        ])
        out = capsys.readouterr().out
        assert "Request Rate: 100" in out
        assert rc == 0

    def test_request_rate_binary_search_finds_slo_rate(self, capsys):
        """--binary-search + --request-rate-range + -l: SLO-seeking
        bisection over REQUEST RATE (the capacity-planning search;
        profile_concurrency_binary only answers the closed-loop
        question) — converges to a passing rate under a generous SLO."""
        from client_tpu.perf.__main__ import main

        rc = main([
            "-m", "simple", "--hermetic",
            "--request-rate-range", "50:400",
            "--binary-search",
            "-l", "500",  # msec; hermetic latencies are ~0.2 ms
            "--measurement-interval", "100",
            "--max-trials", "3",
            "-s", "90",
        ])
        out = capsys.readouterr().out
        assert "Max sustainable rate under SLO" in out
        assert rc == 0

    def test_request_rate_binary_search_slo_unmeetable(self, capsys):
        """An SLO below any achievable latency reports no passing rate
        (best=None) instead of fabricating one."""
        from client_tpu.perf.__main__ import main

        rc = main([
            "-m", "simple", "--hermetic",
            "--request-rate-range", "50:200",
            "--binary-search",
            "-l", "0.000001",
            "--measurement-interval", "100",
            "--max-trials", "3",
            "-s", "90",
        ])
        out = capsys.readouterr().out
        assert "SLO violated at every probed rate" in out
        assert rc == 0

    def test_json_export_per_sweep_point(self, tmp_path, capsys):
        """--json-export writes one full record per sweep point (all
        percentiles + server stats deltas — the fields the flat CSV
        cannot hold) alongside the CSV."""
        import json

        from client_tpu.perf.__main__ import main

        json_path = tmp_path / "report.json"
        csv_path = tmp_path / "report.csv"
        rc = main([
            "-m", "simple", "--hermetic",
            "--concurrency-range", "1:2",
            "--measurement-interval", "100",
            "--max-trials", "3",
            "-s", "90",
            "-f", str(csv_path),
            "--json-export", str(json_path),
        ])
        assert rc == 0
        doc = json.loads(json_path.read_text())
        assert len(doc["results"]) == 2
        for rec in doc["results"]:
            assert rec["level_label"] == "concurrency"
            assert rec["throughput_infer_per_sec"] > 0
            assert set(rec["percentiles_us"]) == {"50", "90", "95", "99"}
            assert "server_stats" in rec and "per_tenant" in rec
        # CSV rode along untouched
        assert csv_path.read_text().startswith("Level,Inferences/Second")


class TestValidation:
    def test_validation_data_marks_mismatches(self):
        """validation_data wiring: wrong expected output -> records not ok."""
        from client_tpu.perf import BackendKind, ClientBackendFactory
        from client_tpu.serve import InferenceEngine
        from client_tpu.serve.builtins import default_models

        engine = InferenceEngine(default_models())
        backend = ClientBackendFactory.create(BackendKind.INPROCESS, engine=engine)
        loader = DataLoader(
            [
                {"name": "INPUT0", "datatype": "INT32", "shape": [1, 16]},
                {"name": "INPUT1", "datatype": "INT32", "shape": [1, 16]},
            ]
        )
        ones = [1] * 16
        doc = {
            "data": [[{"INPUT0": ones, "INPUT1": ones}]],
            "validation_data": [[{"OUTPUT0": [2] * 16}]],  # correct sum
        }
        loader.read_data_from_json(doc)
        out_meta = [{"name": "OUTPUT0", "datatype": "INT32", "shape": [1, 16]}]
        dm = create_infer_data_manager(backend, loader, loader._inputs, out_meta)
        dm.init()
        mgr = ConcurrencyManager(
            backend_factory=lambda: backend, data_loader=loader,
            data_manager=dm, model_name="simple",
        )
        try:
            mgr.change_concurrency_level(1)
            time.sleep(0.2)
            records = mgr.swap_timestamps()
            assert records and all(r.ok for r in records)
        finally:
            mgr.stop_workers()
        # now poison the expectation -> every request flagged failed
        loader.expected_outputs[0][0]["OUTPUT0"].array[:] = 99
        mgr2 = ConcurrencyManager(
            backend_factory=lambda: backend, data_loader=loader,
            data_manager=dm, model_name="simple",
        )
        try:
            mgr2.change_concurrency_level(1)
            time.sleep(0.2)
            records = mgr2.swap_timestamps()
            assert records and all(not r.ok for r in records)
        finally:
            mgr2.cleanup()
            engine.close()


class TestCountWindows:
    """count_windows measurement mode (reference --measurement-mode
    count_windows, MeasureForCountWindows)."""

    def _live_manager(self, latency_s=0.001):
        return _mk_manager(ConcurrencyManager, latency_s=latency_s)

    def test_window_closes_on_request_count(self):
        mgr, _ = self._live_manager()
        try:
            mgr.change_concurrency_level(2)
            prof = InferenceProfiler(
                mgr, measurement_window_s=5.0,  # time mode would take 5s
                measurement_mode="count_windows",
                measurement_request_count=30,
            )
            t0 = time.monotonic()
            m = prof.measure()
            elapsed = time.monotonic() - t0
            # closed by count, far before the 5s time window
            assert elapsed < 2.5
            assert m.latencies_ns.size >= 30
        finally:
            mgr.cleanup()

    def test_stalled_server_hits_time_cap_not_hang(self):
        prof = InferenceProfiler(
            _FakeManager([]),  # never produces records
            measurement_window_s=0.02,
            measurement_mode="count_windows",
            measurement_request_count=1000,
        )
        t0 = time.monotonic()
        m = prof.measure()
        assert time.monotonic() - t0 < 2.0  # 10x window cap
        assert m.throughput == 0

    def test_bad_mode_rejected(self):
        with pytest.raises(InferenceServerException, match="measurement mode"):
            InferenceProfiler(_FakeManager([]), measurement_mode="bogus")


class TestOverheadAccounting:
    def test_overhead_reflects_idle_slot_time(self):
        # 1ms mock latency, 2 slots: workers spend nearly all slot time
        # inside requests -> low overhead; assert it is computed and sane.
        mgr, _ = _mk_manager(ConcurrencyManager, latency_s=0.001)
        try:
            mgr.change_concurrency_level(2)
            prof = InferenceProfiler(
                mgr, measurement_window_s=0.2, max_trials=3,
                stability_threshold=5.0,
            )
            status = prof.profile_level("concurrency", 2)
            assert 0.0 <= status.overhead_pct < 60.0
        finally:
            mgr.cleanup()


class TestEnsemble:
    def test_engine_runs_config_driven_ensemble(self):
        from client_tpu.serve import InferenceEngine
        from client_tpu.serve.builtins import default_models

        engine = InferenceEngine(default_models())
        try:
            a = np.arange(16, dtype=np.int32).reshape(1, 16)
            b = np.ones((1, 16), dtype=np.int32)
            request = {
                "id": "e1",
                "inputs": [
                    {"name": "INPUT0", "datatype": "INT32", "shape": [1, 16],
                     "data": a.flatten().tolist()},
                    {"name": "INPUT1", "datatype": "INT32", "shape": [1, 16],
                     "data": b.flatten().tolist()},
                ],
            }
            response, blobs = engine.execute("simple_ensemble", "", request, b"")
            outs = {o["name"]: o for o in response["outputs"]}
            assert outs["OUTPUT0"]["data"] == (a + b).flatten().tolist()
            assert outs["OUTPUT1"]["data"] == (a - b).flatten().tolist()
            # composing models carry their own statistics
            stats = {
                s["name"]: s for s in engine.statistics("", "")
            }
            assert stats["simple"]["inference_stats"]["success"]["count"] >= 1
            assert (
                stats["identity_int32"]["inference_stats"]["success"]["count"]
                >= 2
            )
            cfg = engine.get_model("simple_ensemble", "").config()
            step_models = [
                s["model_name"] for s in cfg["ensemble_scheduling"]["step"]
            ]
            assert step_models == ["simple", "identity_int32", "identity_int32"]
        finally:
            engine.close()

    def test_profiler_recurses_composing_stats(self):
        from client_tpu.perf.client_backend import BackendKind, ClientBackendFactory
        from client_tpu.perf import create_infer_data_manager
        from client_tpu.serve import InferenceEngine
        from client_tpu.serve.builtins import default_models

        engine = InferenceEngine(default_models())
        try:
            def factory():
                return ClientBackendFactory.create(
                    BackendKind.INPROCESS, engine=engine
                )

            be = factory()
            meta = be.model_metadata("simple_ensemble")
            inputs_meta = [dict(m) for m in meta["inputs"]]
            for m in inputs_meta:
                m["shape"] = [1, 16]
            loader = DataLoader(inputs_meta, batch_size=1)
            loader.generate_data()
            dm = create_infer_data_manager(
                be, loader, inputs_meta, [dict(m) for m in meta["outputs"]],
                shared_memory="none",
            )
            dm.init()
            mgr = ConcurrencyManager(
                backend_factory=factory, data_loader=loader, data_manager=dm,
                model_name="simple_ensemble", max_threads=2,
            )
            prof = InferenceProfiler(
                mgr, backend=be, measurement_window_s=0.1, max_trials=3,
                stability_threshold=5.0,
            )
            try:
                results = prof.profile_concurrency_range(1, 1, 1)
                ens = results[0].ensemble_stats
                assert set(ens) == {"simple", "identity_int32"}
                assert ens["simple"]["success_count"] > 0
                assert ens["identity_int32"]["success_count"] > 0
            finally:
                mgr.cleanup()
        finally:
            engine.close()


class TestModelParser:
    """ModelParser normalization (reference model_parser.h:59-193)."""

    def _parser(self, name):
        from client_tpu.perf import ModelParser
        from client_tpu.perf.client_backend import BackendKind, ClientBackendFactory
        from client_tpu.serve import InferenceEngine
        from client_tpu.serve.builtins import default_models

        engine = InferenceEngine(default_models())
        be = ClientBackendFactory.create(BackendKind.INPROCESS, engine=engine)
        try:
            return ModelParser.create(be, name, batch_size=2)
        finally:
            engine.close()

    def test_dynamic_dims_resolved_and_batch_size(self):
        p = self._parser("simple")
        assert p.inputs[0]["shape"] == [2, 16]  # -1 -> batch_size
        assert p.max_batch_size == 8

    def test_scheduler_kinds(self):
        from client_tpu.perf import SchedulerType

        assert self._parser("simple").scheduler_type == SchedulerType.NONE
        assert (
            self._parser("simple_sequence").scheduler_type
            == SchedulerType.SEQUENCE
        )
        ens = self._parser("simple_ensemble")
        assert ens.scheduler_type == SchedulerType.ENSEMBLE
        assert ens.composing_models == ["simple", "identity_int32"]
        assert self._parser("simple_sequence").requires_sequence_flags()

    def test_decoupled_flag(self):
        assert self._parser("repeat_int32").is_decoupled
        assert not self._parser("simple").is_decoupled


def test_nested_ensemble_recurses():
    from client_tpu.serve import InferenceEngine
    from client_tpu.serve.builtins import default_models, ensemble_model
    from client_tpu.serve.model_runtime import Model, TensorSpec

    outer = Model(
        "outer_ensemble",
        inputs=[
            TensorSpec("INPUT0", "INT32", [-1, 16]),
            TensorSpec("INPUT1", "INT32", [-1, 16]),
        ],
        outputs=[TensorSpec("OUTPUT0", "INT32", [-1, 16])],
        fn=None,
        platform="ensemble",
        ensemble_steps=[
            {
                "model_name": "simple_ensemble",  # nested ensemble step
                "input_map": {"INPUT0": "INPUT0", "INPUT1": "INPUT1"},
                "output_map": {"OUTPUT0": "OUTPUT0"},
            },
        ],
    )
    engine = InferenceEngine(default_models() + [outer])
    try:
        a = np.arange(16, dtype=np.int32).reshape(1, 16)
        b = np.full((1, 16), 2, dtype=np.int32)
        request = {
            "id": "n1",
            "inputs": [
                {"name": "INPUT0", "datatype": "INT32", "shape": [1, 16],
                 "data": a.flatten().tolist()},
                {"name": "INPUT1", "datatype": "INT32", "shape": [1, 16],
                 "data": b.flatten().tolist()},
            ],
        }
        response, _ = engine.execute("outer_ensemble", "", request, b"")
        outs = {o["name"]: o for o in response["outputs"]}
        assert outs["OUTPUT0"]["data"] == (a + b).flatten().tolist()
    finally:
        engine.close()


class TestProcPool:
    """Multi-process load generation (client_tpu.perf.procpool) — the
    GIL-sidestep analog of the reference's native multi-worker perf_analyzer
    (perf_analyzer.cc:56-424)."""

    def test_multiproc_wire_load(self):
        from client_tpu.serve import Server
        from client_tpu.perf.procpool import run_completion_multiproc

        with Server(grpc_port=0) as server:
            res = run_completion_multiproc(
                server.grpc_address, "simple",
                processes=2, concurrency=4,
                window_s=1.0, warmup_s=0.2,
                spec={"mode": "wire"},
            )
            assert res.processes == 2
            assert res.error_count == 0
            assert res.completed_requests > 0
            assert res.throughput > 0
            assert 50 in res.percentiles_us
            # a load worker never opens a device: the chip is the server's
            assert res.worker_backends == [[], []]

    def test_multiproc_worker_error_reported(self):
        from client_tpu.perf.procpool import run_completion_multiproc

        with pytest.raises(InferenceServerException, match="load worker"):
            run_completion_multiproc(
                "127.0.0.1:1", "nope", processes=1, concurrency=1,
                window_s=0.2, warmup_s=0.0, spec={"mode": "wire"},
                start_timeout_s=30,
            )

    def test_preregistered_shm_specs(self):
        """Region-by-name referencing: a worker-side data manager builds
        region-referencing requests without creating regions (no jax)."""
        from client_tpu.perf.procpool import (
            PreRegisteredShmInferDataManager,
            ShapeOnlyLoader,
        )

        class _FakeInput:
            def __init__(self, name, shape, datatype):
                self.name, self.shape, self.datatype = name, shape, datatype

            def set_shared_memory(self, region, nbytes, offset=0):
                self.region, self.nbytes = region, nbytes

        class _FakeOut:
            def __init__(self, name):
                self.name = name

            def set_shared_memory(self, region, nbytes, offset=0):
                self.region = region

        class _FakeBackend:
            infer_input_cls = _FakeInput
            requested_output_cls = _FakeOut

        mgr = PreRegisteredShmInferDataManager(
            _FakeBackend(),
            {(0, 0): [("IN", [1, 4], "FP32", "region_in", 16)]},
            [("OUT", "region_out", 16)],
        )
        mgr.init()
        data = mgr.get_infer_data(0, 0)
        assert data.inputs[0].region == "region_in"
        assert data.outputs[0].region == "region_out"
        loader = ShapeOnlyLoader(1, [1])
        assert loader.num_steps(0) == 1
        assert loader.get_expected_outputs(0, 0) == {}


class TestAsyncConcurrencyManager:
    """Async InferContext slots over grpc.aio (reference -a/--async)."""

    def test_async_slots_drive_requests(self):
        from client_tpu.perf.load_manager import AsyncConcurrencyManager
        from client_tpu.serve import Server

        with Server(grpc_port=0) as server:
            control = ClientBackendFactory.create(
                BackendKind.TRITON_GRPC, url=server.grpc_address
            )
            meta = control.model_metadata("simple")
            inputs_meta = [
                {"name": m["name"], "datatype": m["datatype"],
                 "shape": [1 if d == -1 else d for d in m["shape"]]}
                for m in meta["inputs"]
            ]
            outputs_meta = [dict(m) for m in meta["outputs"]]
            loader = DataLoader(inputs_meta, batch_size=1)
            loader.generate_data()
            mgr_dm = InferDataManager(
                control, loader, inputs_meta, outputs_meta
            )
            mgr_dm.init()
            manager = AsyncConcurrencyManager(
                url=server.grpc_address,
                data_loader=loader,
                data_manager=mgr_dm,
                model_name="simple",
                max_threads=16,
            )
            try:
                manager.change_concurrency_level(8)
                time.sleep(1.0)
                manager.check_health()
                records = manager.swap_timestamps()
                assert len(records) > 8
                assert all(r.ok for r in records)
                # reconfigure to a lower level works (slot teardown + restart)
                manager.change_concurrency_level(2)
                time.sleep(0.4)
                assert manager.get_and_reset_num_sent() > 0
            finally:
                manager.cleanup()
            control.close()

    def test_cli_async_mode(self):
        import subprocess
        import sys

        from client_tpu.serve import Server

        with Server(grpc_port=0) as server:
            proc = subprocess.run(
                [sys.executable, "-m", "client_tpu.perf", "-m", "simple",
                 "-u", server.grpc_address, "-i", "grpc", "--async",
                 "--concurrency-range", "4:4:1",
                 "--measurement-interval", "500", "--max-trials", "4"],
                capture_output=True, text=True, timeout=120,
            )
            assert proc.returncode == 0, proc.stdout + proc.stderr
            assert "Best: concurrency=" in proc.stdout
