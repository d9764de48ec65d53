"""The bench SLO regression gate (`bench._slo_gate` / `_slo_block`):
round-over-round capacity ratchet semantics, including the zero-capacity
case and the link-drift escape hatch."""

import importlib.util
import os

import pytest


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench",
        os.path.join(os.path.dirname(__file__), "..", "bench.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_regression_past_tolerance_fails(bench):
    gate = bench._slo_gate(
        {"value": 100.0}, {"value": 200.0}, tolerance_pct=20.0
    )
    assert not gate["pass"]
    assert gate["regressions"][0]["key"] == "value"
    assert gate["checked"]["value"] == -50.0


def test_within_tolerance_passes(bench):
    gate = bench._slo_gate({"value": 170.0}, {"value": 200.0})
    assert gate["pass"] and not gate["regressions"]
    assert gate["checked"]["value"] == -15.0


def test_zero_capacity_is_the_loudest_regression(bench):
    """slo_qps_under_p99 drops to exactly 0.0 when the measured p99
    misses the objective — the gate must fire on it, not skip a falsy
    figure."""
    cur = {"slo": {"slo_qps_under_p99": 0.0}}
    prev = {"slo": {"slo_qps_under_p99": 900.0}}
    gate = bench._slo_gate(cur, prev)
    assert not gate["pass"]
    assert gate["regressions"][0]["key"] == "slo_qps_under_p99"
    assert gate["regressions"][0]["delta_pct"] == -100.0


def test_unmeasured_keys_are_skipped(bench):
    gate = bench._slo_gate({"value": None}, {"value": 100.0})
    assert gate["pass"] and "value" not in gate["checked"]
    gate = bench._slo_gate({}, {"value": 100.0})
    assert gate["pass"]


def test_link_drift_skips_with_reason(bench):
    gate = bench._slo_gate(
        {"value": 100.0, "mp_link_drift_pct": -22.0}, {"value": 200.0}
    )
    assert gate["pass"]
    assert "value" in gate["skipped"]
    assert "drift" in gate["skipped"]["value"]


def test_slo_block_zeroes_qps_on_missed_objective(bench, monkeypatch):
    monkeypatch.setenv("BENCH_SLO_P99_MS", "10")
    block = bench._slo_block({"value": 500.0, "p99_ms": 50.0}, {})
    assert block["slo_qps_under_p99"] == 0.0
    block = bench._slo_block({"value": 500.0, "p99_ms": 5.0}, {})
    assert block["slo_qps_under_p99"] == 500.0
    monkeypatch.delenv("BENCH_SLO_P99_MS")
    block = bench._slo_block({"value": 500.0, "p99_ms": 50.0}, {"m|": {}})
    assert block["slo_qps_under_p99"] == 500.0
    assert block["slo_series"] == {"m|": {}}


def test_link_drift_floor_blocks_the_escape_hatch(bench):
    """Sub-millisecond baseline RTTs turn microsecond jitter into huge
    drift percentages — below the 1 ms floor the drift escape hatch
    stays shut and real regressions still fail the gate."""
    gate = bench._slo_gate(
        {"value": 100.0, "mp_link_drift_pct": 143.7, "link_rtt_ms": 0.1},
        {"value": 200.0},
    )
    assert not gate["pass"]
    assert gate["drift_floor_applied"]
    assert not gate["skipped"]
    assert gate["regressions"][0]["key"] == "value"


def test_link_drift_above_floor_still_skips(bench):
    gate = bench._slo_gate(
        {"value": 100.0, "mp_link_drift_pct": -22.0, "link_rtt_ms": 8.0},
        {"value": 200.0},
    )
    assert gate["pass"]
    assert "value" in gate["skipped"]
    assert not gate["drift_floor_applied"]


def test_prof_block_attributes_only_ticked_engines(bench):
    split = {"compute_pct": 60.0, "dispatch_pct": 10.0,
             "host_pct": 25.0, "idle_pct": 5.0}
    report = {"engines": [
        {"engine": "serve", "ticks": 12, "attribution": split},
        {"engine": "lm", "ticks": 0, "attribution": None},
    ]}
    block = bench._prof_block(report, 0.4, "TPU v5 lite")
    assert block["cnn224"] == split
    assert block["lm"] is None          # no ticks -> no made-up split
    assert block["wire"] is None
    assert block["prof_overhead_pct"] == 0.4
    assert block["peak_kind"] == "TPU v5 lite"
    assert abs(sum(split.values()) - 100.0) < 0.5
