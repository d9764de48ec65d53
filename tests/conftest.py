"""Test-suite configuration.

Tests run on a virtual 8-device CPU mesh, Pallas kernels interpreted, so
sharding paths compile and execute without TPU hardware (the driver
separately dry-runs the multi-chip path; chip_smoke.py and
benchmark/run.py run on the real chip and do NOT import this).  Must run before jax is imported: jax
reads both variables at import.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # tests run hermetic on CPU
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; the slow tier (make soak) repeats the
    # churn chaos scenario to shake out timing bugs
    config.addinivalue_line(
        "markers", "slow: soak/repetition tests excluded from tier-1"
    )


def pytest_addoption(parser):
    parser.addoption(
        "--lock-witness", action="store_true", default=False,
        help=(
            "wrap every lock/condition constructed under client_tpu/ in "
            "the dynamic lock-order witness and fail any test whose "
            "acquisition graph closes a cycle (TPULINT_LOCK_WITNESS=1 "
            "does the same — the make-soak hookup)"
        ),
    )
    parser.addoption(
        "--race-witness", action="store_true", default=False,
        help=(
            "arm the dynamic RACE witness on top of the lock-order one: "
            "@witness_shared classes run the Eraser lockset algorithm "
            "on every field access against the real held-lock stack; an "
            "unguarded shared write fails the test with both stacks "
            "(TPULINT_RACE_WITNESS=1 does the same — the make-chaos/"
            "make-soak hookup)"
        ),
    )
    parser.addoption(
        "--resource-witness", action="store_true", default=False,
        help=(
            "arm the dynamic resource-leak witness: every registered "
            "acquire/release pair (KvBlockPool alloc/release, endpoint "
            "leases, tracer spans) is tracked in a live-handle table "
            "with acquisition stacks, and a test that ends with live "
            "handles fails at its own teardown with the stacks that "
            "acquired them (TPULINT_RESOURCE_WITNESS=1 does the same — "
            "the make-chaos/make-soak hookup)"
        ),
    )


import pytest  # noqa: E402


def _env_truthy(name):
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "no", "off"
    )


@pytest.fixture(autouse=True)
def _lock_order_witness(request):
    """Opt-in dynamic lock-order witness (see client_tpu.analysis.witness):
    records the acquisition DAG the test actually exercises and fails on a
    cycle — the runtime complement of the static LOCK-INV rule.  With
    --race-witness / TPULINT_RACE_WITNESS=1 the witness is a RaceWitness:
    lock-order duty plus runtime Eraser lockset checks on @witness_shared
    classes (the complement of the static LOCKSET-RACE rule), violations
    dumped to the flight recorder."""
    race = request.config.getoption("--race-witness") or _env_truthy(
        "TPULINT_RACE_WITNESS"
    )
    enabled = race or request.config.getoption(
        "--lock-witness"
    ) or _env_truthy("TPULINT_LOCK_WITNESS")
    if not enabled:
        yield None
        return
    if race:
        from client_tpu.analysis.witness import RaceWitness

        flight = None
        if os.environ.get("TPU_FLIGHT_DIR"):
            from client_tpu.serve.flight import FlightRecorder

            flight = FlightRecorder(name="race-witness")
        witness = RaceWitness(flight=flight)
    else:
        from client_tpu.analysis.witness import LockWitness

        witness = LockWitness()
    with witness.installed():
        yield witness
    witness.assert_acyclic()
    if race:
        witness.assert_race_free()


@pytest.fixture(autouse=True)
def _resource_leak_audit(request):
    """Opt-in dynamic resource-leak audit (the runtime complement of the
    static RESOURCE-LEAK rule): with --resource-witness /
    TPULINT_RESOURCE_WITNESS=1 every registered acquire/release pair is
    patched into a live-handle table, and a test that leaks a KV block
    reservation, endpoint lease or tracer span fails at its own teardown
    with the acquisition stacks of the leaked handles.  Leaks are also
    dumped to the flight recorder when TPU_FLIGHT_DIR is set."""
    enabled = request.config.getoption("--resource-witness") or _env_truthy(
        "TPULINT_RESOURCE_WITNESS"
    )
    if not enabled:
        yield None
        return
    from client_tpu.analysis.witness import ResourceWitness

    flight = None
    if os.environ.get("TPU_FLIGHT_DIR"):
        from client_tpu.serve.flight import FlightRecorder

        flight = FlightRecorder(name="resource-witness")
    witness = ResourceWitness(flight=flight)
    with witness.installed():
        yield witness
    witness.assert_clean()


# Native libraries are build artifacts (gitignored): build them on demand so a
# fresh checkout runs the full suite instead of failing the shm-backed tests.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _lib in (
    "client_tpu/utils/shared_memory/libcshm_tpu.so",
    "client_tpu/utils/tpu_shared_memory/libctpushm.so",
):
    if not os.path.exists(os.path.join(_ROOT, _lib)):
        import subprocess

        subprocess.run(["make", "-C", _ROOT, "native"], check=True,
                       capture_output=True)
        break
