"""Every example is an acceptance test (the reference treats its examples
corpus as the de-facto acceptance suite — reference src/python/examples/*,
SURVEY §2.5): run each against one shared in-process server over real
sockets and require its PASS line."""

import os
import subprocess
import sys

import pytest

from client_tpu.serve import Server

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLES = os.path.join(_REPO, "examples")

# example -> which address it takes (grpc/http).  Excludes only the
# interactive / special-setup ones covered elsewhere (image_client's file
# inputs, llm_streaming's language model set).
GRPC_EXAMPLES = [
    "simple_grpc_infer_client.py",
    "simple_grpc_async_infer_client.py",
    "simple_grpc_aio_infer_client.py",
    "simple_grpc_string_infer_client.py",
    "simple_grpc_model_control.py",
    "simple_grpc_sequence_stream_infer_client.py",
    "simple_grpc_sequence_sync_infer_client.py",
    "simple_grpc_aio_sequence_stream_infer_client.py",
    "simple_grpc_shm_client.py",
    "simple_grpc_shm_string_client.py",
    "simple_grpc_tpushm_client.py",
    "simple_grpc_health_metadata.py",
    "simple_grpc_keepalive_client.py",
    "simple_grpc_custom_args_client.py",
    "simple_grpc_custom_repeat.py",
    "simple_grpc_replicated_client.py",
    "simple_grpc_discovery_client.py",
    "ensemble_client.py",
    "ensemble_image_client.py",
    "reuse_infer_objects_client.py",
    "grpc_client.py",
    "grpc_image_client.py",
    "grpc_explicit_int_content_client.py",
    "grpc_explicit_int8_content_client.py",
    "grpc_explicit_byte_content_client.py",
    "memory_growth_test.py",
]
HTTP_EXAMPLES = [
    "simple_http_infer_client.py",
    "simple_http_async_infer_client.py",
    "simple_http_aio_infer_client.py",
    "simple_http_string_infer_client.py",
    "simple_http_health_metadata.py",
    "simple_http_model_control.py",
    "simple_http_sequence_sync_infer_client.py",
    "simple_http_replicated_client.py",
    "simple_http_shm_client.py",
    "simple_http_shm_string_client.py",
    "simple_http_tpushm_client.py",
]


@pytest.fixture(scope="module")
def server():
    with Server(grpc_port=0, http_port=0) as s:
        yield s


def _run_example(name, url):
    # JAX_PLATFORMS=cpu in the child is also how an out-of-process TPU-shm
    # example runs beside a server that holds the chip (host-window face)
    proc = subprocess.run(
        [sys.executable, os.path.join(_EXAMPLES, name), "-u", url],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"{name}: {proc.stdout}\n{proc.stderr}"
    assert "PASS" in proc.stdout, f"{name}: no PASS line\n{proc.stdout}"


@pytest.mark.parametrize("name", GRPC_EXAMPLES)
def test_grpc_example(server, name):
    _run_example(name, server.grpc_address)


@pytest.mark.parametrize("name", HTTP_EXAMPLES)
def test_http_example(server, name):
    _run_example(name, server.http_address)


def test_example_corpus_size():
    """VERDICT r02 acceptance: >=25 Python examples, all runnable."""
    names = [n for n in os.listdir(_EXAMPLES) if n.endswith(".py")]
    assert len(names) >= 25, sorted(names)
