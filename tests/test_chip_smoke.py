"""chip_smoke.py's control flow, proven on the CPU before chip time is spent.

The smoke's phase functions run here at tiny size — ``cnn_small`` in place
of resnet50, a 32-wide LM, two streams, kernels interpreted — through the
same Server, gRPC socket, TPU-shm regions, perf ``main()`` and ``LmEngine``
the chip run drives.  Nothing here is a device number.  Also: the script
itself refuses to run off-TPU, and the compile-cache helper places the cache
where ``JAX_COMPILATION_CACHE_DIR`` says or at the one in-checkout path.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _tiny_lm(**overrides):
    from client_tpu.serve.models import transformer as tfm

    cfg = dict(vocab_size=258, d_model=32, n_layers=1, n_heads=2,
               n_kv_heads=1, d_ff=64, max_seq=128)
    cfg.update(overrides)
    return tfm.TransformerConfig(**cfg)


@pytest.fixture(scope="module")
def served():
    """(server, models by name): the smoke's model set at tiny size, with
    the names its phases look up."""
    from client_tpu.serve import Server
    from client_tpu.serve.models import language as lang
    from client_tpu.serve.models.vision import cnn_classifier_model

    runner = lang._LmRunner(cfg=_tiny_lm())
    int8 = lang._LmRunner(cfg=runner.cfg, params=runner.params, quantize=True)
    models = [
        cnn_classifier_model(name="cnn_small", image_size=32),
        lang.lm_streaming_batched_model(runner=runner),
        # off-TPU the stock set serves int8 from the serial runner
        lang.lm_streaming_model(name="lm_streaming_int8", runner=int8),
        lang.lm_streaming_batched_model(
            name="lm_spec_smoke", runner=runner,
            speculative={"k": 4, "drafter": "ngram"},
        ),
    ]
    with Server(models=models, http_port=0, grpc_port=0,
                with_default_models=False) as server:
        yield server, {m.name: m for m in models}


def test_vision_phase_runs_tpu_shm_fused_and_every_transport(served):
    import numpy as np

    server, models = served
    classifier = models["cnn_small"].fn
    facts = {}
    chip_smoke.vision_phase(
        server, "cnn_small",
        lambda rows: np.asarray(
            classifier({"INPUT0": rows}, {}, None)["OUTPUT0"]
        ),
        classifier.image_size, "cpu", facts,
        # four workers: the three that queue behind the first request's
        # compile are gathered into one fused group
        requests=8, rows=2, concurrency=4, mp_window_s=0.3,
    )
    assert facts["server_statistics"]["success"] == 8
    assert facts["server_statistics"]["executions"] < 8  # a fused batch ran
    assert "JAX backends opened: [[], []]" in facts["load_workers"]
    assert facts["first_s"] > 0
    # the device-time checks ran: the program's time for a step and the
    # blocked step beside it (their agreement is asserted on the chip),
    # and no MFU off the TPU
    timed = facts["device_time"]
    assert timed["compute_infer_ms_a_step"] > 0
    assert timed["blocked_step_ms"] > 0 and timed["device_s"] > 0
    assert timed["mfu_pct"] is None


def test_perf_phase_reports_throughput_and_names_the_device(served):
    server, _ = served
    device = {"platform": "cpu", "kind": "cpu", "count": 8}
    facts = {}
    chip_smoke.perf_phase(server, "cnn_small", device, facts, rows=2,
                          concurrency=2, window_ms=300)
    assert facts["report"]["device"] == device
    assert facts["report"]["errors"] == 0
    with pytest.raises(AssertionError, match="report names"):
        chip_smoke.perf_phase(server, "cnn_small", {"platform": "tpu"},
                              {}, rows=2, concurrency=2, window_ms=300)


def test_lm_phase_streams_prefix_hit_int8_spec_and_preemption(served):
    server, models = served
    facts = {}
    chip_smoke.lm_phase(server, models, facts, prompt_lens=(20, 40),
                        max_tokens=8, int8_tokens=2, spec_tokens=16)
    assert facts["runner[lm_streaming_batched]"] == (
        "BatchedLmRunner -> LmEngine")
    assert facts["runner[lm_streaming_int8]"].startswith("_LmRunner")
    assert "repeats identical" in facts["prefix_cache"]
    assert "swap-out" in facts["preemption"]


def test_lm_reference_rejects_a_wrong_token(served):
    """The teacher-forced margin is what stands between a corrupted KV
    block and a passing smoke: a token the reference does not rank near
    its argmax must fail the check."""
    import numpy as np

    _, models = served
    engine = models["lm_streaming_batched"].runner.scheduler
    reference = chip_smoke.LmReference(engine.params, engine.cfg)
    prompt = np.arange(1, 12, dtype=np.int32)
    queue_, _ = engine.submit(prompt, 4)
    tokens = []
    while (token := queue_.get(timeout=120)) is not engine.CLOSE:
        tokens.append(token)
    assert reference.check("ok", prompt, tokens, 4) <= chip_smoke.LM_MARGIN
    row = np.zeros((1, engine.cfg.max_seq), np.int32)
    row[0, :len(prompt)] = prompt
    logits = np.asarray(reference._forward(reference._params, row))[0]
    tokens[0] = int(logits[len(prompt) - 1].argmin())
    with pytest.raises(AssertionError, match="under the reference argmax"):
        reference.check("corrupted", prompt, tokens, 4)


def test_kernel_phase_finds_the_pallas_call_interpreted():
    # one (K, N) and four M: interpret mode costs ~a second per call
    cfg = _tiny_lm(d_model=64, n_heads=4, n_kv_heads=4, d_ff=64)
    facts = {}
    chip_smoke.kernel_phase(cfg, (2,), (16,), facts, interpret=True,
                            flash_shapes=((1, 64, 2, 16),), spec_k=1)
    assert "4 shapes" in facts["int8_matmul"]


def test_kernel_phase_fails_when_a_reference_stands_in():
    """A ragged N takes int8_matmul's dequantised jnp path; the smoke must
    call that a missing kernel, not a pass."""
    # (64, 258): N does not tile
    cfg = _tiny_lm(d_model=64, n_heads=4, n_kv_heads=4, d_ff=258)
    with pytest.raises(AssertionError, match="no Pallas call lowered"):
        chip_smoke.kernel_phase(cfg, (2,), (16,), {}, interpret=True,
                                flash_shapes=(), spec_k=1)


def _run(code_or_script, env_extra, *args):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, *code_or_script, *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_script_exits_nonzero_off_tpu_before_doing_any_work():
    done = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert "platform cpu" in done.stdout
    assert '"ok"' not in done.stdout and "--- build" not in done.stdout
    assert "needs a TPU" in done.stderr


_HELPER = ("from client_tpu._compile_cache import enable_compile_cache;"
           "print(enable_compile_cache())")


def test_cache_helper_leaves_a_placed_cache_alone(tmp_path):
    placed = str(tmp_path / "placed")
    done = _run(["-c", _HELPER + ";import os;"
                 "print(os.environ['JAX_COMPILATION_CACHE_DIR'])"],
                {"JAX_COMPILATION_CACHE_DIR": placed})
    assert done.stdout.split() == [placed, placed], done.stderr


def test_cache_helper_defaults_to_the_fixed_in_checkout_path():
    done = _run(["-c", _HELPER], {})
    assert done.stdout.strip() == os.path.join(ROOT, ".jax_cache"), done.stderr
    # too late once jax is imported: jax read its settings already
    late = _run(["-c", "import jax;" + _HELPER], {"JAX_PLATFORMS": "cpu"})
    assert late.returncode != 0
    assert "before jax is imported" in late.stderr
