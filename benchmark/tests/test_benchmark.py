"""The benchmark's own tests: CPU, tiny sizes, no chip touched at import.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import gzip
import importlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import run, traffic, work  # noqa: E402

TINY = os.path.join(HERE, "tiny")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module", autouse=True)
def native():
    run.build_native()


def run_cell(workload, *extra, **kwargs):
    return run.main(["--workload", workload, "--seed", "2147483999",
                     "--seconds", "2", *extra],
                    require_tpu=False, roots=(TINY, BENCH), **kwargs)


def test_every_file_parses_and_names_only_what_exists():
    manifest = load(ROOT, "BENCHMARK.json")
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for c in manifest["configs"]:
        body = load(ROOT, c["file"])
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
    for w in manifest["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        cell, config, driver, metrics, chips = run.load_cell(w["name"])
        assert cell["config"] in configs and chips == w["chips"]
        assert cell["why"] == w["why"]
        assert set(driver.END_TO_END) <= e2e
        for m in metrics:
            listed = per_layer[m["name"]]
            assert w["name"] in listed["workloads"]
            assert {k: listed[k] for k in ("unit", "better", "source", "layer",
                                           "moves")} == {
                k: m[k] for k in ("unit", "better", "source", "layer", "moves")}
    for name in os.listdir(os.path.join(BENCH, "metrics")):
        metric = load(BENCH, "metrics", name)
        assert name == metric["name"] + ".json"
        importlib.import_module(f"benchmark.readers.{metric['reader']}")
        if "work" in metric["params"]:
            assert callable(getattr(work, metric["params"]["work"]))


def test_a_made_up_cell_is_found_from_new_files_alone(tmp_path):
    for kind in ("configs", "workloads", "metrics"):
        os.makedirs(tmp_path / kind)
    config = dict(load(BENCH, "configs", "resnet50-224.json"), name="made-up")
    cell = dict(load(BENCH, "workloads", "resnet50-224.tpushm-c64-b32.json"),
                name="made-up.c2", config="made-up",
                metrics=["ack_p99_ms", "batch_rows_mean"])
    cell["traffic"] = dict(cell["traffic"], clients=2)
    metric = dict(load(BENCH, "metrics", "ack_p50_ms.json"), name="ack_p99_ms")
    metric["params"] = dict(metric["params"], q=99)
    for kind, name, body in (("configs", "made-up", config),
                             ("workloads", "made-up.c2", cell),
                             ("metrics", "ack_p99_ms", metric)):
        with open(tmp_path / kind / f"{name}.json", "w") as f:
            json.dump(body, f)
    found, _, driver, metrics, _ = run.load_cell(
        "made-up.c2", roots=(str(tmp_path), BENCH))
    assert found["traffic"]["clients"] == 2 and driver.__name__.endswith(
        "unary_tpushm")
    assert [m["name"] for m in metrics] == ["ack_p99_ms", "batch_rows_mean"]
    assert metrics[0]["read"]({"series": "ack_ms", "q": 99}, {
        "window": {"series": {"ack_ms": list(range(1, 101))}}}) == 99.0
    # a metric that moves what the cell's driver does not report is refused
    cell["metrics"] = ["decode_lanes_mean"]
    with open(tmp_path / "workloads" / "made-up.c2.json", "w") as f:
        json.dump(cell, f)
    with pytest.raises(SystemExit, match="does not report"):
        run.load_cell("made-up.c2", roots=(str(tmp_path), BENCH))


def test_work_counts_against_hand_worked_numbers():
    resnet = load(BENCH, "configs", "resnet50-224.json")
    assert work.resnet50_row_flops(resnet) == pytest.approx(8.18e9, rel=2e-3)
    assert work.resnet50_param_count(resnet) == pytest.approx(25.5e6, rel=5e-3)
    lm = load(BENCH, "configs", "mistral-7b-v0.3-d16.json")
    # a layer: q and o 4096x4096, k and v 4096x1024, three 4096x14336
    assert work.lm_layer_params(lm) == 2 * 4096 ** 2 + 2 * 4096 * 1024 \
        + 3 * 4096 * 14336
    tick = work.lm_decode(lm, {"calls": 1, "lane_steps": 0, "context_sum": 0})
    assert tick["bytes"] == pytest.approx(7.25e9, rel=1e-3)
    assert 2 * work.lm_param_count(lm) == pytest.approx(7.5e9, rel=5e-3)
    assert work.lm_kv_bytes_per_token(lm) == 65536
    least, bound = work.roofline_seconds(tick, "TPU v5 lite")
    assert bound == "hbm" and least == pytest.approx(8.85e-3, rel=1e-2)
    chunk = work.lm_prefill(lm, {"chunks": [(0, 512)]})
    assert work.roofline_seconds(chunk, "TPU v5 lite")[1] == "flops"
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_traffic_gives_every_seed_the_same_sizes_and_other_tokens():
    mix = load(BENCH, "workloads", "mistral-7b-v0.3-d16.chat-c16.json")["traffic"]
    block = traffic.size_block(mix)
    assert min(p for p, _ in block) >= 128 and max(p for p, _ in block) <= 1024
    one = [traffic.request_sizes(mix, 5, i) for i in range(128)]
    two = [traffic.request_sizes(mix, 2 ** 31 + 9, i) for i in range(128)]
    assert one == two and one[:64] != one[64:]
    assert sorted(one[:64]) == sorted(one[64:]) == sorted(block)
    a = traffic.prompt_tokens(mix, 5, 3, 200, 32768)
    assert a.shape == (200,) and (a == traffic.prompt_tokens(
        mix, 5, 3, 200, 32768)).all()
    assert (a != traffic.prompt_tokens(mix, 6, 3, 200, 32768)).any()
    assert traffic.percentile(list(range(1, 101)), 95) == 95.0


def test_trace_reduction_on_a_recorded_trace(tmp_path):
    from benchmark import trace

    path = tmp_path / "small.xplane.pb"
    with gzip.open(os.path.join(HERE, "small.xplane.pb.gz")) as src:
        path.write_bytes(src.read())
    summary = trace.read(str(path))
    expected = load(HERE, "small.expected.json")
    assert 0 < summary["busy_s"] <= summary["window_s"]
    assert summary["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert summary["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)
    calls, seconds = summary["modules"][expected["module"]]
    # a module spans its operations and the slivers between them
    assert calls == expected["calls"]
    assert summary["busy_s"] <= seconds <= 1.01 * summary["busy_s"]
    assert len(summary["module_events"]) == calls
    assert summary["device_ops"] and len(summary["device_ops"]) <= 10
    assert len(summary["idle_gaps"]) <= 10
    # the readers on it: a roofline share is a share, an idle share too
    ctx = {"trace": summary, "device_kind": "TPU v5 lite", "chips": 1,
           "config": load(BENCH, "configs", "resnet50-224.json"),
           "window": {"traced_seconds": summary["window_s"],
                      "traced_counts": {"rows": 0}}}
    roof = importlib.import_module("benchmark.readers.trace_module").read
    idle = importlib.import_module("benchmark.readers.trace_idle").read
    assert 0 <= idle({}, ctx) < 100
    assert roof({"module": "no_such_module", "work": "resnet50_forward",
                 "bound": "flops"}, ctx) is None
    assert trace.module_name("jit__decode_tick(123)") == "jit__decode_tick"


def test_resnet_reference_against_the_programs_model():
    import jax.numpy as jnp

    from benchmark import reference, weights
    from client_tpu.serve.models.vision import _resnet_forward

    config = load(TINY, "configs", "resnet-tiny.json")
    params = weights.resnet50_params(config, 11)
    x = weights.rows(11, 0, (4, 3, 32, 32))
    want = np.asarray(reference.resnet50_scores(config, params, x))
    f32 = jnp.float32
    got = np.asarray(_resnet_forward(params, x))
    assert got.shape == want.shape == (4, 1000) and got.dtype == f32
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 0.02
    low = np.asarray(reference.resnet50_scores(config, params, x,
                                               reference.fp8))
    assert np.max(np.abs(low - want)) / np.max(np.abs(want)) > 0.02


def test_decoder_reference_against_the_programs_model():
    from benchmark import reference, weights
    from benchmark.drivers import lm_stream
    from client_tpu.serve.models import transformer as tfm

    config = load(TINY, "configs", "lm-tiny.json")
    driver = lm_stream.Run({"name": "t", "traffic": {}}, config, 3, print)
    params = weights.lm_params(config, 3)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 48)).astype(np.int32)
    at = np.tile(np.arange(48, dtype=np.int32), (2, 1))
    want = np.asarray(reference.decoder_logits(
        config, tokens, at, weights.lm_ends(config, 3),
        lambda i: weights.lm_layer(config, 3, i), block_rows=1)[0])
    cfg = tfm.TransformerConfig(
        vocab_size=512, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=128, rope_theta=1e6, dtype="float32")
    as_f32 = __import__("jax").tree_util.tree_map(
        lambda a: a.astype(np.float32), params)
    got = np.asarray(tfm.forward(as_f32, tokens, cfg))
    assert np.max(np.abs(got - want)) < 2e-2 * np.max(np.abs(want))
    assert driver.max_seq == 128


@pytest.mark.parametrize("workload,metric", [
    ("resnet-tiny.shm", "rows_per_s"), ("lm-tiny.chat", "tokens_per_s")])
def test_a_driver_end_to_end_at_a_tiny_size(workload, metric, capsys):
    result = run_cell(workload, "--control", "1")
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(last)[-1] == "checked" and last["correct"] is True
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"][metric]["value"] > 0
    assert last["metrics"]["setup_s"]["unit"] == "s"
    for c in result["checked"].values():
        assert c["value"] <= c["limit"]
    # the control, the reference in fp8 in the program's place, is not correct
    assert any(c["value"] > c["limit"] for c in result["control"].values())


def test_an_altered_answer_where_it_is_produced_is_not_correct(monkeypatch):
    from benchmark.drivers import unary_tpushm

    build = unary_tpushm.Run.build_model

    def broken(self):
        model = build(self)
        forward = model.fn._forward
        # one row in 8 gets its neighbour's scores
        model.fn._forward = lambda p, x: forward(p, x).at[::8].set(
            forward(p, x)[1::8])
        return model

    monkeypatch.setattr(unary_tpushm.Run, "build_model", broken)
    result = run_cell("resnet-tiny.shm")
    assert result["correct"] is False and result["failed"] == 0


def test_an_altered_token_where_it_is_produced_is_not_correct(monkeypatch):
    from benchmark.drivers import lm_stream

    build = lm_stream.Run.build_model

    def broken(self):
        model = build(self)
        stream = model.runner.stream

        def altered(*args, **kwargs):
            for i, token in enumerate(stream(*args, **kwargs)):
                yield (token + 1) % 512 if i == 2 else token

        model.runner.stream = altered
        return model

    monkeypatch.setattr(lm_stream.Run, "build_model", broken)
    result = run_cell("lm-tiny.chat")
    assert result["correct"] is False and result["failed"] == 0


def test_run_exits_non_zero_off_a_tpu_and_prints_no_result():
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "resnet50-224.tpushm-c64-b32", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, cwd=ROOT)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "needs 1 TPU" in done.stderr
