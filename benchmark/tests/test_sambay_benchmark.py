"""The SambaY cell's own files (driver, readers, work functions) on the CPU
at the tiny configuration of ``tiny/``, as ``test_benchmark.py`` does for the
two cells there.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import importlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import run, work, work_sambay  # noqa: E402

TINY = os.path.join(HERE, "tiny")
CELL = "phi-4-mini-flash-reasoning.reason-c32"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module", autouse=True)
def native():
    run.build_native()


def test_the_configuration_keeps_every_key_of_the_catalog():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"Phi-4-mini-flash-reasoning"' in line)
    body = load(BENCH, "configs", "phi-4-mini-flash-reasoning.json")
    assert body["source"] == row["source_url"] and body["reduced"] == []
    for key, value in row["config"].items():
        assert body[key] == value, key
    assert body["head_dim"] * body["num_attention_heads"] == body["hidden_size"]
    assert body["assumed"]["mamba"]["expand"] * body["hidden_size"] == 5120


def test_work_counts_against_the_issues_arithmetic():
    c = load(BENCH, "configs", "phi-4-mini-flash-reasoning.json")
    kinds = work_sambay.kinds(c)
    assert [kinds.count(k) for k in ("mamba", "window", "memory", "full",
                                     "gmu", "cross")] == [8, 8, 1, 1, 7, 7]
    assert work_sambay.mixer_params(c, "mamba") == pytest.approx(41.2e6, rel=2e-3)
    assert work_sambay.mixer_params(c, "window") == pytest.approx(19.7e6, rel=2e-3)
    assert work_sambay.mixer_params(c, "gmu") == pytest.approx(26.2e6, rel=2e-3)
    assert work_sambay.mixer_params(c, "cross") == pytest.approx(13.1e6, rel=2e-3)
    assert work_sambay.layer_params(c) == pytest.approx(3341e6, rel=1e-3)
    assert work_sambay.head_params(c) == pytest.approx(512e6, rel=1e-3)
    assert work_sambay.kv_row_bytes(c) == 5120
    recurrent, rings = work_sambay.lane_state_bytes(c)
    assert recurrent == pytest.approx(3.2e6, rel=2e-2)
    assert rings == pytest.approx(21e6, rel=1e-2)
    # an empty tick reads every matrix once: 7.7 GB, 9.4 ms at 819 GB/s
    tick = work_sambay.decode_tick(c, {"calls": 1, "lane_steps": 0,
                                       "context_sum": 0, "window_sum": 0})
    assert tick["bytes"] == pytest.approx(7.7e9, rel=1e-2)
    least, bound = work.roofline_seconds(tick, "TPU v5 lite")
    assert bound == "hbm" and least == pytest.approx(9.4e-3, rel=1e-2)
    # 32 lanes at 1,500 positions: eight reads of the full cache, 2.0 GB,
    # eight windows of 512, 0.67 GB, nine states read and written
    full = work_sambay.decode_tick(c, {"calls": 1, "lane_steps": 32,
                                       "context_sum": 32 * 1500,
                                       "window_sum": 32 * 512})
    extra = full["bytes"] - tick["bytes"]
    assert extra == pytest.approx(2.0e9 + 0.67e9 + 2 * 32 * 3.2e6, rel=2e-2)
    # a key costs a differential head 6 x 64 operations, not 4 x 64
    more = work_sambay.decode_tick(c, {"calls": 1, "lane_steps": 32,
                                       "context_sum": 32 * 1500 + 1,
                                       "window_sum": 32 * 512})
    assert more["flops"] - full["flops"] == 6 * 64 * 40 * 8
    chunk = work_sambay.prefill_chunk(c, {"chunks": [(512, 512)]})
    assert work.roofline_seconds(chunk, "TPU v5 lite")[1] == "flops"
    assert chunk["flops"] == pytest.approx(512 * 2 * 3341e6, rel=0.06)
    # position 1,023 of a window layer meets 512 keys, of the full one 1,024
    assert work_sambay._chunk_keys(c, 1023, 1) == (512, 1024)
    assert work_sambay._chunk_keys(c, 0, 3) == (6, 6)
    step = work_sambay.tokens(c, {"prompt_tokens": 1000, "output_tokens": 10,
                                  "context_sum": 0, "window_context_sum": 0})
    assert step["flops"] == pytest.approx(
        2 * 1010 * 3341e6 + 2 * 10 * 512e6, rel=2e-3)


def test_readers_on_made_up_ticks():
    c = load(BENCH, "configs", "phi-4-mini-flash-reasoning.json")
    ratio = importlib.import_module("benchmark.readers.tick_ratio").read
    program = importlib.import_module("benchmark.readers.trace_program").read
    mfu = importlib.import_module("benchmark.readers.work_mfu").read
    ticks = [{"kind": "decode", "t0": 1.0 + 0.02 * i, "lanes": (0, 1),
              "context_tokens": 3000 + 2 * i, "window_tokens": 1024}
             for i in range(10)]
    ticks += [{"kind": "prefill_chunk", "t0": 1.01, "lanes": (2,),
               "start": 512, "tokens": 300, "width": 512,
               "context_tokens": 812, "window_tokens": 512},
              {"kind": "decode", "t0": 1.5, "lanes": (0,)}]  # an older tick
    window = {"ticks": ticks, "seconds": 30.0, "traced_span": (1.0, 1.2),
              "counts": {"prompt_tokens": 50000, "output_tokens": 40000,
                         "context_sum": 9e7, "window_context_sum": 4e7}}
    params = {"kind": "decode", "num": "context_tokens", "den": "lanes"}
    assert ratio(params, {"window": window}) == pytest.approx(1504.5)
    assert ratio(params, {"window": {"ticks": [ticks[-1]]}}) is None
    trace = {"modules": {"jit_sambay_decode_tick": [20, 0.40],
                         "jit_sambay_prefill_chunk": [2, 0.12]}}
    ctx = {"window": window, "trace": trace, "config": c,
           "device_kind": "TPU v5 lite", "chips": 1}
    decode = load(BENCH, "metrics", "sambay_decode_roofline_pct.json")
    prefill = load(BENCH, "metrics", "sambay_prefill_roofline_pct.json")
    # twenty events of 20 ms against a floor of 9.4 ms and two short lanes
    share = program(decode["params"], ctx)
    assert 47.0 < share < 49.0
    assert 5.0 < program(prefill["params"], ctx) < 100.0
    # nothing to read: no trace, no such program, ticks without the counts
    assert program(decode["params"], dict(ctx, trace=None)) is None
    assert program(decode["params"], dict(ctx, trace={"modules": {}})) is None
    old = dict(window, ticks=[ticks[-1]])
    assert program(decode["params"], dict(ctx, window=old)) is None
    step = load(BENCH, "metrics", "step_mfu_pct.sambay.json")
    assert 0.0 < mfu(step["params"], ctx) < 100.0
    assert mfu(step["params"], dict(ctx, window={"seconds": 30.0})) is None


def test_the_cell_names_what_benchmark_json_lists():
    manifest = load(ROOT, "BENCHMARK.json")
    cell, config, driver, metrics, chips = run.load_cell(CELL)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert chips == 1 and cell["driver"] == "lm_sambay_stream"
    assert {m["name"] for m in metrics} == {
        name for name, m in listed.items() if CELL in m["workloads"]}
    for name in driver.END_TO_END:
        entry = next(m for m in manifest["end_to_end"] if m["name"] == name)
        assert CELL in entry["workloads"]
    assert cell["traffic"]["clients"] == config["engine"]["max_slots"] == 32
    longest = (cell["traffic"]["prompt_tokens"]["max"]
               + cell["traffic"]["output_tokens"]["max"])
    assert longest <= config["engine"]["max_seq"]
    assert 32 * longest <= config["engine"]["pool_tokens"]


def test_reference_against_the_programs_steps():
    """The plain reference and the program's two steps on the tiny
    configuration's own weights; tests/test_sambay.py holds them closer."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_sambay, weights_sambay
    from benchmark.drivers import lm_sambay_stream
    from client_tpu.serve.models import sambay

    config = load(TINY, "configs", "sambay-tiny.json")
    cell = load(TINY, "workloads", "sambay-tiny.reason.json")
    driver = lm_sambay_stream.Run(cell, config, 3, print)
    model = driver.build_model()
    try:
        cfg = model.runner.scheduler.cfg
        assert cfg.kinds == tuple(reference_sambay.layer_kind(config, i)
                                  for i in range(8))
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), model.runner.scheduler.params)
    finally:
        model.closer()
    cfg = sambay.SambaYConfig(**dict(cfg.__dict__, dtype="float32"))
    tokens = np.random.default_rng(0).integers(0, 512, (1, 48)).astype(np.int32)
    at = np.arange(48, dtype=np.int32)[None]
    ends = weights_sambay.sambay_ends(config, 3)
    hidden = reference_sambay.hidden_states(
        config, tokens, at, ends,
        lambda i: weights_sambay.sambay_layer(config, 3, i))[0]
    want = np.asarray(reference_sambay.logits_at(config, hidden, ends))[0]
    _, _, spec = cfg.state_spec
    state = {name: [jnp.zeros((1,) + tuple(s), d) for s, d in layers]
             for name, layers in spec.items()}
    pool = [jnp.zeros((9, 1, 16, 32), jnp.float32)]
    chunk = np.zeros((1, 64), np.int32)
    chunk[0, :48] = tokens[0]
    got, _ = sambay.prefill_step(
        params, jnp.asarray(chunk), pool, pool, state,
        jnp.arange(1, 9, dtype=jnp.int32), jnp.int32(0), jnp.int32(0),
        jnp.int32(48), jnp.bool_(True), cfg, 16)
    assert np.max(np.abs(np.asarray(got) - want[47])) < 1e-3
    low = reference_sambay.hidden_states(
        config, tokens, at, ends,
        lambda i: weights_sambay.sambay_layer(config, 3, i),
        (None, __import__("benchmark.reference").reference.fp8))[1]
    gaps = np.asarray(reference_sambay.token_gaps(
        config, hidden, None, ends,
        control=(low, __import__("benchmark.reference").reference.fp8)))
    assert gaps.shape == (1, 48) and gaps.max() > 0.1 and gaps.min() >= 0.0


def test_the_driver_end_to_end_at_a_tiny_size(capsys):
    result = run.main(["--workload", "sambay-tiny.reason", "--seed",
                       "2147483999", "--seconds", "2", "--control", "1"],
                      require_tpu=False, roots=(TINY, BENCH))
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(last)[-1] == "checked" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"]["tokens_per_s"]["value"] > 0
    assert last["metrics"]["setup_s"]["unit"] == "s"
    for c in result["checked"].values():
        assert c["value"] <= c["limit"]
    # the control, the reference in fp8 in the program's place, is not correct
    assert any(c["value"] > c["limit"] for c in result["control"].values())


def test_an_altered_token_where_it_is_produced_is_not_correct(monkeypatch):
    from benchmark.drivers import lm_sambay_stream

    build = lm_sambay_stream.Run.build_model

    def broken(self):
        model = build(self)
        stream = model.runner.stream

        def altered(*args, **kwargs):
            for i, token in enumerate(stream(*args, **kwargs)):
                yield (token + 1) % 512 if i == 2 else token

        model.runner.stream = altered
        return model

    monkeypatch.setattr(lm_sambay_stream.Run, "build_model", broken)
    result = run.main(["--workload", "sambay-tiny.reason", "--seed",
                       "2147483999", "--seconds", "2"],
                      require_tpu=False, roots=(TINY, BENCH))
    assert result["correct"] is False and result["failed"] == 0


def test_counts_sum_what_the_window_layers_met():
    from benchmark.drivers import lm_sambay_stream

    config = load(TINY, "configs", "sambay-tiny.json")
    cell = load(TINY, "workloads", "sambay-tiny.reason.json")
    driver = lm_sambay_stream.Run(cell, config, 3, print)
    record = {"prompt_tokens": 20, "times": [1.0, 1.1, 1.2, 5.0]}
    counts = driver.counts([record], 0.0, 2.0)
    # window 8: the prompt's positions meet 1..8 keys then 8 each; two
    # decode steps over 21 and 22 positions meet 8 each
    assert counts["window_context_sum"] == 36 + 12 * 8 + 16
    assert counts["decode_window_sum"] == 16
    assert counts["context_sum"] == 20 * 21 // 2 + 21 + 22
    assert counts["output_tokens"] == 3 and counts["lane_steps"] == 2


def test_the_window_opens_at_the_cells_place_in_the_order_of_sizes(monkeypatch):
    """``traffic.first_index`` is the index of the ramp's first request in
    ``traffic.request_sizes``' one order; a cell without it follows its
    warm-up, as ``lm_stream`` does."""
    from benchmark.drivers import lm_sambay_stream, lm_stream

    seen = []
    monkeypatch.setattr(lm_stream.Run, "measure",
                        lambda self, seconds, tracer: seen.append(
                            (self.next_index, seconds, tracer)))
    config = load(TINY, "configs", "sambay-tiny.json")
    cell = load(TINY, "workloads", "sambay-tiny.reason.json")
    assert "first_index" not in cell["traffic"]
    driver = lm_sambay_stream.Run(cell, config, 3, print)
    driver.next_index = 2   # what a warm-up of two requests leaves
    driver.measure(1.5, None)
    cell["traffic"]["first_index"] = 40
    driver = lm_sambay_stream.Run(cell, config, 3, print)
    driver.next_index = 2
    driver.measure(1.5, None)
    assert seen == [(2, 1.5, None), (40, 1.5, None)]
    real = load(BENCH, "workloads", CELL + ".json")["traffic"]
    assert real["ramp_seconds"] == 8 and real["first_index"] >= 0
