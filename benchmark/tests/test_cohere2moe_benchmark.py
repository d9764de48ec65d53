"""The ``cohere2_moe`` cell's own files (driver, reader, work functions) on
the CPU at the tiny configuration of ``tiny/``, as ``test_sambay_benchmark.py``
does for its cell.  ``tests/test_cohere2moe_benchmark.py`` imports these so
that ``pytest tests/`` counts them.

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

from benchmark import run, work, work_cohere2moe  # noqa: E402

TINY = os.path.join(HERE, "tiny")
CONFIG = "command-a-plus-05-2026-ep8-d4"
CELL = CONFIG + ".rag-c32"
NEW_METRICS = {
    "step_mfu_pct.cohere2moe", "step_mfu_pct.cohere2moe_ttft",
    "cohere2moe_decode_roofline_pct", "cohere2moe_prefill_roofline_pct",
    "expert_rows_per_hit", "experts_hit_share", "expert_busiest_share",
    "kv_read_share"}


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
                   if '"command-a-plus-05-2026"' in line)
    body = load(BENCH, "configs", CONFIG + ".json")
    assert body["source"] == row["source_url"]
    assert body["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    for key, value in row["config"].items():
        if key in body["reduced"]:
            assert body["published"][key] == value, key
        else:
            assert body[key] == value, key
    # the floors of a cut: a whole period and four layers, 8 experts or
    # more, an eighth of the vocabulary; the router keeps its width
    share = body["deployment"]
    kinds = body["layer_types"][:body["num_hidden_layers"]]
    assert kinds == ["sliding_attention"] * 3 + ["full_attention"]
    assert share["router_experts"] == body["published"]["num_experts"] == 128
    assert share["experts_held"] == list(range(16))
    assert len(share["experts_held"]) == body["num_experts"] >= 8
    assert share["chips_per_layer"] * body["num_experts"] == 128
    assert share["chips_per_layer"] * body["vocab_size"] == 262144
    assert share["vocab_rows"] == [0, body["vocab_size"]]


def test_work_counts_against_the_issues_arithmetic():
    c = load(BENCH, "configs", CONFIG + ".json")
    assert work_cohere2moe.attention_params(c) == pytest.approx(142.6e6, rel=1e-3)
    assert work_cohere2moe.router_params(c) == 4096 * 128
    assert work_cohere2moe.expert_params(c) == pytest.approx(50.33e6, rel=1e-3)
    assert 4 * work_cohere2moe.expert_params(c) == pytest.approx(201.3e6, rel=1e-3)
    assert work_cohere2moe.head_params(c) == 32768 * 4096
    assert work_cohere2moe.held_params(c) == pytest.approx(4.73e9, rel=2e-3)
    assert work_cohere2moe.kv_row_bytes(c) == 4096
    # an empty tick reads what every row meets, once: attention 1.14 GB,
    # shared experts 1.61, router 0.004, head 0.27
    empty = {"calls": 1, "lane_steps": 0, "expert_rows": 0, "experts_hit": 0,
             "kv_positions_live": 0}
    tick = work_cohere2moe.decode_tick(c, empty)
    assert tick["flops"] == 0
    assert tick["bytes"] == pytest.approx(3.02e9, rel=5e-3)
    # the issue's tick: 32 lanes, 13.9 of 16 held experts hit a layer, a
    # mean context of 3,700 with the window layers capped: about 10.3 GB,
    # 12.6 ms at 819 GB/s
    live = 32 * (3700 + 3 * 3100)
    full = work_cohere2moe.decode_tick(c, dict(
        empty, lane_steps=32, expert_rows=4 * 32, experts_hit=56,
        kv_positions_live=live))
    assert full["bytes"] - tick["bytes"] == pytest.approx(
        56 * 100.66e6 + (live + 4 * 32) * 4096, rel=1e-3)
    assert full["bytes"] == pytest.approx(10.3e9, rel=0.03)
    least, bound = work.roofline_seconds(full, "TPU v5 lite")
    assert bound == "hbm" and least == pytest.approx(12.6e-3, rel=0.03)
    # a routed pair costs one expert's 2 x 50.33 M, and nothing else moves
    more = work_cohere2moe.decode_tick(c, dict(
        empty, lane_steps=32, expert_rows=4 * 32 + 1, experts_hit=56,
        kv_positions_live=live))
    assert more["flops"] - full["flops"] == 2 * work_cohere2moe.expert_params(c)
    # a key costs 4 x 128 a query head
    more = work_cohere2moe.decode_tick(c, dict(
        empty, lane_steps=32, expert_rows=4 * 32, experts_hit=56,
        kv_positions_live=live + 1))
    assert more["flops"] - full["flops"] == 4 * 128 * 128
    # position 5,000 of a window layer meets 4,096 keys, of the full 5,001
    assert work_cohere2moe.chunk_keys(c, 5000, 1) == (4096, 5001)
    assert work_cohere2moe.chunk_keys(c, 0, 3) == (6, 6)
    chunk = work_cohere2moe.prefill_chunk(c, {
        "chunks": [(512, 512)], "expert_rows": 4 * 512, "experts_hit": 64})
    # 512 rows meet 1.67 TFLOP (8.5 ms at peak) but the chunk reads all 16
    # held experts of every layer, 9.5 GB (11.6 ms): at this share of the
    # experts a chunk of 512 is bound by the weights' bytes, not by FLOPs
    least, bound = work.roofline_seconds(chunk, "TPU v5 lite")
    assert bound == "hbm" and least == pytest.approx(11.6e-3, rel=0.02)
    assert chunk["flops"] / 197e12 == pytest.approx(8.5e-3, rel=0.03)
    dense = 4 * work_cohere2moe.layer_dense_params(c)
    assert chunk["flops"] == pytest.approx(
        2 * 512 * dense + 2 * 4 * 512 * 50.33e6 + 2 * 134.2e6
        + 4 * 4 * 128 * 128 * (512 * 512 + 512 * 513 // 2), rel=1e-3)
    # in a chunk the routed experts are all 16 a layer: 6.4 GB of 9.5
    assert chunk["bytes"] == pytest.approx(
        3.02e9 + 64 * 100.66e6 + (4 * 512 + 4 * 512) * 4096, rel=5e-3)
    step = work_cohere2moe.tokens(c, {
        "prompt_tokens": 1000, "output_tokens": 10, "expert_rows": 4040,
        "context_sum": 0, "window_context_sum": 0})
    assert step["flops"] == pytest.approx(
        2 * 1010 * dense + 2 * 10 * 134.2e6 + 2 * 4040 * 50.33e6, rel=1e-3)


def test_readers_on_made_up_ticks():
    c = load(BENCH, "configs", CONFIG + ".json")
    ratio = importlib.import_module("benchmark.readers.tick_ratio").read
    sums = importlib.import_module("benchmark.readers.trace_program_sums").read
    mfu = importlib.import_module("benchmark.readers.work_mfu").read
    ticks = [{"kind": "decode", "t0": 1.0 + 0.02 * i, "lanes": tuple(range(32)),
              "context_tokens": 32 * 3700, "window_tokens": 32 * 3100,
              "experts_held": 64, "experts_hit": 56, "expert_rows": 128,
              "expert_rows_max": 16, "kv_positions_live": 32 * 13000,
              "kv_positions_read": 32 * 13500} for i in range(10)]
    ticks += [{"kind": "prefill_chunk", "t0": 1.01, "lanes": (2,),
               "start": 512, "tokens": 300, "width": 512,
               "context_tokens": 812, "window_tokens": 812,
               "experts_held": 64, "experts_hit": 64, "expert_rows": 1200,
               "expert_rows_max": 110, "kv_positions_live": 3248,
               "kv_positions_read": 4096},
              {"kind": "decode", "t0": 1.5, "lanes": (0,),   # no counts yet
               "context_tokens": 100, "window_tokens": 100}]
    window = {"ticks": ticks, "seconds": 30.0, "traced_span": (1.0, 1.2),
              "counts": {"prompt_tokens": 200000, "output_tokens": 30000,
                         "expert_rows": 230000, "context_sum": 4e8,
                         "window_context_sum": 3e8}}
    ctx = {"window": window, "config": c, "device_kind": "TPU v5 lite",
           "chips": 1, "trace": {"modules": {
               "jit_cohere2moe_decode_tick": [20, 0.32],
               "jit_cohere2moe_prefill_chunk": [2, 0.06]}}}
    metric = {name: load(BENCH, "metrics", name + ".json")
              for name in NEW_METRICS}
    assert ratio(metric["expert_rows_per_hit"]["params"], ctx) == \
        pytest.approx(128 / 56)
    assert ratio(metric["experts_hit_share"]["params"], ctx) == \
        pytest.approx(56 / 64)
    assert ratio(metric["expert_busiest_share"]["params"], ctx) == \
        pytest.approx(16 / 128)
    assert ratio(metric["kv_read_share"]["params"], ctx) == \
        pytest.approx(13500 / 13000)
    # twenty events of 16 ms against a floor of about 12.7 ms
    decode = sums(metric["cohere2moe_decode_roofline_pct"]["params"], ctx)
    assert 75.0 < decode < 85.0
    prefill = sums(metric["cohere2moe_prefill_roofline_pct"]["params"], ctx)
    assert 5.0 < prefill < 100.0
    # nothing to read: no trace, no such program, ticks without the counts
    params = metric["cohere2moe_decode_roofline_pct"]["params"]
    assert sums(params, dict(ctx, trace=None)) is None
    assert sums(params, dict(ctx, trace={"modules": {}})) is None
    old = dict(window, ticks=[ticks[-1]])
    assert sums(params, dict(ctx, window=old)) is None
    assert ratio(metric["kv_read_share"]["params"],
                 dict(ctx, window=old)) is None
    step = metric["step_mfu_pct.cohere2moe"]["params"]
    assert 0.0 < mfu(step, ctx) < 100.0
    assert mfu(step, dict(ctx, window={"seconds": 30.0})) is None


def test_the_cell_names_what_benchmark_json_lists():
    manifest = load(ROOT, "BENCHMARK.json")
    cell, config, driver, metrics, chips = run.load_cell(CELL)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert chips == 1 and cell["driver"] == "lm_cohere2moe_stream"
    assert {m["name"] for m in metrics} == {
        name for name, m in listed.items() if CELL in m["workloads"]}
    assert NEW_METRICS <= {m["name"] for m in metrics}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL]
    for name in driver.END_TO_END:
        entry = next(m for m in manifest["end_to_end"] if m["name"] == name)
        assert CELL in entry["workloads"]
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry == manifest["workloads"][-1] and entry["traffic"] == "rag-c32"
    traffic = cell["traffic"]
    assert traffic["clients"] == config["engine"]["max_slots"] == 32
    assert traffic["prompt_tokens"] == {"dist": "log_uniform", "min": 1024,
                                        "max": 8192}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256,
                                        "max": 768}
    assert (traffic["block"], traffic["check_requests"],
            traffic["ramp_seconds"], traffic["shared_prefix_tokens"]) == (
                64, 4, 8, 0)
    longest = traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
    assert longest <= config["engine"]["max_seq"]
    # the pool holds the mix's mean stream 32 times over, not its longest
    assert 32 * 5000 <= config["engine"]["pool_tokens"]


def test_the_driver_end_to_end_at_a_tiny_size(capsys):
    result = run.main(["--workload", "cohere2moe-tiny.rag", "--seed",
                       "2147483999", "--seconds", "2", "--control", "1"],
                      require_tpu=False, roots=(TINY, BENCH))
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(last)[-1] == "checked" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"]["tokens_per_s"]["value"] > 0
    assert last["metrics"]["setup_s"]["unit"] == "s"
    assert list(result["checked"]) == ["token_gap_mean"]
    for c in result["checked"].values():
        assert c["value"] <= c["limit"]
    # the control, the reference in fp8 in the program's place, is not correct
    assert any(c["value"] > c["limit"] for c in result["control"].values())


def test_weights_stand_at_fan_in_scale_but_the_down_projections():
    """Every matrix a fan-in scaled normal; the experts' down projections
    ``DOWN_GAIN`` times that, which is what keeps a lane's stream a token's
    own and the router's picks spread for every seed (PERF.md section 6)."""
    from benchmark import weights_cohere2moe

    config = load(TINY, "configs", "cohere2moe-tiny.json")
    layer = weights_cohere2moe.cohere2moe_layer(config, 7, 0)
    ffn = layer["ffn"]

    def gain(w, fan_in):
        return float(np.asarray(w, np.float32).std()) * fan_in ** 0.5

    d, ff = config["hidden_size"], config["intermediate_size"]
    heads = config["num_attention_heads"] * config["head_dim"]
    # 3% is four deviations of the smallest matrix's sample
    for w, fan_in, want in (
            (layer["wqkv"], d, 1.0), (layer["wo"], heads, 1.0),
            (ffn["router"], d, 1.0), (ffn["w_gate_up"], d, 1.0),
            (ffn["shared_gate_up"], d, 1.0),
            (ffn["w_down"], ff, weights_cohere2moe.DOWN_GAIN),
            (ffn["shared_down"], ff, weights_cohere2moe.DOWN_GAIN)):
        assert gain(w, fan_in) == pytest.approx(want, rel=0.03)


def test_counts_sum_the_window_and_the_routed_pairs():
    from benchmark.drivers import lm_cohere2moe_stream

    config = load(TINY, "configs", "cohere2moe-tiny.json")
    cell = load(TINY, "workloads", "cohere2moe-tiny.rag.json")
    driver = lm_cohere2moe_stream.Run(cell, config, 3, print)

    class Engine:
        def tick_trace(self):
            return [{"t0": 0.5, "expert_rows": 7}, {"t0": 1.5},
                    {"t0": 1.9, "expert_rows": 5}, {"t0": 2.5,
                                                    "expert_rows": 100}]

    driver.engine = Engine()
    record = {"prompt_tokens": 20, "times": [1.0, 1.1, 1.2, 5.0]}
    counts = driver.counts([record], 0.0, 2.0)
    # window 16: the prompt's positions meet 1..16 keys then 16 each; two
    # decode steps over 21 and 22 positions meet 16 each
    assert counts["window_context_sum"] == 136 + 4 * 16 + 32
    assert counts["decode_window_sum"] == 32
    assert counts["context_sum"] == 20 * 21 // 2 + 21 + 22
    assert counts["output_tokens"] == 3 and counts["lane_steps"] == 2
    assert counts["expert_rows"] == 12
