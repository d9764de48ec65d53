"""The ``sdar_moe`` cell's own files (configuration, driver, metric files,
work functions) on the CPU at the tiny configuration of ``tiny/``, as
``test_cohere2moe_benchmark.py`` does for its cell.
``tests/test_sdar_benchmark.py`` imports these so that ``pytest tests/``
counts them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import run, work, work_sdar  # noqa: E402

TINY = os.path.join(HERE, "tiny")
CONFIG = "sdar-30b-a3b-chat-d7"
CELL = CONFIG + ".fixedgen-c32"
NEW_METRICS = {
    "step_mfu_pct.sdar", "step_mfu_pct.sdar_ttft", "sdar_decode_roofline_pct",
    "sdar_prefill_roofline_pct", "passes_per_token", "commit_pass_share",
    "block_gap_p50_ms"}


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
                   if '"SDAR-30B-A3B-Chat"' in line)
    body = load(BENCH, "configs", CONFIG + ".json")
    assert body["source"] == row["source_url"]
    assert body["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in body["reduced"]:
            assert body["published"][key] == value, key
        else:
            assert body[key] == value, key
    # the floors of a cut: whole periods and four layers or more (every
    # layer is alike), every expert, the whole vocabulary
    assert body["num_hidden_layers"] == 7 >= 4
    assert body["published"] == {"num_hidden_layers": 48}
    share = body["deployment"]
    assert share["chips_per_layer"] == 1 and share["pipeline_stages"] == 7
    assert share["router_experts"] == body["num_experts"] == 128
    assert share["experts_held"] == list(range(128))
    assert share["vocab_rows"] == [0, body["vocab_size"]]
    assert body["mlp_only_layers"] == [] and body["decoder_sparse_step"] == 1
    rule = body["assumed"]
    assert (rule["block_length"], rule["denoising_steps"],
            rule["remasking_strategy"], rule["mask_token_id"]) == (
                4, 4, "low_confidence_static", 151669)
    for key in ("tie_break", "no_shift", "attention", "experts", "weights"):
        assert rule[key]
    assert rule["mask_token_id"] < body["vocab_size"]


def test_work_counts_against_the_issues_arithmetic():
    c = load(BENCH, "configs", CONFIG + ".json")
    assert work_sdar.attention_params(c) == pytest.approx(18.87e6, rel=1e-3)
    assert work_sdar.router_params(c) == 2048 * 128
    assert work_sdar.expert_params(c) == pytest.approx(4.72e6, rel=1e-3)
    assert 128 * work_sdar.expert_params(c) == pytest.approx(603.98e6, rel=1e-4)
    assert work_sdar.head_params(c) == 151936 * 2048
    assert work_sdar.held_params(c) == pytest.approx(4984e6, rel=1e-3)
    assert work_sdar.kv_row_bytes(c) == 2048
    assert (work_sdar.block_length(c), work_sdar.fixed_a_pass(c)) == (4, 1)
    dense, expert = work_sdar.layer_dense_params(c), work_sdar.expert_params(c)
    head = work_sdar.head_params(c)
    # a tick in which nothing ran reads the dense matrices, and no head
    empty = {"calls": 1, "block_rows": 0, "masked_rows": 0, "expert_rows": 0,
             "experts_hit": 0, "kv_positions_live": 0}
    assert work_sdar.block_tick(c, empty) == {
        "flops": 0, "bytes": 2 * 7 * dense}
    # the issue's tick: 32 lanes, 128 rows, every expert of every layer
    # hit, a mean context of 1,100: 7 x 1.246 + 0.62 GB of weights and
    # 0.5 GB of keys and values, about 12 ms at 819 GB/s; 26 of the lanes
    # denoise with 2.5 positions masked on average
    live = 7 * 32 * 1104
    full = work_sdar.block_tick(c, dict(
        empty, block_rows=128, masked_rows=64, expert_rows=7 * 1024,
        experts_hit=7 * 128, kv_positions_live=live))
    assert full["bytes"] == 2 * (7 * dense + head) + 7 * 128 * 2 * expert + (
        live + 7 * 128) * 2048
    assert full["bytes"] == pytest.approx(9.85e9, rel=0.01)
    least, bound = work.roofline_seconds(full, "TPU v5 lite")
    assert bound == "hbm" and least == pytest.approx(12.0e-3, rel=0.02)
    assert full["flops"] == (
        2 * 128 * 7 * dense + 2 * 64 * head + 2 * 7 * 1024 * expert
        + 4 * 32 * 128 * 4 * live)
    assert full["flops"] / 197e12 < 1e-3
    # a masked row costs the head's 2 x 311 M, and nothing else moves
    more = work_sdar.block_tick(c, dict(
        empty, block_rows=128, masked_rows=65, expert_rows=7 * 1024,
        experts_hit=7 * 128, kv_positions_live=live))
    assert more["flops"] - full["flops"] == 2 * head
    assert more["bytes"] == full["bytes"]
    # block-causal: positions 0..3 meet 4 keys each, 4..5 eight each
    assert work_sdar.keys_met(c, 0, 6) == 4 * 4 + 2 * 8
    chunk = work_sdar.prefill_chunk(c, {
        "chunks": [(512, 512)], "expert_rows": 7 * 8 * 512,
        "experts_hit": 7 * 128})
    assert chunk["flops"] == (
        2 * 512 * 7 * dense + 2 * 7 * 8 * 512 * expert
        + 4 * 32 * 128 * 7 * work_sdar.keys_met(c, 512, 512))
    assert chunk["bytes"] == 2 * 7 * dense + 7 * 128 * 2 * expert + 7 * (
        512 + 512) * 2048
    least, bound = work.roofline_seconds(chunk, "TPU v5 lite")
    assert bound == "hbm" and least == pytest.approx(10.67e-3, rel=0.01)


def test_the_static_schedule_by_hand():
    """``work_sdar.stream_blocks`` against a hand count: a prompt of 6 and 7
    tokens of budget: the first block (positions 4..7) knows 2, takes 2
    denoising passes and a commit; the second (8..11) 4 and a commit; the
    last (12..15) delivers position 12 alone after 4 passes and is not
    committed."""
    c = load(BENCH, "configs", CONFIG + ".json")
    blocks = work_sdar.stream_blocks(c, 6, 7)
    assert blocks == [
        (4, 2, 4 * 3, 2 + 1, 4 * 3 * 8),
        (8, 4, 4 * 5, 4 + 3 + 2 + 1, 4 * 5 * 12),
        (12, 1, 4 * 4, 10, 4 * 4 * 16)]
    assert sum(n for _, n, *_ in blocks) == 7
    # a whole answer of the cell: 64 blocks of five passes but the last
    whole = work_sdar.stream_blocks(c, 256, 256)
    assert len(whole) == 64 and sum(rows for _, _, rows, *_ in whole) \
        == 4 * (64 * 5 - 1)
    two = dict(c, assumed=dict(c["assumed"], denoising_steps=2))
    assert work_sdar.stream_blocks(two, 5, 7) == [
        (4, 3, 4 * 3, 3 + 1, 4 * 3 * 8), (8, 4, 4 * 2, 4 + 2, 4 * 2 * 12)]


def test_readers_on_made_up_ticks():
    c = load(BENCH, "configs", CONFIG + ".json")
    ratio = importlib.import_module("benchmark.readers.tick_ratio").read
    sums = importlib.import_module("benchmark.readers.trace_program_sums").read
    mfu = importlib.import_module("benchmark.readers.work_mfu").read
    clock = importlib.import_module("benchmark.readers.client_clock").read
    live = 7 * 32 * 1104
    ticks = [{"kind": "decode", "t0": 1.0 + 0.02 * i, "lanes": tuple(range(32)),
              "context_tokens": 32 * 1100, "window_tokens": 32 * 1100,
              "block_rows": 128, "denoise_lanes": 26, "commit_lanes": 6,
              "masked_rows": 64, "tokens_out": 26,
              "experts_held": 7 * 128, "experts_hit": 7 * 128,
              "expert_rows": 7 * 1024, "expert_rows_max": 7 * 20,
              "kv_positions_live": live, "kv_positions_read": 7 * 32 * 1280}
             for i in range(10)]
    ticks += [{"kind": "prefill_chunk", "t0": 1.01, "lanes": (2,),
               "start": 512, "tokens": 300, "width": 512,
               "context_tokens": 812, "experts_held": 896, "experts_hit": 890,
               "expert_rows": 7 * 8 * 300, "expert_rows_max": 300,
               "kv_positions_live": 7 * 812, "kv_positions_read": 7 * 1280},
              {"kind": "decode", "t0": 1.5, "lanes": (0,),   # no counts yet
               "context_tokens": 100, "window_tokens": 100}]
    window = {"ticks": ticks, "seconds": 30.0, "traced_span": (1.0, 1.2),
              "series": {"block_gap_ms": [70.0, 72.0, 71.0, 90.0]},
              "counts": {"stored_tokens": 170000, "prefill_keys": 2e8,
                         "pass_rows": 64000, "head_rows": 32000,
                         "pass_keys": 8e7, "expert_rows": 15e6}}
    ctx = {"window": window, "config": c, "device_kind": "TPU v5 lite",
           "chips": 1, "trace": {"modules": {
               "jit_sdar_block_tick": [20, 0.30],
               "jit_sdar_prefill_chunk": [2, 0.06]}}}
    metric = {name: load(BENCH, "metrics", name + ".json")
              for name in NEW_METRICS}
    assert ratio(metric["passes_per_token"]["params"], ctx) == \
        pytest.approx(32 / 26)
    assert ratio(metric["commit_pass_share"]["params"], ctx) == \
        pytest.approx(6 / 32)
    assert clock(metric["block_gap_p50_ms"]["params"], ctx) == 71.0
    # twenty events of 15 ms against a floor of about 12 ms
    decode = sums(metric["sdar_decode_roofline_pct"]["params"], ctx)
    assert 75.0 < decode < 85.0
    prefill = sums(metric["sdar_prefill_roofline_pct"]["params"], ctx)
    assert 5.0 < prefill < 100.0
    # nothing to read: no trace, no such program (the parent's), ticks
    # without the counts, a window without the series
    params = metric["sdar_decode_roofline_pct"]["params"]
    assert sums(params, dict(ctx, trace=None)) is None
    assert sums(params, dict(ctx, trace={"modules": {}})) is None
    old = dict(window, ticks=[ticks[-1]], series={})
    assert sums(params, dict(ctx, window=old)) is None
    assert ratio(metric["passes_per_token"]["params"],
                 dict(ctx, window=old)) is None
    assert clock(metric["block_gap_p50_ms"]["params"],
                 dict(ctx, window=old)) is None
    step = metric["step_mfu_pct.sdar"]["params"]
    assert 0.0 < mfu(step, ctx) < 100.0
    assert mfu(step, dict(ctx, window={"seconds": 30.0})) is None


def test_the_cell_names_what_benchmark_json_lists():
    manifest = load(ROOT, "BENCHMARK.json")
    cell, config, driver, metrics, chips = run.load_cell(CELL)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert chips == 1 and cell["driver"] == "lm_sdar_stream"
    assert len(metrics) == 17 and "token_gap_p50_ms" not in cell["metrics"]
    assert {m["name"] for m in metrics} == {
        name for name, m in listed.items() if CELL in m["workloads"]}
    assert NEW_METRICS <= {m["name"] for m in metrics}
    for name in NEW_METRICS:
        assert listed[name]["workloads"][0] == CELL
    for m in metrics:
        assert listed[m["name"]]["moves"] == m["moves"]
        assert listed[m["name"]]["layer"] == m["layer"]
    for name in driver.END_TO_END:
        entry = next(m for m in manifest["end_to_end"] if m["name"] == name)
        assert CELL in entry["workloads"]
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["traffic"] == "fixedgen-c32" and entry["config"] == CONFIG
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    listed_config = next(x for x in manifest["configs"] if x["name"] == CONFIG)
    assert listed_config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert listed_config["source"] == config["source"]
    assert listed_config["reduced"] == config["reduced"]
    assert len(listed_config["why"]) <= 200
    traffic = cell["traffic"]
    assert traffic["clients"] == config["engine"]["max_slots"] == 32
    assert traffic["prompt_tokens"] == {"dist": "log_uniform", "min": 256,
                                        "max": 2048}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256,
                                        "max": 256}
    assert (traffic["block_length"], traffic["denoising_steps"],
            traffic["remasking_strategy"], traffic["temperature"]) == (
                4, 4, "low_confidence_static", 0)
    assert (traffic["block"], traffic["check_requests"],
            traffic["ramp_seconds"], traffic["shared_prefix_tokens"],
            traffic["first_index"], cell["trace_seconds"]) == (
                64, 4, 8, 0, 0, 4)
    engine = config["engine"]
    longest = traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
    assert longest <= engine["max_seq"] == 2560
    assert engine["pool_tokens"] == 32 * engine["max_seq"] == 81920
    assert (engine["block_size"], engine["prefill_chunk"],
            engine["min_bucket"], engine["prefix_cache"],
            engine["lane_counts"]) == (16, 512, 128, False, [32])
    assert set(cell["limits"]) == {"token_gap_mean", "place_gap_mean"}


def test_the_driver_end_to_end_at_a_tiny_size(capsys):
    result = run.main(["--workload", "sdar-tiny.fixedgen", "--seed",
                       "2147483999", "--seconds", "2", "--control", "1"],
                      require_tpu=False, roots=(TINY, BENCH))
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(last)[-1] == "checked" and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"]["tokens_per_s"]["value"] > 0
    assert last["metrics"]["setup_s"]["unit"] == "s"
    assert list(result["checked"]) == ["token_gap_mean", "place_gap_mean"]
    for c in result["checked"].values():
        assert c["value"] <= c["limit"]
    # the control, the reference in fp8 in the program's place, is not correct
    assert any(c["value"] > c["limit"] for c in result["control"].values())


def test_counts_follow_the_schedule_and_gaps_the_blocks():
    """The driver's counts against a hand count, and the series of block
    gaps: a prompt of 6 with 7 tokens of budget (``test_the_static_schedule
    _by_hand``'s stream), its first block's tokens at 1.0, the second's at
    1.5, the last's at 3.0, outside the interval."""
    from benchmark.drivers import lm_sdar_stream

    config = load(TINY, "configs", "sdar-tiny.json")
    cell = load(TINY, "workloads", "sdar-tiny.fixedgen.json")
    driver = lm_sdar_stream.Run(cell, config, 3, print)

    class Engine:
        def tick_trace(self):
            return [{"t0": 0.5, "expert_rows": 7}, {"t0": 1.5},
                    {"t0": 1.9, "expert_rows": 5}, {"t0": 2.5,
                                                    "expert_rows": 100}]

    driver.engine = Engine()
    record = {"prompt_tokens": 6, "max_tokens": 7, "tokens": list(range(7)),
              "times": [1.0, 1.0, 1.5, 1.5, 1.5, 1.5, 3.0]}
    assert driver.block_starts(record) == [0, 2, 6]
    counts = driver.counts([record], 0.0, 2.0)
    assert counts["output_tokens"] == 6 and counts["blocks"] == 2
    assert counts["stored_tokens"] == 4 and counts["prefill_keys"] == 16
    assert counts["pass_rows"] == 4 * 3 + 4 * 5
    assert counts["head_rows"] == 3 + 10
    assert counts["pass_keys"] == 4 * 3 * 8 + 4 * 5 * 12
    assert counts["expert_rows"] == 12
    need = work_sdar.tokens(config, counts)
    dense = work_sdar.layer_dense_params(config)
    assert need["flops"] == (
        2 * (4 + 32) * 3 * dense + 2 * 13 * work_sdar.head_params(config)
        + 2 * 12 * work_sdar.expert_params(config)
        + 4 * 4 * 32 * 3 * (16 + 336))
    assert driver.ids_under == 500 < config["vocab_size"]
