"""The ``longcat_flash`` cell's own files (configuration, driver, readers'
metric files, work functions, weights) on the CPU at the tiny configuration
of ``tiny/``, as ``test_axk1_benchmark.py`` does for its cell.
``tests/test_longcat_benchmark.py`` imports these so that ``pytest tests/``
counts them.

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

from benchmark import run, work, work_longcat  # noqa: E402

TINY = os.path.join(HERE, "tiny")
CONFIG = "longcat-flash-omni-ep32-d4"
CELL = CONFIG + ".dialog-c32"
NEW_METRICS = {
    "step_mfu_pct.longcat", "step_mfu_pct.longcat_ttft",
    "longcat_decode_roofline_pct", "longcat_prefill_roofline_pct",
    "zero_pick_share"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# the language model's keys as published (``config.json`` of LongCat-Flash-Omni,
# the configuration's ``source``)
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers":
    28, "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 512,
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-05, "rope_theta":
    10000000, "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


@pytest.fixture(scope="module", autouse=True)
def native():
    run.build_native()


def test_the_configuration_keeps_every_published_key():
    body = load(BENCH, "configs", CONFIG + ".json")
    assert body["source"] == ("https://huggingface.co/meituan-longcat/"
                              "LongCat-Flash-Omni/blob/main/config.json")
    assert body["reduced"] == ["num_layers", "n_routed_experts",
                               "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in body["reduced"]:
            assert body["published"][key] == value, key
        else:
            assert body[key] == value, key
    # the floors of a cut: four of the double layers (all alike, no leading
    # dense one), 8 experts or more, an eighth of the vocabulary; the router
    # keeps its width and the zero slots
    share = body["deployment"]
    assert body["num_layers"] >= 4
    assert share["router_experts"] == body["published"]["n_routed_experts"] \
        == 512
    assert body["zero_expert_num"] == 256 and body["moe_topk"] == 12
    assert share["experts_held"] == list(range(16))
    assert len(share["experts_held"]) == body["n_routed_experts"] >= 8
    assert share["chips_per_layer"] * body["n_routed_experts"] == 512
    assert 8 * body["vocab_size"] == body["published"]["vocab_size"]
    assert share["vocab_rows"] == [0, body["vocab_size"]]
    for key in ("block", "attention", "lora_scale_folding", "rope", "router",
                "zero_experts", "head", "bias_scale", "weights"):
        assert key in body["assumed"], key
    assert "bytes" in share and "stated" in share
    engine = body["engine"]
    assert (engine["max_slots"], engine["lane_counts"], engine["max_seq"],
            engine["block_size"], engine["pool_tokens"],
            engine["prefill_chunk"], engine["prefix_cache"]) == (
                32, [32], 8704, 16, 278528, 512, False)


def test_work_counts_against_the_issues_arithmetic():
    c = load(BENCH, "configs", CONFIG + ".json")
    assert work_longcat.sublayers(c) == 8
    assert work_longcat.layer_dense_params(c) == 638844928
    assert work_longcat.expert_params(c) == 37748736
    assert work_longcat.dense_ffn_params(c) == 226492416
    assert work_longcat.router_params(c) == 6144 * 768
    assert work_longcat.head_params(c) == 16384 * 6144
    # 5,172,625,408 parameters, 10.35 GB in bf16
    assert work_longcat.held_params(c) == 5172625408
    # an empty tick reads what every row meets, once: four double layers'
    # dense matrices and the head, 5.31 GB
    empty = {"calls": 1, "lane_steps": 0, "expert_rows": 0, "experts_hit": 0,
             "kv_positions_live": 0}
    tick = work_longcat.decode_tick(c, empty)
    assert tick["flops"] == 0 and tick["bytes"] == 5312086016
    # the issue's tick: 32 lanes at a mean context of 3,730, 6.3 of 16 held
    # experts hit a layer: 1.1 GB of latents, 1.9 GB of hit experts
    live = 8 * 32 * 3730
    full = work_longcat.decode_tick(c, dict(
        empty, lane_steps=32, expert_rows=26, experts_hit=25,
        kv_positions_live=live))
    assert full["bytes"] - tick["bytes"] == 25 * 75497472 \
        + (live + 8 * 32) * 1152
    assert live * 1152 == pytest.approx(1.1e9, rel=0.01)
    least, bound = work.roofline_seconds(full, "TPU v5 lite")
    assert bound == "hbm" and least == pytest.approx(10.1e-3, rel=0.02)
    # a routed pair costs one expert's 2 x 37.7 M FLOPs, a zero pick none
    more = work_longcat.decode_tick(c, dict(
        empty, lane_steps=32, expert_rows=27, experts_hit=25,
        kv_positions_live=live))
    assert more["flops"] - full["flops"] == 2 * 37748736
    assert full["flops"] == 2 * 32 * (4 * 638844928 + 16384 * 6144) \
        + 2 * 26 * 37748736 + 2 * 64 * (2 * 512 + 64) * live
    # a chunk of 512 rows at start 0: 2.6 TFLOP of dense matrices against
    # 5.3 GB of them and all 64 held experts, 4.8 GB: FLOPs by a little
    chunk = work_longcat.prefill_chunk(c, {
        "chunks": [(0, 512)], "expert_rows": 128, "experts_hit": 64})
    assert chunk["flops"] == pytest.approx(
        2 * 512 * 4 * 638844928 + 2 * 16384 * 6144 + 2 * 128 * 37748736
        + 8 * 40960 * (512 * 513 // 2), rel=1e-12)
    assert chunk["bytes"] == 5312086016 + 64 * 75497472 + 8 * 512 * 1152
    least, bound = work.roofline_seconds(chunk, "TPU v5 lite")
    assert bound == "flops" and least == pytest.approx(13.55e-3, rel=0.01)
    step = work_longcat.tokens(c, {
        "prompt_tokens": 1000, "output_tokens": 10, "expert_rows": 2020,
        "context_sum": 7000, "decode_context_sum": 2000})
    assert step["flops"] == 2 * 1010 * 4 * 638844928 \
        + 2 * 10 * 16384 * 6144 + 2 * 2020 * 37748736 \
        + 8 * (40960 * 5000 + 139264 * 2000)


def _ticks():
    ticks = [{"kind": "decode", "t0": 1.0 + 0.02 * i,
              "lanes": tuple(range(32)), "context_tokens": 32 * 3730,
              "window_tokens": 32 * 3730, "experts_held": 64,
              "experts_hit": 25, "expert_rows": 26, "expert_rows_max": 3,
              "zero_pairs": 512, "pairs": 1536,
              "kv_positions_live": 8 * 32 * 3731,
              "kv_positions_read": 8 * 32 * 3840} for i in range(10)]
    ticks += [{"kind": "prefill_chunk", "t0": 1.01, "lanes": (2,),
               "start": 4096, "tokens": 512, "width": 512,
               "context_tokens": 4608, "experts_held": 64, "experts_hit": 64,
               "expert_rows": 500, "expert_rows_max": 20, "zero_pairs": 8000,
               "pairs": 24576, "kv_positions_live": 8 * 4608,
               "kv_positions_read": 8 * 4608},
              {"kind": "decode", "t0": 1.5, "lanes": (0,),   # no counts yet
               "context_tokens": 100, "window_tokens": 100}]
    return ticks


def test_readers_on_made_up_ticks():
    c = load(BENCH, "configs", CONFIG + ".json")
    ratio = importlib.import_module("benchmark.readers.tick_ratio").read
    sums = importlib.import_module("benchmark.readers.trace_program_sums").read
    mfu = importlib.import_module("benchmark.readers.work_mfu").read
    ticks = _ticks()
    window = {"ticks": ticks, "seconds": 30.0, "traced_span": (1.0, 1.2),
              "counts": {"prompt_tokens": 120000, "output_tokens": 35000,
                         "expert_rows": 40000, "context_sum": 4e8,
                         "decode_context_sum": 1.2e8}}
    ctx = {"window": window, "config": c, "device_kind": "TPU v5 lite",
           "chips": 1, "trace": {"modules": {
               "jit_longcat_decode_tick": [20, 0.28],
               "jit_longcat_prefill_chunk": [2, 0.06]}}}
    metric = {name: load(BENCH, "metrics", name + ".json")
              for name in NEW_METRICS}
    # a third of a decode tick's pairs on zero slots; the chunk's are not
    # read (decode ticks only)
    assert ratio(metric["zero_pick_share"]["params"], ctx) == \
        pytest.approx(1 / 3)
    # twenty events of 14 ms against a floor of about 10.1 ms
    decode = sums(metric["longcat_decode_roofline_pct"]["params"], ctx)
    assert 65.0 < decode < 80.0
    # two events of 30 ms against 3.4 TFLOP at the peak, 17.2 ms
    prefill = sums(metric["longcat_prefill_roofline_pct"]["params"], ctx)
    assert 50.0 < prefill < 65.0
    # nothing to read: no trace, no such program (one from before the
    # family has none),
    # ticks without the counts
    for name in ("longcat_decode_roofline_pct",
                 "longcat_prefill_roofline_pct"):
        params = metric[name]["params"]
        assert sums(params, dict(ctx, trace=None)) is None
        assert sums(params, dict(ctx, trace={"modules": {}})) is None
        old = dict(window, ticks=[ticks[-1]])
        assert sums(params, dict(ctx, window=old)) is None
    assert ratio(metric["zero_pick_share"]["params"],
                 dict(ctx, window=dict(window, ticks=[ticks[-1]]))) is None
    step = metric["step_mfu_pct.longcat"]["params"]
    assert 0.0 < mfu(step, ctx) < 100.0
    assert mfu(step, ctx) == mfu(
        metric["step_mfu_pct.longcat_ttft"]["params"], ctx)
    assert mfu(step, dict(ctx, window={"seconds": 30.0})) is None


def test_the_cell_names_what_benchmark_json_lists():
    manifest = load(ROOT, "BENCHMARK.json")
    cell, config, driver, metrics, chips = run.load_cell(CELL)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert chips == 1 and cell["driver"] == "lm_longcat_stream"
    assert len(metrics) == 16
    assert {m["name"] for m in metrics} == {
        name for name, m in listed.items() if CELL in m["workloads"]}
    assert NEW_METRICS <= {m["name"] for m in metrics}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL]
    for m in metrics:
        assert listed[m["name"]]["moves"] == m["moves"]
        assert listed[m["name"]]["layer"] == m["layer"]
        assert listed[m["name"]]["workloads"][-1] == CELL
    for name in driver.END_TO_END:
        entry = next(m for m in manifest["end_to_end"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry == manifest["workloads"][-1]
    assert entry["traffic"] == "dialog-c32" and entry["config"] == CONFIG
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    listed_config = next(x for x in manifest["configs"] if x["name"] == CONFIG)
    assert listed_config == manifest["configs"][-1]
    assert listed_config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert listed_config["reduced"] == config["reduced"]
    traffic = cell["traffic"]
    assert traffic["clients"] == config["engine"]["max_slots"] == 32
    assert traffic["prompt_tokens"] == {"dist": "log_uniform", "min": 1024,
                                        "max": 8192}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 128,
                                        "max": 512}
    assert (traffic["block"], traffic["check_requests"],
            traffic["ramp_seconds"], traffic["shared_prefix_tokens"],
            cell["trace_seconds"]) == (64, 4, 8, 0, 4)
    longest = traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
    assert longest == config["engine"]["max_seq"]
    assert config["engine"]["pool_tokens"] == 32 * longest


def test_the_driver_end_to_end_at_a_tiny_size(capsys):
    result = run.main(["--workload", "longcat-tiny.dialog", "--seed",
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


def _driver(seed=3):
    from benchmark.drivers import lm_longcat_stream

    config = load(TINY, "configs", "longcat-tiny.json")
    cell = load(TINY, "workloads", "longcat-tiny.dialog.json")
    return lm_longcat_stream.Run(cell, config, seed, print)


def test_the_driver_builds_the_program_the_configuration_describes():
    """Every width of the tiny configuration reaches the program's own, the
    share and the zero slots among them; the pool the engine builds is two
    latent rows a double layer."""
    model = _driver().build_model()
    try:
        cfg = model.runner.scheduler.cfg
        assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
                cfg.nope_dim, cfg.rope_dim, cfg.v_dim) == (
                    64, 4, 32, 32, 16, 8, 16)
        assert (cfg.d_dense, cfg.d_ff, cfg.n_experts, cfg.n_zero, cfg.top_k,
                cfg.experts_held, cfg.routed_scale) == (
                    96, 32, 16, 8, 4, (4, 5, 6, 7), 6.0)
        assert (cfg.q_gain, cfg.kv_gain, cfg.rope_theta, cfg.norm_eps) == (
            2 ** 0.5, 2 ** 0.5, 10000.0, 1e-5)
        assert cfg.state_spec == (4, {"latent": (1, None, 128)}, {})
        layer = model.runner.scheduler.params["layers"][0]
        assert set(layer) == {"attn", "mlp", "moe"}
        assert layer["moe"]["router"].shape == (64, 24)
    finally:
        model.runner.scheduler.close()


def test_a_program_without_the_family_exits_at_once(monkeypatch):
    """Where ``client_tpu.serve.models.longcat`` is missing (a commit from
    before the family), ``lm_longcat_stream`` stops before it makes a
    weight, with an exit that names what is missing."""
    import client_tpu.serve.models as models

    monkeypatch.setitem(sys.modules, "client_tpu.serve.models.longcat", None)
    monkeypatch.delattr(models, "longcat", raising=False)
    with pytest.raises(SystemExit, match="longcat_flash"):
        _driver().build_model()


def test_weights_stand_at_fan_in_scale_but_the_down_projections():
    """Every matrix a fan-in scaled normal; the down projections
    ``DOWN_GAIN`` times that; the selection bias float32 at one over the
    router's slots; the served tree and the reference's layers come from
    one program."""
    from benchmark import weights_longcat

    config = load(TINY, "configs", "longcat-tiny.json")
    layer = weights_longcat.longcat_layer(config, 7, 1)

    def gain(w, fan_in):
        return float(np.asarray(w, np.float32).std()) * fan_in ** 0.5

    d, ff, wide = (config["hidden_size"], config["expert_ffn_hidden_size"],
                   config["ffn_hidden_size"])
    a, m, moe = layer["attn"][1], layer["mlp"][0], layer["moe"]
    # within four deviations of each matrix's own sample (3% at the least)
    for w, fan_in, want in (
            (a["w_qa"], d, 1.0), (a["w_qb"], 32, 1.0), (a["w_kva"], d, 1.0),
            (a["w_uk"], 32, 1.0), (a["w_uv"], 32, 1.0), (a["w_o"], 64, 1.0),
            (moe["router"], d, 1.0), (moe["w_gate_up"], d, 1.0),
            (moe["w_down"], ff, weights_longcat.DOWN_GAIN),
            (m["w_gate_up"], d, 1.0),
            (m["w_down"], wide, weights_longcat.DOWN_GAIN)):
        assert gain(w, fan_in) == pytest.approx(
            want, rel=max(0.03, 4 * (2 * w.size) ** -0.5))
    slots = config["deployment"]["router_experts"] + config["zero_expert_num"]
    assert moe["bias"].dtype == np.float32 and moe["bias"].shape == (slots,)
    assert float(np.asarray(moe["bias"]).std()) * slots == pytest.approx(
        1.0, rel=0.5)
    assert moe["w_gate_up"].shape == (4, 64, 64)
    assert a["w_uk"].shape == (4, 16, 32) and a["w_uv"].shape == (4, 32, 16)
    params = weights_longcat.longcat_params(config, 7)
    np.testing.assert_array_equal(
        np.asarray(params["layers"][1]["attn"][1]["w_qb"], np.float32),
        np.asarray(a["w_qb"], np.float32))
    assert not np.array_equal(np.asarray(layer["attn"][0]["w_qa"]),
                              np.asarray(a["w_qa"]))
    assert not np.array_equal(np.asarray(params["embed"], np.float32),
                              np.asarray(params["head"], np.float32))


def test_counts_sum_the_routed_pairs_and_no_window():
    driver = _driver()

    class Engine:
        def tick_trace(self):
            return [{"t0": 0.5, "expert_rows": 7}, {"t0": 1.5},
                    {"t0": 1.9, "expert_rows": 5}, {"t0": 2.5,
                                                    "expert_rows": 100}]

    driver.engine = Engine()
    record = {"prompt_tokens": 20, "times": [1.0, 1.1, 1.2, 5.0]}
    counts = driver.counts([record], 0.0, 2.0)
    assert counts["context_sum"] == 20 * 21 // 2 + 21 + 22
    assert counts["decode_context_sum"] == 43
    assert counts["output_tokens"] == 3 and counts["lane_steps"] == 2
    assert counts["expert_rows"] == 12 and "window_context_sum" not in counts
    assert counts["chunks"] == [(0, 20)]
