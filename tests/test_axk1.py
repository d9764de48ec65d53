"""The ``axk1`` family (``serve/models/axk1.py``: multi-head latent attention
over a paged latent cache, a leading dense layer, sigmoid-routed experts
with a routed scale and an added shared expert) at tiny sizes on the CPU,
float32 where logits are compared: the family's two steps and the engine
against the plain reference's full forward (``benchmark/reference_axk1.py``,
the expanded form only), the absorbed form against the expanded one on the
same cache, YaRN's frequencies and the softmax scale against the formulas
written out here, the share of an expert-parallel deployment against the
uncut layer, and ``experts.py`` unchanged for the family that had it."""

import functools
import math
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_axk1 as reference
from benchmark import weights_axk1 as weights
from client_tpu.ops import latent_prefill, paged_decode
from client_tpu.ops.grouped_matmul import grouped_matmul
from client_tpu.serve.lm import KvBlockPool, LmEngine
from client_tpu.serve.metrics import Registry
from client_tpu.serve.models import axk1, experts

CLOSE = LmEngine.CLOSE
BLOCK = 4
SEED = 5
HELD = (1, 2, 5, 6)

# a leading dense layer and three expert layers; a YaRN whose ramp lies
# inside the four rotary pairs (low 0, high 1) and whose factor moves the
# softmax scale; a latent (24) that is no whole tile, so the stored row
# (128) has padding behind latent + rope (32)
CONFIG = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 24,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "first_k_dense_replace": 1, "n_routed_experts": len(HELD),
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "num_hidden_layers": 4,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "vocab_size": 97,
    "rope_scaling": {"beta_fast": 4, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32, "type": "yarn"},
    "deployment": {"router_experts": 8, "experts_held": list(HELD)},
}
CFG = axk1.AxK1Config(
    vocab_size=97, d_model=32, n_layers=4, n_heads=4, q_lora_rank=16,
    kv_lora_rank=24, nope_dim=8, rope_dim=8, v_dim=8, first_dense=1,
    d_dense=48, d_ff=16, n_experts=8, top_k=2, experts_held=HELD, n_shared=1,
    routed_scale=2.5, rope_factor=4.0, rope_original=32, beta_fast=4.0,
    beta_slow=1.0, max_seq=160, dtype="float32")

# float32 through four layers, two formulations of one sum (a running
# softmax over groups of columns, or queries carried into the latent space,
# against a dense expanded softmax; a sorted grouped product against every
# expert on every row): rounding alone, measured under 1e-5; a wrong mask,
# rotary pair, scale or gate moves a logit by 1e-2 and more
TOL = 2e-5


@functools.lru_cache(maxsize=None)
def _params():
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), weights.axk1_params(CONFIG, SEED))


def _reference_logits(tokens):
    """float32 logits [T, V] of the plain reference's full forward."""
    tokens = np.asarray(tokens, np.int32)[None]
    ends = weights.axk1_ends(CONFIG, SEED)
    at = np.arange(tokens.shape[1], dtype=np.int32)[None]
    hidden = reference.hidden_states(
        CONFIG, tokens, at, ends,
        lambda i: weights.axk1_layer(CONFIG, SEED, i))[0]
    return np.asarray(reference.logits_at(CONFIG, hidden, ends))[0]


_PREFILL = jax.jit(axk1.prefill_step, static_argnums=(6, 7))
_DECODE = jax.jit(axk1.decode_step, static_argnums=(6, 7))


def _prefill(tokens, prompt_len, chunk, table, pool):
    params = _params()
    for start in range(0, prompt_len, chunk):
        n = min(chunk, prompt_len - start)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = tokens[start:start + n]
        logits, pool, _ = _PREFILL(
            params, jnp.asarray(padded), pool, table, jnp.int32(start),
            jnp.int32(prompt_len), CFG, BLOCK)
    return logits, pool


def _paged_forward(tokens, prompt_len, chunk=8):
    """Logits at positions ``prompt_len - 1 ..`` of ``tokens`` through the
    latent cache: the prompt in chunks (expanded), then one decode step a
    token (absorbed), over a table of shuffled blocks."""
    held = max(40, -(-len(tokens) // BLOCK) + 2)     # table columns
    kv = KvBlockPool(CFG, held + 8, BLOCK, lanes=1)
    table = jnp.asarray(
        np.random.default_rng(1).permutation(held + 8)[:held] + 1, jnp.int32)
    logits, pool = _prefill(tokens, prompt_len, chunk, table,
                            kv.pools["latent"])
    out = [np.asarray(logits)]
    for pos in range(prompt_len, len(tokens)):
        logits, pool, _ = _DECODE(
            _params(), jnp.asarray(tokens[pos:pos + 1], jnp.int32), pool,
            table[None], jnp.asarray([pos], jnp.int32), jnp.asarray([True]),
            CFG, BLOCK)
        out.append(np.asarray(logits)[0])
    return np.stack(out)


@pytest.mark.parametrize("prompt_len", [3, 8, 13, 64, 125, 253])
def test_chunks_then_decode_agree_with_the_reference(prompt_len):
    """Prefill in chunks of 8 and then decoding through the latent cache
    against the reference's full forward, every position compared: prompts
    inside a block, at a block's and a chunk's edge, and at and past the
    decode kernel's step and the chunk's group, both 128 positions (32
    blocks of 4), each decoded on across the next edge; the longest one
    decodes across two whole steps, where the decode kernel's first step
    takes its straight-line path (``paged_decode.full_steps``)."""
    tokens = np.random.default_rng(prompt_len).integers(
        0, CFG.vocab_size, prompt_len + 5).astype(np.int32)
    want = _reference_logits(tokens)[prompt_len - 1:]
    got = _paged_forward(tokens, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_the_cache_row_is_the_latent_and_the_shared_rotary_key():
    """What a position leaves in the pool: 24 + 8 values and zeros behind
    them, one row for all four heads, and no pool but ``latent``."""
    layers, blocks, lane_spec = CFG.state_spec
    assert (layers, blocks, lane_spec) == (4, {"latent": (1, None, 128)}, {})
    assert (CFG.row_width, CFG.value_width) == (128, 128)
    kv = KvBlockPool(CFG, 8, BLOCK, lanes=1)
    assert list(kv.pools) == ["latent"]
    assert kv.pools["latent"][0].shape == (9, 1, BLOCK, 128)
    tokens = np.arange(1, 7, dtype=np.int32)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    _, pool = _prefill(tokens, 6, 8, table, kv.pools["latent"])
    for layer in pool:
        rows = np.asarray(layer)[1:3, 0].reshape(8, 128)[:6]
        assert np.abs(rows[:, :32]).min() > 0 and not rows[:, 32:].any()
        assert not np.asarray(layer)[3:].any()       # nothing past the rows
    # at the published widths: 576 values in a row of 640, 1,280 B in bf16
    full = axk1.AxK1Config()
    assert (full.row_width, full.value_width) == (640, 512)
    assert full.state_spec[1] == {"latent": (1, None, 640)}


def test_absorbed_against_expanded_on_the_same_cache():
    """One layer's attention in both forms over one cache: the chunk's
    expanded read (keys and values rebuilt from latents) and the tick's
    absorbed read (queries carried into the latent space, the kernel over
    the rows as they lie) give the same mixed heads for the same query."""
    layer = _params()["layers"][1]
    rng = np.random.default_rng(3)
    n_pos = 70                                   # past a kernel's step of 64
    kv = KvBlockPool(CFG, 24, BLOCK, lanes=1)
    table = jnp.asarray(rng.permutation(24)[:20] + 1, jnp.int32)
    rows = np.zeros((n_pos, 128), np.float32)
    rows[:, :32] = rng.normal(size=(n_pos, 32))
    pool = kv.pools["latent"][0]
    pool = pool.at[table[np.arange(n_pos) // BLOCK], 0,
                   np.arange(n_pos) % BLOCK].set(jnp.asarray(rows))
    q_nope = jnp.asarray(rng.normal(size=(1, 1, 4, 8)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(1, 1, 4, 8)), jnp.float32)
    tick = axk1._DecodeView(CFG, table[None], jnp.asarray([n_pos - 1]),
                            jnp.asarray([True]), BLOCK)
    absorbed = tick.attend(q_nope, q_pe, pool, layer)
    chunk = axk1._PrefillView(CFG, 1, table, jnp.int32(n_pos - 1),
                              jnp.int32(n_pos), BLOCK)
    expanded = chunk.attend(q_nope, q_pe, pool, layer)
    assert absorbed.shape == expanded.shape == (1, 1, 32)
    assert np.abs(np.asarray(absorbed) - np.asarray(expanded)).max() < 2e-6


def test_yarn_frequencies_and_scale_against_the_formulas():
    """The program's constants against the formulas written out: at the
    published numbers (low 10, high 23, m = 1.3466) and at this file's."""
    full = axk1.AxK1Config()
    d = [64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(10000.0))
         for r in (32, 1)]
    low, high = math.floor(d[0]), math.ceil(d[1])
    assert (low, high) == (10, 23)
    want = []
    for i in range(32):
        f = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f / 32 * ramp + f * (1 - ramp))
    got = axk1.yarn_inv_freq(full)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[10] == pytest.approx(10000.0 ** (-20 / 64))       # kept
    assert got[23] == pytest.approx(10000.0 ** (-46 / 64) / 32)  # divided
    m = 0.1 * 1 * math.log(32) + 1
    assert m == pytest.approx(1.3466, abs=1e-4)
    assert full.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert axk1.rope_gain(full) == 1.0
    # the reference computes its own, from the configuration's keys
    published = dict(CONFIG, qk_rope_head_dim=64, qk_nope_head_dim=128,
                     rope_scaling={"beta_fast": 32, "beta_slow": 1,
                                   "factor": 32, "mscale": 1,
                                   "mscale_all_dim": 1, "type": "yarn",
                                   "original_max_position_embeddings": 4096})
    np.testing.assert_allclose(reference.inv_freq(published), want, rtol=1e-9)
    assert reference.softmax_scale(published) == pytest.approx(
        full.softmax_scale)
    np.testing.assert_allclose(axk1.yarn_inv_freq(CFG),
                               reference.inv_freq(CONFIG), rtol=1e-6)
    assert CFG.softmax_scale == pytest.approx(
        16 ** -0.5 * (0.1 * math.log(4) + 1) ** 2)
    # no scaling: the plain frequencies and the plain scale
    plain = axk1.AxK1Config(rope_factor=1.0)
    np.testing.assert_allclose(axk1.yarn_inv_freq(plain),
                               10000.0 ** (-np.arange(32) / 32), rtol=1e-6)
    assert plain.softmax_scale == pytest.approx(192 ** -0.5)


# -- the share of an expert-parallel deployment -------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Eight experts in four shares of two: the routed parts that the four
    shares compute under the routed scale, each told which two experts it
    holds under the router over all eight, plus the ONE shared expert
    counted once, are the uncut expert layer as the plain reference computes
    it with all eight held; and the leading dense layer, which every chip
    computes alike, is the reference's.  float32: the sum's order differs,
    nothing else."""
    layer = experts.init_params(jax.random.PRNGKey(0), 16, 8, 8, 8, 1,
                                jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (12, 16), jnp.float32)
    real = jnp.ones((12,), bool)
    total = experts.shared(h, layer, 1)
    rows = 0
    for share in range(4):
        held = (2 * share, 2 * share + 1)
        mine = dict(layer, w_gate_up=layer["w_gate_up"][2 * share:][:2],
                    w_down=layer["w_down"][2 * share:][:2])
        part, counts = experts.routed(h, mine, held, 3, real, scale=2.5)
        total = total + part
        rows += int(counts[1])
    assert rows == 12 * 3                      # every pair fell on one share
    want = reference.moe_ffn(h, layer, tuple(range(8)), 3, 2.5)
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 1e-5
    # without the scale the routed part is 2.5 times smaller: it is applied
    plain = reference.moe_ffn(h, layer, tuple(range(8)), 3, 1.0)
    assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 1e-2
    dense = _params()["layers"][0]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 32), jnp.float32)
    assert np.abs(np.asarray(axk1._dense_ffn(x, dense))
                  - np.asarray(reference.dense_ffn(x, dense))).max() < 1e-5


def _routed_before(h, layer, held, top_k, real):
    """``experts.routed`` as it was before it took a scale (PR 33), word
    for word."""
    t = h.shape[0]
    n_experts = layer["router"].shape[-1]
    n_held = len(held)
    picks, weights = experts.route(h, layer["router"], top_k)
    local = jnp.full((n_experts,), n_held, jnp.int32).at[
        jnp.asarray(held, jnp.int32)].set(jnp.arange(n_held, dtype=jnp.int32))
    group = jnp.where(real[:, None], local[picks], n_held).reshape(-1)
    pairs = t * top_k
    group = jnp.pad(group, (0, -pairs % experts.ROW_TILE),
                    constant_values=n_held)
    order = jnp.argsort(group, stable=True)
    group_sizes = jnp.bincount(group, length=n_held + 1)[:n_held].astype(
        jnp.int32)
    rows = jnp.take(h, order // top_k, axis=0, mode="clip")
    act = experts._swiglu(grouped_matmul(rows, layer["w_gate_up"],
                                         group_sizes))
    out = grouped_matmul(act.astype(h.dtype), layer["w_down"], group_sizes)
    back = jnp.argsort(order)[:pairs].reshape(t, top_k)
    mine = jnp.where((group[:pairs] < n_held).reshape(t, top_k, 1),
                     jnp.take(out, back, axis=0).astype(jnp.float32), 0.0)
    counts = jnp.stack([jnp.sum(group_sizes > 0), jnp.sum(group_sizes),
                        jnp.max(group_sizes)]).astype(jnp.int32)
    return jnp.sum(mine * weights[:, :, None], axis=1), counts


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_experts_without_a_scale_compute_what_they_did(dtype):
    """The family that had ``experts.py`` before passes no scale: its routed
    sum and its ``ffn`` are bit-equal to the function as it was, and trace
    to the same program (no multiplication by one)."""
    layer = experts.init_params(jax.random.PRNGKey(3), 16, 8, 8, 4, 2,
                                jnp.dtype(dtype))
    h = jax.random.normal(jax.random.PRNGKey(4), (10, 16), jnp.dtype(dtype))
    real = jnp.arange(10) != 7
    held = (1, 2, 5, 6)
    was, was_counts = _routed_before(h, layer, held, 2, real)
    now, now_counts = experts.routed(h, layer, held, 2, real)
    assert np.array_equal(np.asarray(was), np.asarray(now))
    assert np.array_equal(np.asarray(was_counts), np.asarray(now_counts))
    both, _ = experts.ffn(h, layer, held, 2, 2, real)
    assert np.array_equal(
        np.asarray(both), np.asarray(was + experts.shared(h, layer, 2)))
    trace = functools.partial(jax.make_jaxpr, static_argnums=(2, 3))
    assert str(trace(experts.routed)(h, layer, held, 2, real)) == str(
        trace(_routed_before)(h, layer, held, 2, real))
    scaled, _ = experts.routed(h, layer, held, 2, real, scale=2.5)
    np.testing.assert_allclose(
        np.asarray(scaled, np.float32), 2.5 * np.asarray(now, np.float32),
        rtol=1e-5 if dtype == "float32" else 1e-2, atol=1e-6)


# -- through the engine ---------------------------------------------------------

def _collect(q, timeout=300):
    out = []
    while True:
        tok = q.get(timeout=timeout)
        if tok is CLOSE:
            return out
        out.append(tok)


def _engine(**kwargs):
    args = dict(max_slots=2, lane_counts=(2,), block_size=BLOCK,
                prefill_chunk=8, min_bucket=4)
    args.update(kwargs)
    return LmEngine(_params(), CFG, **args)


def _assert_follows_reference(prompt, served):
    """Every served token is the reference's best at its position, or lies
    within float32 rounding of it (a tie)."""
    logits = _reference_logits(list(prompt) + list(served))
    for i, token in enumerate(served):
        row = logits[len(prompt) - 1 + i]
        assert row.max() - row[token] < 1e-4, (i, token)


def test_engine_streams_follow_the_reference_and_count():
    """Two streams at once through ``LmEngine`` (chunked prefill, batched
    ticks, the latent pool): tokens as the reference ranks them, and every
    entry of ``tick_trace()`` that dispatched device work carries the
    family's fields, the device's counts consistent with the host's."""
    reg = Registry()
    eng = _engine(registry=reg)
    prompts = [tuple(range(1, 6)), tuple(range(7, 30))]
    try:
        queues = [eng.submit(list(p), 6)[0] for p in prompts]
        served = [_collect(q) for q in queues]
        for _ in range(200):            # the observer fills the counts in
            ticks = eng.tick_trace()
            if all("expert_rows" in t for t in ticks):
                break
            time.sleep(0.02)
    finally:
        eng.close()
    for prompt, tokens in zip(prompts, served):
        assert len(tokens) == 6
        _assert_follows_reference(prompt, tokens)
    assert {t["kind"] for t in ticks} == {"decode", "prefill_chunk"}
    held = (CFG.n_layers - CFG.first_dense) * len(HELD)
    for t in ticks:
        assert t["experts_held"] == held
        assert 0 <= t["experts_hit"] <= min(held, t["expert_rows"])
        assert t["expert_rows_max"] <= t["expert_rows"]
        assert t["kv_positions_read"] >= t["kv_positions_live"] > 0
        assert ("kv_rows_rebuilt" in t) == (t["kind"] == "prefill_chunk")
        rows = len(t["lanes"]) if t["kind"] == "decode" else t["tokens"]
        assert t["expert_rows"] <= rows * CFG.top_k * 3
    for t in (t for t in ticks if t["kind"] == "decode"):
        assert t["window_tokens"] == t["context_tokens"]   # no window
        assert t["kv_positions_live"] == CFG.n_layers * (
            t["context_tokens"] + len(t["lanes"]))
    assert reg.get("ctpu_lm_expert_rows_total") == sum(
        t["expert_rows"] for t in ticks)
    assert reg.get("ctpu_lm_experts_hit_total") == sum(
        t["experts_hit"] for t in ticks)


def test_tick_fields_count_what_the_programs_read():
    """``kv_positions_live``, ``kv_positions_read`` and a chunk's
    ``kv_rows_rebuilt`` by hand: block 4, the decode kernel's step over
    latent rows is 32 blocks, 128 positions, and so is a chunk's group of 32
    blocks; four layers, every one over the whole context.  What a tick
    reads is the decode kernel's own trip count (``paged_decode.steps_read``
    for a latent pool), what a chunk reads the chunk kernel's
    (``latent_prefill.groups_read``) times its group."""
    programs = CFG.family(CFG, BLOCK)
    # lanes of lengths 135 and 10 attend 136 and 11 rows: two steps and one
    got = programs.tick_fields("decode", [135, 10])
    assert got == {"kv_positions_live": 4 * (136 + 11),
                   "kv_positions_read": 4 * (256 + 128),
                   "window_tokens": 145,    # no window: the contexts' sum
                   "kv_steps": 4 * 3, "kv_steps_full": 0}
    # 400 rows: four steps a layer, of the three whole ones the first two
    # on the kernel's straight-line path (the step ahead is whole too);
    # 256 rows: two whole steps, the first on it
    got = programs.tick_fields("decode", [399, 255])
    assert (got["kv_steps"], got["kv_steps_full"]) == (4 * (4 + 2), 4 * 3)
    assert got["kv_steps"] * 128 == got["kv_positions_read"]
    assert programs._tick_reads(np.array([127, 128]), 40) == [128, 256]
    for lengths in ([0], [127, 128, 129], [511, 640, 1000]):
        reads = programs._tick_reads(np.array(lengths), 40)
        assert reads == (paged_decode.steps_read(
            np.array(lengths) + 1, BLOCK, latent=True) * 128).tolist()
    # a chunk of 8 rows from 124, 5 of them real: 129 rows may be seen, and
    # the chunk's last row (131) lies in the second group
    got = programs.tick_fields("prefill_chunk", [129], start=124, width=8)
    assert got == {"kv_positions_live": 4 * 129,
                   "kv_positions_read": 4 * 256, "kv_rows_rebuilt": 4 * 256}
    assert programs.attended_positions(127, 40) == 128
    span = latent_prefill.group_span(BLOCK)
    for start, width in ((0, 8), (120, 8), (121, 8), (128, 4), (380, 8)):
        trips = latent_prefill.groups_read(start + width - 1, BLOCK)
        got = programs.tick_fields("prefill_chunk", [start + width],
                                   start=start, width=width)
        assert got["kv_positions_read"] == got["kv_rows_rebuilt"] \
            == CFG.n_layers * trips * span
        assert programs.attended_positions(start + width - 1, 40) \
            == trips * span
    assert programs.window is None and not programs.recurrent


def test_a_block_that_is_no_whole_tile_is_refused_on_the_chip(monkeypatch):
    """The tick reads the latent blocks in place; compiled for the chip a
    block has to be whole tiles of its type (16 bf16 rows), and a
    configuration that is not says so when the programs are built."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf16 = axk1.AxK1Config(dtype="bfloat16")
    with pytest.raises(ValueError, match="whole tile"):
        axk1.AxK1Programs(bf16, 8)
    assert axk1.AxK1Programs(bf16, 16).donate == (2,)


def test_prefix_adoption_shares_latent_blocks():
    """A lane IS its blocks here: a second prompt with the same first three
    blocks adopts them, prefills its tail alone and streams the same tokens
    as an engine that adopts nothing."""
    shared = tuple(range(1, 13))                       # three blocks of 4
    prompts = [shared + (40 + i,) for i in range(2)]
    served = {}
    for adopt in (True, False):
        reg = Registry()
        eng = _engine(registry=reg, prefix_cache=adopt)
        try:
            served[adopt] = [_collect(eng.submit(list(p), 4)[0])
                             for p in prompts]
            stats = eng.prefix_stats()
        finally:
            eng.close()
        if adopt:
            assert stats["hits"] == 3
            assert reg.get("ctpu_lm_prefill_tokens_saved_total") == 12
        assert eng.kv.used_blocks == 0, eng.kv.ref_counts()
    assert served[True] == served[False]
    for prompt, tokens in zip(prompts, served[True]):
        _assert_follows_reference(prompt, tokens)


def test_preemption_swaps_the_latent_blocks_out_and_back():
    """``tests/test_lm.py``'s scenario for this family: the pool cannot
    hold the high-priority stream beside the low one, so the low lane's
    latent blocks go to the host (``KvBlockPool.read_blocks``: one pool,
    no key/value pair) and come back; both streams are the reference's
    greedy continuations, the same tokens as without the pressure."""
    pa, pb = (1, 2, 3), (9, 4)
    roomy = _engine()
    try:
        calm = [_collect(roomy.submit(list(p), n)[0])
                for p, n in ((pa, 150), (pb, 18))]
    finally:
        roomy.close()
    eng = _engine(pool_tokens=160, tenant_priority={"hi": 10.0})
    moved = []
    try:
        qa, _ = eng.submit(list(pa), 150, tenant="lo")  # 39 of 40 blocks
        first = qa.get(timeout=300)
        read = eng.kv.read_blocks
        eng.kv.read_blocks = lambda blocks: moved.append(
            read(blocks)) or moved[-1]
        qb, _ = eng.submit(list(pb), 18, tenant="hi")   # 5: must preempt
        got = {"a": [first], "b": []}
        threads = [threading.Thread(
            target=lambda q=q, name=name: got[name].extend(_collect(q)),
            daemon=True) for name, q in (("a", qa), ("b", qb))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
            assert not t.is_alive(), "stream wedged across preemption"
        stats = eng.preempt_stats()
    finally:
        eng.close()
    assert [got["a"], got["b"]] == calm
    _assert_follows_reference(pa, got["a"])
    assert stats["preemptions"] >= 1 and "swap" not in stats
    assert stats["resumes"] == stats["preemptions"]
    assert moved and all(list(host) == ["latent"] for host in moved)
    assert all(len(host["latent"]) == CFG.n_layers
               and host["latent"][0].shape[1:] == (1, BLOCK, 128)
               for host in moved)
    assert eng.kv.used_blocks == 0, eng.kv.ref_counts()


def test_fleet_export_carries_the_one_pool():
    """A finished prefill publishes its full blocks to the fleet tier as
    the pools the family owns: here ``latent`` alone, a layer an array."""
    seen = []

    class Tier:
        def export_prefix(self, row, n_blocks, block_size, pools):
            seen.append((n_blocks, block_size, pools))

        def prefix_lookup(self, *args, **kw):
            return None

        def note_engine(self, *args, **kw):
            pass

    eng = _engine(prefix_cache=True, fleet=Tier())
    try:
        _collect(eng.submit(list(range(1, 14)), 2)[0])
    finally:
        eng.close()
    assert seen
    n_blocks, block_size, pools = seen[0]
    assert (n_blocks, block_size, list(pools)) == (3, BLOCK, ["latent"])
    assert [a.shape for a in pools["latent"]] == [(3, 1, BLOCK, 128)] * 4
    assert np.abs(pools["latent"][0][:, 0, :, :32]).min() > 0
