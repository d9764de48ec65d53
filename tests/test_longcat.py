"""The ``longcat_flash`` family (``serve/models/longcat.py``: double layers of
multi-head latent attention with LoRA scales, dense feed-forwards and a
shortcut mixture of experts whose router picks under a selection bias among
routed experts and zero-compute slots) at tiny sizes on the CPU, float32
where logits are compared: the family's two steps and the engine against
the plain reference's full forward (``benchmark/reference_longcat.py``, the
expanded form only), the share of an expert-parallel deployment against the
uncut layer, the zero slots, the bias, the unnormalised weights and the
device's counts against a recount on the host."""

import functools
import math
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference
from benchmark import reference_longcat as ref
from benchmark import weights_longcat as weights
from client_tpu.serve.lm import KvBlockPool, LmEngine
from client_tpu.serve.metrics import Registry
from client_tpu.serve.models import axk1, experts, longcat

CLOSE = LmEngine.CLOSE
BLOCK = 4
SEED = 5
HELD = tuple(range(1, 32, 2))        # 16 of the 32 routed experts

# two double layers, 16 held of 32 routed experts and 16 zero slots, top 6;
# LoRA scales of sqrt(2) and sqrt(4 / 3); a latent (24) that is no whole
# tile, so the stored row (128) has padding behind latent + rope (32); a
# rotary base at which every pair of a head of 8 turns within 250 positions
CONFIG = {
    "hidden_size": 32, "ffn_hidden_size": 48, "expert_ffn_hidden_size": 16,
    "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 24,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "n_routed_experts": len(HELD), "zero_expert_num": 16, "moe_topk": 6,
    "routed_scaling_factor": 6, "num_layers": 2, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "vocab_size": 97,
    "deployment": {"router_experts": 32, "experts_held": list(HELD)},
}
CFG = longcat.LongcatConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, q_lora_rank=16,
    kv_lora_rank=24, nope_dim=8, rope_dim=8, v_dim=8, d_dense=48, d_ff=16,
    n_experts=32, n_zero=16, top_k=6, experts_held=HELD, routed_scale=6.0,
    rope_theta=10000.0, max_seq=160, dtype="float32")

# float32 through two double layers, two formulations of one sum (a running
# softmax over groups of columns, or queries carried into the latent space,
# against a dense expanded softmax; a sorted grouped product against every
# expert on every row; the LoRA scales folded into the norms against
# applied after them): rounding alone, measured under 1e-5; a wrong mask,
# rotary pair, scale, gate, zero slot or LoRA scale moves a logit by 1e-2
# and more, and the reference in fp8 by more than 0.1
TOL = 2e-5


@functools.lru_cache(maxsize=None)
def _params():
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), weights.longcat_params(CONFIG, SEED))


def _reference_logits(tokens, quant=None):
    """float32 logits [T, V] of the plain reference's full forward."""
    tokens = np.asarray(tokens, np.int32)[None]
    ends = weights.longcat_ends(CONFIG, SEED)
    at = np.arange(tokens.shape[1], dtype=np.int32)[None]
    hidden = ref.hidden_states(
        CONFIG, tokens, at, ends,
        lambda i: weights.longcat_layer(CONFIG, SEED, i), (quant,))[0]
    return np.asarray(ref.logits_at(CONFIG, hidden, ends, quant))[0]


_PREFILL = jax.jit(
    functools.partial(axk1.prefill_step, layers=longcat._layers),
    static_argnums=(6, 7))
_DECODE = jax.jit(functools.partial(axk1.decode_step, layers=longcat._layers),
                  static_argnums=(6, 7))


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
    """Prefill in chunks of 8 and then decoding through the latent cache,
    against the reference's full forward, every position compared: prompts
    inside a block, at a block's and a chunk's edge, and at and past the
    decode kernel's step and the chunk's group, both 128 positions (32
    blocks of 4), each decoded on across the next edge; the longest one
    decodes across two whole steps."""
    tokens = np.random.default_rng(prompt_len).integers(
        0, CFG.vocab_size, prompt_len + 5).astype(np.int32)
    want = _reference_logits(tokens)[prompt_len - 1:]
    got = _paged_forward(tokens, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


def test_the_tolerance_fails_the_fp8_control():
    """The reference with both operands of every product in fp8 (the
    benchmark's control) lies far outside ``TOL`` of the float32 one."""
    tokens = np.random.default_rng(9).integers(0, CFG.vocab_size, 24)
    exact = _reference_logits(tokens)
    control = _reference_logits(tokens, reference.fp8)
    assert np.abs(control - exact).max() > 1e4 * TOL


def test_two_latent_rows_a_double_layer_the_latent_scaled():
    """A double layer pages two rows a position, one for each attention
    sublayer, in pool layers 2l and 2l + 1; a row is the latent and the
    shared rotary key (24 + 8 values) and zeros behind them; the stored
    latent over its norm's scale has the root mean square of the LoRA scale
    (sqrt(32 / 24)), which both forms of attention read."""
    layers, blocks, lane_spec = CFG.state_spec
    assert (layers, blocks, lane_spec) == (4, {"latent": (1, None, 128)}, {})
    kv = KvBlockPool(CFG, 8, BLOCK, lanes=1)
    assert list(kv.pools) == ["latent"] and len(kv.pools["latent"]) == 4
    tokens = np.arange(1, 7, dtype=np.int32)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    _, pool = _prefill(tokens, 6, 8, table, kv.pools["latent"])
    params = _params()
    for i, layer in enumerate(pool):
        rows = np.asarray(layer)[1:3, 0].reshape(8, 128)[:6]
        assert np.abs(rows[:, :32]).min() > 0 and not rows[:, 32:].any()
        assert not np.asarray(layer)[3:].any()       # nothing past the rows
        attn = params["layers"][i // 2]["attn"][i % 2]
        latent = rows[:, :24] / np.asarray(attn["ln_kv"])
        rms = np.sqrt(np.mean(latent ** 2, axis=-1))
        np.testing.assert_allclose(rms, math.sqrt(32 / 24), rtol=1e-4)
    # the two sublayers of a double layer write different rows
    assert not np.allclose(np.asarray(pool[0]), np.asarray(pool[1]))


def test_the_published_constants():
    """LongCat-Flash's widths: LoRA scales (6144 / 1536)^0.5 = 2 and
    (6144 / 512)^0.5 = sqrt(12), plain rotary at 1e7, the softmax's scale
    192^-0.5, a row of 640 stored for 576, eight latent layers for four
    double layers; the reference derives the same from the configuration's
    own keys."""
    full = longcat.LongcatConfig()
    assert full.q_gain == 2.0 and full.kv_gain == pytest.approx(12 ** 0.5)
    assert full.softmax_scale == pytest.approx(192 ** -0.5)
    assert (full.row_width, full.value_width) == (640, 512)
    assert full.state_spec == (8, {"latent": (1, None, 640)}, {})
    assert (full.slots, full.top_k, full.routed_scale) == (768, 12, 6.0)
    np.testing.assert_allclose(
        axk1.yarn_inv_freq(full), 1e7 ** (-np.arange(32) / 32), rtol=1e-6)
    assert axk1.rope_gain(full) == 1.0
    published = dict(CONFIG, hidden_size=6144, q_lora_rank=1536,
                     kv_lora_rank=512, qk_rope_head_dim=64,
                     rope_theta=10000000)
    assert ref.lora_scales(published) == pytest.approx((2.0, 12 ** 0.5))
    np.testing.assert_allclose(ref.inv_freq(published),
                               axk1.yarn_inv_freq(full), rtol=1e-6)
    assert ref.lora_scales(dict(published, mla_scale_q_lora=False,
                                mla_scale_kv_lora=False)) == (1.0, 1.0)
    # FLOPs a token here: 4 x 638.8 M dense parameters, 12 x 16 / 768 of an
    # expert's 37.7 M a layer, the head's 100.7 M; twice each
    flops = longcat.lm_flops_per_token(full)
    assert flops == pytest.approx(
        2 * (4 * (638844928 + 0.25 * 37748736) + 6144 * 16384), rel=1e-9)


# -- the router and the share of an expert-parallel deployment -----------------

def _moe_layer(key=0):
    """A moe tree over 32 routed experts (all held) and 16 zero slots, with
    a seeded selection bias."""
    layer = experts.init_params(jax.random.PRNGKey(key), 16, 8, 48, 32, 1,
                                jnp.float32)
    bias = jax.random.normal(jax.random.PRNGKey(key + 1), (48,)) / 48
    return {"router": layer["router"], "bias": bias,
            "w_gate_up": layer["w_gate_up"], "w_down": layer["w_down"]}


def _route(h, layer, **kw):
    return experts.routed(h, layer, **dict(dict(
        held=tuple(range(32)), top_k=6, real=jnp.ones((h.shape[0],), bool),
        scale=6.0, score="softmax", bias=layer["bias"], normalize=False,
        n_zero=16), **kw))


def test_the_shares_add_up_to_the_uncut_layer():
    """32 routed experts in four shares of eight and 16 zero slots: the
    routed parts that the four shares compute, each told which eight it
    holds under the router over all 48 slots, with the zero slots' part
    added once (by the chip whose rows they are: share 0 here), plus the
    dense feed-forward, are the reference's uncut shortcut branch and dense
    feed-forward with every expert held.  float32: the sum's order differs,
    nothing else."""
    layer = _moe_layer()
    h = jax.random.normal(jax.random.PRNGKey(2), (12, 16), jnp.float32)
    total, rows, zeros = 0.0, 0, 0
    for share in range(4):
        held = tuple(range(8 * share, 8 * share + 8))
        mine = dict(layer, w_gate_up=layer["w_gate_up"][8 * share:][:8],
                    w_down=layer["w_down"][8 * share:][:8])
        part, counts = _route(h, mine, held=held,
                              n_zero=16 if share == 0 else 0)
        total = total + part
        rows += int(counts[1])
        zeros += int(counts[3]) if share == 0 else 0
    assert rows + zeros == 12 * 6           # every pair fell on one share
    want = ref.moe(h, layer, tuple(range(32)), 32, 6, 6.0)
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 1e-5
    # the zero slots' part is there: without it the sum is another
    assert zeros > 0
    routed_only = sum(_route(h, dict(
        layer, w_gate_up=layer["w_gate_up"][8 * s:][:8],
        w_down=layer["w_down"][8 * s:][:8]),
        held=tuple(range(8 * s, 8 * s + 8)), n_zero=0)[0] for s in range(4))
    assert np.abs(np.asarray(routed_only) - np.asarray(want)).max() > 1e-2
    dense = _params()["layers"][0]["mlp"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 32), jnp.float32)
    assert np.abs(np.asarray(axk1._dense_ffn(x, dense))
                  - np.asarray(ref.dense_ffn(x, dense))).max() < 1e-5


def test_zero_pairs_never_reach_the_grouped_product(monkeypatch):
    """On a one-share model (every routed expert held) a real row's pairs
    are expert rows or zero pairs, never both: the rows that the grouped
    products are told of are the expert rows alone, and padding rows route
    nowhere."""
    seen = []
    real_gmm = experts.grouped_matmul

    def spy(x, w, group_sizes):
        seen.append(int(jnp.sum(group_sizes)))
        return real_gmm(x, w, group_sizes)

    monkeypatch.setattr(experts, "grouped_matmul", spy)
    layer = _moe_layer(4)
    h = jax.random.normal(jax.random.PRNGKey(5), (20, 16), jnp.float32)
    real = jnp.arange(20) < 17
    _, counts = _route(h, layer, real=real)
    hit, rows, busiest, zeros = (int(c) for c in counts)
    assert rows + zeros == 17 * 6 and zeros > 0
    assert seen == [rows, rows]             # gate-and-up, then down
    picks, _ = experts.route(h, layer["router"], 6, "softmax",
                             layer["bias"], False)
    picks = np.asarray(picks)[:17]
    assert zeros == int((picks >= 32).sum())
    assert hit == len(set(picks[picks < 32].tolist()))
    assert busiest == max(np.bincount(picks[picks < 32]))


def test_the_bias_changes_picks_but_not_weights():
    """The picks are the largest of ``p + bias``, their weights ``p`` at
    those picks: a bias changes which slots are picked (here for most of the
    rows), never what a picked slot weighs; a bias that favours one slot
    overwhelmingly puts it in every row at its own small probability."""
    layer = _moe_layer(6)
    h = jax.random.normal(jax.random.PRNGKey(7), (64, 16), jnp.float32)
    p = np.asarray(jax.nn.softmax(h @ layer["router"], axis=-1))
    plain, _ = experts.route(h, layer["router"], 6, "softmax",
                             normalize=False)
    biased, weights_ = experts.route(h, layer["router"], 6, "softmax",
                                     layer["bias"], False)
    biased = np.asarray(biased)
    changed = [set(a) != set(b) for a, b in zip(np.asarray(plain), biased)]
    assert sum(changed) > 16
    np.testing.assert_allclose(np.asarray(weights_),
                               np.take_along_axis(p, biased, -1), rtol=1e-6)
    order = np.argsort(-(p + np.asarray(layer["bias"])), axis=-1)[:, :6]
    assert all(set(a) == set(b) for a, b in zip(order, biased))
    strong = layer["bias"].at[40].set(10.0)
    picks, weights_ = experts.route(h, layer["router"], 6, "softmax",
                                    strong, False)
    at = np.argmax(np.asarray(picks) == 40, axis=-1)
    assert (np.asarray(picks) == 40).any(axis=-1).all()
    np.testing.assert_allclose(
        np.asarray(weights_)[np.arange(64), at], p[:, 40], rtol=1e-6)


def test_unnormalised_weights_are_six_times_the_picked_probabilities():
    """``normalize=False``: a row's weights sum to 6 times the picked
    ``p``, under 6, where the normalised ones sum to 6 itself; the routed
    sum, zero part and all, scales the same way."""
    layer = _moe_layer(8)
    h = jax.random.normal(jax.random.PRNGKey(9), (16, 16), jnp.float32)
    p = np.asarray(jax.nn.softmax(h @ layer["router"], axis=-1))
    picks, weights_ = experts.route(h, layer["router"], 6, "softmax",
                                    layer["bias"], normalize=False)
    total = 6 * np.asarray(weights_).sum(-1)
    np.testing.assert_allclose(
        total, 6 * np.take_along_axis(p, np.asarray(picks), -1).sum(-1),
        rtol=1e-6)
    assert (total < 6).all()
    _, normed = experts.route(h, layer["router"], 6, "softmax",
                              layer["bias"])
    np.testing.assert_allclose(6 * np.asarray(normed).sum(-1), 6.0,
                               rtol=1e-6)
    # a row routed to zero slots alone gets its own input times their weight
    only_zero = dict(layer, bias=jnp.zeros((48,)).at[32:38].set(10.0))
    out, counts = _route(h, only_zero)
    zeros = np.take_along_axis(p, np.arange(32, 38)[None].repeat(16, 0), -1)
    np.testing.assert_allclose(np.asarray(out),
                               6 * zeros.sum(-1)[:, None] * np.asarray(h),
                               rtol=1e-5)
    assert [int(c) for c in counts] == [0, 0, 0, 16 * 6]


def test_counters_match_a_host_recount(monkeypatch):
    """A decode step over three lanes, one of them idle, and a chunk with
    padding rows, run eagerly with the router's picks recorded: the device's
    counts (held, hit, rows, busiest, zero pairs, all pairs of real rows,
    each summed over the two layers) are what the host counts from those
    picks and the rows that are real."""
    recorded = []
    real_route = experts.route

    def spy(*args, **kw):
        picks, weights_ = real_route(*args, **kw)
        recorded.append(np.asarray(picks))
        return picks, weights_

    monkeypatch.setattr(experts, "route", spy)
    params = _params()
    kv = KvBlockPool(CFG, 24, BLOCK, lanes=3)
    tables = jnp.arange(1, 25, dtype=jnp.int32).reshape(3, 8)
    live = np.array([True, False, True])

    def recount(real):
        held = np.asarray(HELD)
        want = np.zeros(6, np.int64)
        want[0] = CFG.n_layers * len(HELD)
        for picks in recorded:
            mine = picks[real]
            on = mine[np.isin(mine, held)]
            sizes = np.array([(on == e).sum() for e in held])
            want[1:] += [(sizes > 0).sum(), sizes.sum(), sizes.max(),
                         (mine >= 32).sum(), mine.size]
        return want.tolist()

    _, _, _, counts = longcat.longcat_decode_tick(
        params, jnp.asarray([3, 5, 7], jnp.int32), kv.pools["latent"],
        tables, jnp.asarray([4, 9, 2], jnp.int32), jnp.asarray(live),
        jnp.zeros((3,)), jnp.zeros((3,), jnp.int32),
        jax.random.split(jax.random.PRNGKey(0), 3), cfg=CFG, n=3,
        block_size=BLOCK)
    assert len(recorded) == CFG.n_layers
    assert [int(c) for c in counts] == recount(live)
    recorded.clear()
    chunk = jnp.asarray(np.arange(10, 26, dtype=np.int32)[None])
    _, _, _, counts = longcat.longcat_prefill_chunk(
        params, chunk, kv.pools["latent"], tables[0], jnp.int32(0),
        jnp.int32(11), jax.random.PRNGKey(1), jnp.float32(0.0),
        jnp.int32(0), cfg=CFG, block_size=BLOCK)
    assert [int(c) for c in counts] == recount(np.arange(16) < 11)
    assert [name for name, *_ in longcat.COUNTERS] == [
        "experts_held", "experts_hit", "expert_rows", "expert_rows_max",
        "zero_pairs", "pairs"]


# -- through the engine ---------------------------------------------------------

def _collect(q, timeout=300):
    out = []
    while True:
        tok = q.get(timeout=timeout)
        if tok is CLOSE:
            return out
        out.append(tok)


def test_engine_streams_follow_the_reference_and_count():
    """Two streams at once through ``LmEngine`` (chunked prefill, batched
    ticks, eight latent layers over two double layers): tokens as the
    reference ranks them, and every entry of ``tick_trace()`` that
    dispatched device work carries the family's fields and counts, the zero
    pairs in their Prometheus series."""
    reg = Registry()
    eng = LmEngine(_params(), CFG, max_slots=2, lane_counts=(2,),
                   block_size=BLOCK, prefill_chunk=8, min_bucket=4,
                   registry=reg)
    prompts = [tuple(range(1, 6)), tuple(range(7, 30))]
    try:
        queues = [eng.submit(list(p), 6)[0] for p in prompts]
        served = [_collect(q) for q in queues]
        for _ in range(200):            # the observer fills the counts in
            ticks = eng.tick_trace()
            if all("zero_pairs" in t for t in ticks):
                break
            time.sleep(0.02)
    finally:
        eng.close()
    for prompt, tokens in zip(prompts, served):
        assert len(tokens) == 6
        logits = _reference_logits(list(prompt) + tokens)
        for i, token in enumerate(tokens):
            row = logits[len(prompt) - 1 + i]
            assert row.max() - row[token] < 1e-4, (i, token)
    assert {t["kind"] for t in ticks} == {"decode", "prefill_chunk"}
    for t in ticks:
        rows = len(t["lanes"]) if t["kind"] == "decode" else t["tokens"]
        assert t["experts_held"] == CFG.n_layers * len(HELD)
        assert t["pairs"] == CFG.n_layers * CFG.top_k * rows
        assert t["expert_rows"] + t["zero_pairs"] <= t["pairs"]
        assert t["kv_positions_read"] >= t["kv_positions_live"] > 0
    for t in (t for t in ticks if t["kind"] == "decode"):
        assert t["kv_positions_live"] == 4 * (
            t["context_tokens"] + len(t["lanes"]))
    zeros = sum(t["zero_pairs"] for t in ticks)
    assert zeros > 0 and reg.get("ctpu_lm_zero_pairs_total") == zeros
    assert reg.get("ctpu_lm_expert_rows_total") == sum(
        t["expert_rows"] for t in ticks)


def test_tick_fields_count_every_paged_layer():
    """Two double layers page four latent layers: what a tick and a chunk
    may see and do read is counted over all four, as ``axk1``'s fields are
    over its five."""
    programs = CFG.family(CFG, BLOCK)
    assert isinstance(programs, longcat.LongcatPrograms)
    got = programs.tick_fields("decode", [135, 10])
    assert got == {"kv_positions_live": 4 * (136 + 11),
                   "kv_positions_read": 4 * (256 + 128),
                   "window_tokens": 145, "kv_steps": 4 * 3,
                   "kv_steps_full": 0}
    got = programs.tick_fields("prefill_chunk", [129], start=124, width=8)
    assert got == {"kv_positions_live": 4 * 129,
                   "kv_positions_read": 4 * 256, "kv_rows_rebuilt": 4 * 256}
    assert programs._annotations == ("lm.longcat_prefill_chunk",
                                    "lm.longcat_decode_tick")
    assert programs.prefill_jit.__name__ == "longcat_prefill_chunk"
    assert programs.flops_per_token == longcat.lm_flops_per_token(CFG)
    assert "verify" in programs.no_verify and not programs.recurrent
