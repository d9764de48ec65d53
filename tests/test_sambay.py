"""The SambaY family (``serve/models/sambay.py``) through its step functions
and through ``LmEngine``, against the plain float32 reference
(``benchmark/reference_sambay.py``), at a small size on the CPU with seeded
weights from the benchmark's own generator: 8 layers (Mamba, window, Mamba,
window, memory, full, GMU, cross), hidden 64, heads 4/2 of 16, ``d_state``
4, window 8, vocabulary 97, blocks of 4.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_sambay, weights_sambay
from client_tpu.ops import paged_decode
from client_tpu.serve.lm import KvBlockPool, LmEngine
from client_tpu.serve.lm.policy import chunk_plan, geometric_buckets, pad_prompt
from client_tpu.serve.metrics import Registry
from client_tpu.serve.models import sambay
from client_tpu.serve.models import transformer as tfm
from client_tpu.serve.models.language import (
    _LmRunner,
    lm_streaming_batched_model,
)

CLOSE = LmEngine.CLOSE
SEED = 5
BLOCK = 4
VOCAB = 97

# the configuration as the benchmark's files state one
CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": VOCAB,
    "num_hidden_layers": 8, "mb_per_layer": 2, "sliding_window": 8,
    "layer_norm_eps": 1e-5,
    "assumed": {"mamba": {"d_state": 4, "d_conv": 4, "expand": 2,
                          "dt_rank": 4}},
}


def _cfg(**kw):
    base = dict(vocab_size=VOCAB, d_model=64, n_layers=8, n_heads=4,
                n_kv_heads=2, head_dim=16, d_ff=128, max_seq=64, window=8,
                d_inner=128, d_state=4, d_conv=4, dt_rank=4, dtype="float32")
    return sambay.SambaYConfig(**dict(base, **kw))


CFG = _cfg()

# the family's two steps, compiled once a shape for the whole file
_STATIC = ("cfg", "block_size")
prefill_step = jax.jit(sambay.prefill_step, static_argnames=_STATIC)
decode_step = jax.jit(sambay.decode_step, static_argnames=_STATIC)


@pytest.fixture(scope="module")
def params():
    """The benchmark's seeded weights, in float32 so that the comparison is
    of the mathematics and not of bf16 rounding."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        weights_sambay.sambay_params(CONFIG, SEED))


def _reference_logits(tokens):
    """float32 logits [T, V] of the reference's full forward over ``tokens``
    [T]: no cache, no chunks."""
    ends = weights_sambay.sambay_ends(CONFIG, SEED)
    at = np.arange(len(tokens), dtype=np.int32)[None]
    hidden = reference_sambay.hidden_states(
        CONFIG, np.asarray(tokens, np.int32)[None], at, ends,
        lambda i: weights_sambay.sambay_layer(CONFIG, SEED, i))[0]
    return np.asarray(reference_sambay.logits_at(CONFIG, hidden, ends))[0]


def _reference_gaps(prompt, served):
    """By how much each served token's reference logit lies below the
    reference's best at its position."""
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])
    logits = _reference_logits(seq[:-1])[len(prompt) - 1:]
    return logits.max(-1) - logits[np.arange(len(served)), served]


def _collect(q, timeout=120):
    out = []
    while True:
        tok = q.get(timeout=timeout)
        if tok is CLOSE:
            return out
        out.append(tok)


def _served_logits(cfg, params, tokens, prompt_len, buckets, slot=1, slots=3):
    """What the engine's two shapes compute for one lane: the prompt in the
    chunks ``policy.chunk_plan`` gives (the last padded to its bucket), then
    a decode step a token, teacher-forced with ``tokens``.  The lane's state
    starts as garbage (the first chunk has to zero it), the other lanes ride
    every decode tick as idle ones, and the pool's blocks are out of order.
    Returns logits [len(tokens) - prompt_len + 1, V] at the served
    positions."""
    _, _, spec = cfg.state_spec
    state = {name: [jnp.full((slots,) + tuple(shape), 7, dtype)
                    for shape, dtype in layers]
             for name, layers in spec.items()}
    n_blocks = 40
    shape = (n_blocks + 1, cfg.kv_row[0], BLOCK, cfg.kv_row[1])
    pool_k, pool_v = [jnp.zeros(shape, cfg.jdtype)], [jnp.zeros(shape,
                                                                cfg.jdtype)]
    width = cfg.max_seq // BLOCK
    table = np.zeros((width,), np.int32)
    used = -(-len(tokens) // BLOCK)
    table[:used] = np.random.default_rng(1).permutation(
        np.arange(1, n_blocks + 1))[:used]
    out = []
    plan = chunk_plan(prompt_len, buckets)
    for idx, (start, wide) in enumerate(plan):
        chunk = pad_prompt(tokens[None, start:min(start + wide, prompt_len)],
                           wide)
        logits, (pool_k, pool_v, state) = prefill_step(
            params, jnp.asarray(chunk), pool_k, pool_v, state,
            jnp.asarray(table), jnp.int32(slot), jnp.int32(start),
            jnp.int32(prompt_len), jnp.bool_(idx == 0), cfg=cfg,
            block_size=BLOCK)
    out.append(np.asarray(logits))
    tables = np.zeros((slots, width), np.int32)
    tables[slot] = table
    live = np.arange(slots) == slot
    for pos in range(prompt_len, len(tokens)):
        toks = np.where(live, tokens[pos], 0).astype(np.int32)
        lens = np.where(live, pos, 0).astype(np.int32)
        logits, (pool_k, pool_v, state) = decode_step(
            params, jnp.asarray(toks), pool_k, pool_v, state,
            jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(live),
            cfg=cfg, block_size=BLOCK)
        out.append(np.asarray(logits)[slot])
    # the idle lanes' state is as it was
    for layers in state.values():
        for array in layers:
            assert (np.asarray(array[0], np.float32) == 7).all()
    return np.stack(out), plan


# float32 on both sides, HIGHEST against the CPU's default (also float32):
# what is left is the order of the sums (a chunked scan against a whole one,
# a ring against a dense mask), some 1e-5 of logits of size 4.  A lower
# precision anywhere is 100 times over it (the bf16-state test below).
TOLERANCE = 2e-4


@pytest.mark.parametrize("prompt_len,total,why", [
    (27, 40, "two chunks of 16, the second padded by five, ends mid-block"),
    (32, 44, "ends on a chunk boundary and on a block boundary"),
    (5, 20, "shorter than the window: the ring is never full"),
    (21, 60, "decodes far past the window, to the last cached position"),
])
def test_chunked_prefill_then_decode_matches_the_reference(
        params, prompt_len, total, why):
    tokens = np.random.default_rng(prompt_len).integers(
        0, VOCAB, total).astype(np.int32)
    got, plan = _served_logits(CFG, params, tokens, prompt_len,
                               geometric_buckets(4, 16))
    widths = [w for _, w in plan]
    if prompt_len == 27:
        assert widths == [16, 16] and prompt_len % BLOCK  # padded, mid-block
    want = _reference_logits(tokens)[prompt_len - 1:]
    assert got.shape == want.shape
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)


def test_chunks_of_unequal_widths_carry_the_state(params):
    """A chunk plan of the engine's own making with three different widths
    (a prefix-free plan never has one, so it is spelled out): 16, 8 and a
    4-wide bucket with one padded position."""
    tokens = np.random.default_rng(3).integers(0, VOCAB, 36).astype(np.int32)
    prompt_len, plan = 27, [(0, 16), (16, 8), (24, 4)]
    _, _, spec = CFG.state_spec
    state = {name: [jnp.zeros((1,) + tuple(s), d) for s, d in layers]
             for name, layers in spec.items()}
    shape = (17, CFG.kv_row[0], BLOCK, CFG.kv_row[1])
    pool_k, pool_v = [jnp.zeros(shape)], [jnp.zeros(shape)]
    table = np.arange(1, 17, dtype=np.int32)
    for idx, (start, wide) in enumerate(plan):
        chunk = pad_prompt(tokens[None, start:min(start + wide, prompt_len)],
                           wide)
        logits, (pool_k, pool_v, state) = prefill_step(
            params, jnp.asarray(chunk), pool_k, pool_v, state,
            jnp.asarray(table), jnp.int32(0), jnp.int32(start),
            jnp.int32(prompt_len), jnp.bool_(idx == 0), cfg=CFG,
            block_size=BLOCK)
    want = _reference_logits(tokens)[prompt_len - 1]
    np.testing.assert_allclose(np.asarray(logits), want, atol=TOLERANCE,
                               rtol=0)


def test_a_bf16_state_where_float32_is_stated_fails(params):
    """The comparison is tight enough that keeping the SSM state in bf16
    (the configuration states float32) is not inside it."""
    tokens = np.random.default_rng(27).integers(0, VOCAB, 40).astype(np.int32)
    got, _ = _served_logits(_cfg(state_dtype="bfloat16"), params, tokens, 27,
                            geometric_buckets(4, 16))
    want = _reference_logits(tokens)[26:]
    assert np.abs(got - want).max() > 10 * TOLERANCE


def test_layer_kinds_of_the_published_depth():
    cfg = sambay.SambaYConfig()
    kinds = cfg.kinds
    assert len(kinds) == 32
    assert [i for i, k in enumerate(kinds) if k == sambay.MAMBA] == list(
        range(0, 16, 2))
    assert [i for i, k in enumerate(kinds) if k == sambay.WINDOW] == list(
        range(1, 16, 2))
    assert kinds[16] == sambay.MEMORY and kinds[17] == sambay.FULL
    assert [i for i, k in enumerate(kinds) if k == sambay.GMU] == list(
        range(18, 32, 2))
    assert [i for i, k in enumerate(kinds) if k == sambay.CROSS] == list(
        range(19, 32, 2))
    # the reference and the yardstick derive the same from the file's keys
    from benchmark import work_sambay

    published = {"num_hidden_layers": 32, "mb_per_layer": 2}
    assert [reference_sambay.layer_kind(published, i)
            for i in range(32)] == list(kinds) == work_sambay.kinds(published)
    # what a lane owns: one paged layer, 8 rings of 512, 9 recurrent states
    paged, block, spec = cfg.state_spec
    assert paged == 1 and block == {"k": (10, None, 128),
                                    "v": (10, None, 128)}
    assert [len(spec[k]) for k in ("ring_k", "ring_v", "conv", "ssm")] == [
        8, 8, 9, 9]
    assert spec["ring_k"][0] == ((10, 512, 128), jnp.dtype("bfloat16"))
    assert spec["ssm"][0] == ((16, 5120), jnp.dtype("float32"))
    assert spec["conv"][0] == ((3, 5120), jnp.dtype("bfloat16"))


# -- through the engine ---------------------------------------------------------

def _engine(params, **kw):
    args = dict(max_slots=2, lane_counts=(2,), block_size=BLOCK,
                prefill_chunk=16, min_bucket=4)
    return LmEngine(params, CFG, **dict(args, **kw))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def test_engine_streams_match_the_reference(params):
    """Three streams over two lanes: a padded multi-chunk prompt, one that
    ends on a chunk and block boundary, and a short one that waits for a
    lane and reuses it.  Every served token is the reference's best (in
    float32 a gap means another token was served)."""
    eng = _engine(params)
    try:
        prompts = [_prompt(s, n) for s, n in ((1, 27), (2, 16), (3, 5))]
        queues = [eng.submit(p, 14)[0] for p in prompts]
        served = [_collect(q) for q in queues]
        ticks = eng.tick_trace()
    finally:
        eng.close()
    for prompt, tokens in zip(prompts, served):
        assert len(tokens) == 14
        assert _reference_gaps(prompt, tokens).max() < 1e-3
    lanes_used = {t["lanes"] for t in ticks if t["kind"] == "prefill_chunk"}
    assert len(lanes_used) == 2  # three prompts on two lanes: one was reused
    assert eng.kv.used_blocks == 0


def test_lanes_do_not_see_each_other_and_a_reused_lane_starts_from_zero(params):
    """A stream's tokens do not depend on what the other lane does while it
    runs (another stream is admitted in the middle of it, and prefills in
    chunks between its decode ticks), nor on what its lane held before."""
    pa, pb = _prompt(11, 23), _prompt(12, 37)
    eng = _engine(params)
    try:
        alone = _collect(eng.submit(pa, 30)[0])
    finally:
        eng.close()
    eng = _engine(params)
    try:
        qa, _ = eng.submit(pa, 30)
        first = qa.get(timeout=120)
        qb, _ = eng.submit(pb, 20)  # admitted while A decodes
        together = [first] + _collect(qa)
        other = _collect(qb)
        # both lanes have held a stream: whichever this takes is a reused one
        again = _collect(eng.submit(pa, 30)[0])
    finally:
        eng.close()
    assert together == alone and again == alone
    assert _reference_gaps(pb, other).max() < 1e-3


def test_speculative_decoding_is_refused_with_the_reason(params):
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(params, speculative={"k": 4, "drafter": "ngram"})
    eng = _engine(params)
    try:
        stats = eng.spec_stats()
        assert stats["enabled"] is False and "recurrent" in stats["reason"]
    finally:
        eng.close()


def test_prefix_adoption_and_fleet_export_are_switched_off(params):
    """Asked for, the prefix cache stays off with the reason in its stats:
    the same prompt twice prefills every token twice, exports nothing to a
    fleet tier, and serves the same tokens."""
    class Tier:
        exported = looked_up = 0

        def export_prefix(self, *args):
            Tier.exported += 1

        def prefix_lookup(self, *args, **kw):
            Tier.looked_up += 1

    prompt = _prompt(21, 24)  # six full blocks: all of them shareable
    eng = _engine(params, prefix_cache=True, fleet=Tier())
    try:
        one = _collect(eng.submit(prompt, 6)[0])
        two = _collect(eng.submit(prompt, 6)[0])
        stats, fleet = eng.prefix_stats(), eng.fleet_stats()
        chunks = [t for t in eng.tick_trace() if t["kind"] == "prefill_chunk"]
    finally:
        eng.close()
    assert one == two
    assert stats["enabled"] is False and "recurrent" in stats["reason"]
    assert fleet["prefix_export"].startswith("off") and not Tier.exported
    assert not Tier.looked_up and eng.prefix is None
    assert sum(t["tokens"] for t in chunks) == 2 * len(prompt)
    assert [t["start"] for t in chunks] == [0, 16, 0, 16]


def test_preemption_resumes_by_recompute_and_serves_the_same_tokens(params):
    """The host swap would bring back blocks without the state that goes
    with them, so it is off: a preempted lane's prompt and delivered tokens
    are replayed through chunked prefill, which rebuilds rings and recurrent
    state.  Both streams serve what they serve unpreempted."""
    pa, pb = _prompt(31, 3), _prompt(32, 2)
    eng = _engine(params, block_size=8, pool_tokens=80)
    try:
        want_a = _collect(eng.submit(pa, 50)[0])
        want_b = _collect(eng.submit(pb, 40)[0])
    finally:
        eng.close()
    eng = _engine(params, block_size=8, pool_tokens=80,
                  tenant_priority={"hi": 10.0})
    try:
        qa, _ = eng.submit(pa, 50, tenant="lo")   # 7 of 10 blocks
        first = qa.get(timeout=120)
        qb, _ = eng.submit(pb, 40, tenant="hi")   # needs 6: must preempt
        got_b = _collect(qb)
        got_a = [first] + _collect(qa)
        stats = eng.preempt_stats()
    finally:
        eng.close()
    assert got_a == want_a and got_b == want_b
    assert stats["preemptions"] >= 1
    assert stats["resumes"] == stats["preemptions"]
    assert stats["swapped_blocks"] == 0 and stats["swapped_streams"] == 0
    assert stats["swap"].startswith("off, recompute only")
    assert eng.kv.used_blocks == 0


def test_the_serial_runner_and_int8_weights_are_refused(params):
    runner = _LmRunner(CFG, params=params)
    with pytest.raises(Exception, match="continuous-batching engine"):
        next(runner.stream([1, 2, 3], 4))
    with pytest.raises(ValueError, match="decoder family"):
        _LmRunner(CFG, params=params, quantize=True)
    # and init_params gives the tree that the benchmark's generator gives
    own = jax.eval_shape(lambda: sambay.init_params(jax.random.PRNGKey(0),
                                                    _cfg(dtype="bfloat16")))
    made = jax.eval_shape(lambda: weights_sambay.sambay_params(CONFIG, SEED))
    assert jax.tree_util.tree_structure(own) == \
        jax.tree_util.tree_structure(made)
    assert jax.tree_util.tree_leaves(own) == jax.tree_util.tree_leaves(made)


def test_the_served_model_streams_through_the_batched_runner(params):
    """``lm_streaming_batched_model`` takes the family's runner as it takes
    the decoder's: the model's ``stream`` is the engine's."""
    model = lm_streaming_batched_model(
        name="lm", runner=_LmRunner(CFG, params=params), max_slots=2,
        lane_counts=(2,), block_size=BLOCK, prefill_chunk=16, min_bucket=4,
        prefix_cache=False)
    try:
        # 97 ids are not the byte-level tokenizer's 258: no id ends a stream
        assert model.runner.scheduler.eos_id is None
        prompt = _prompt(41, 19)
        tokens = list(model.runner.stream(prompt, 8))
        with pytest.raises(Exception, match="exceeds"):
            next(model.runner.stream(_prompt(42, CFG.max_seq), 4))
    finally:
        model.closer()
    assert len(tokens) == 8 and _reference_gaps(prompt, tokens).max() < 1e-3



@pytest.mark.parametrize("vocab,eos", [
    (258, 257),      # the byte-level tokenizer's: 256 bytes, BOS, EOS
    (32768, None),   # any other vocabulary: id 257 is a token like the rest
    (200064, None),
])
def test_what_ends_a_stream_follows_from_the_vocabulary(vocab, eos):
    """``lm_streaming_batched_model`` has no option for it: the runner
    derives ``eos_id`` from its configuration, whichever the family."""
    dense = tfm.TransformerConfig(vocab_size=vocab, d_model=32, n_layers=1,
                                  n_heads=2, n_kv_heads=1, d_ff=64)
    assert _LmRunner(dense, params={}).eos_id == eos
    assert _LmRunner(_cfg(vocab_size=vocab), params={}).eos_id == eos


# -- state, gauges and the ticks' counts ------------------------------------------

def test_pool_allocates_one_paged_layer_and_the_lanes_fixed_state():
    reg = Registry()
    pool = KvBlockPool(CFG, n_blocks=12, block_size=BLOCK, registry=reg,
                       lanes=3)
    assert len(pool.pools["k"]) == len(pool.pools["v"]) == 1
    assert pool.pools["k"][0].shape == (13, 1, BLOCK, 32)
    assert [a.shape for a in pool.lane_state["ring_k"]] == [(3, 1, 8, 32)] * 2
    assert [a.shape for a in pool.lane_state["ssm"]] == [(3, 4, 128)] * 3
    assert [a.shape for a in pool.lane_state["conv"]] == [(3, 3, 128)] * 3
    per_lane = 2 * 2 * 8 * 2 * 16 * 4 + 3 * (4 * 128 + 3 * 128) * 4
    assert pool.state_bytes == 3 * per_lane
    pool.release(pool.alloc(2))
    assert reg.get("ctpu_lm_state_bytes") == 3 * per_lane
    # a decoder of identical layers pages every layer and holds nothing else
    dense = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=3,
                                  n_heads=2, n_kv_heads=1, d_ff=64)
    pool = KvBlockPool(dense, n_blocks=4, block_size=BLOCK, registry=reg,
                       lanes=3)
    assert len(pool.pools["k"]) == 3 and pool.lane_state == {}
    assert pool.pools["v"][0].shape == (5, 1, BLOCK, 16)
    assert pool.state_bytes == 0


def test_ticks_count_context_and_window_tokens_for_both_families(params):
    reg = Registry()
    eng = _engine(params, registry=reg)
    try:
        prompt = _prompt(51, 21)
        assert len(_collect(eng.submit(prompt, 5)[0])) == 5
        ticks = eng.tick_trace()
    finally:
        eng.close()
    assert reg.get("ctpu_lm_attended_positions") == \
        ticks[-1]["attended_positions"]
    chunks = [t for t in ticks if t["kind"] == "prefill_chunk"]
    assert [(t["start"], t["width"], t["tokens"]) for t in chunks] == [
        (0, 16, 16), (16, 16, 5)]
    assert [t["context_tokens"] for t in chunks] == [16, 21]
    assert [t["window_tokens"] for t in chunks] == [8, 8]
    decodes = [t for t in ticks if t["kind"] == "decode"]
    # the first token comes from the prefill: four decode ticks, each over
    # the lane's length before its write
    assert [t["context_tokens"] for t in decodes][:4] == [21, 22, 23, 24]
    assert all(t["window_tokens"] == 8 * len(t["lanes"]) for t in decodes)
    # what attention read: a chunk gathers its lane's whole table (64
    # positions here); a decode tick reads each lane to its own length,
    # this tick's row with it, in whole steps of the kernel
    assert all(t["attended_tokens"] == t["attended_positions"] == 64
               for t in chunks)
    span = paged_decode.STEP_BLOCKS * BLOCK
    assert [t["attended_tokens"] for t in decodes][:4] == [
        -(-(n + 1) // span) * span for n in (21, 22, 23, 24)]
    assert all(t["attended_positions"] == t["attended_tokens"]
               for t in decodes)
    # the steps behind those reads, over the layers that read the full
    # cache: none on the kernel's straight-line path at these lengths
    calls = CFG.kinds.count("full") + CFG.kinds.count("cross")
    assert all(t["kv_steps"] * span == calls * t["attended_tokens"]
               and t["kv_steps_full"] == 0 for t in decodes)
    assert not any("kv_steps" in t for t in chunks)
    assert eng._programs.tick_fields("decode", [4 * span + 1, 2 * span]) == {
        "kv_steps": calls * (5 + 3), "kv_steps_full": calls * (3 + 1)}

    dense = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                  n_heads=2, n_kv_heads=1, d_ff=64,
                                  max_seq=64, dtype="float32")
    eng = LmEngine(tfm.init_params(jax.random.PRNGKey(0), dense), dense,
                   max_slots=2, lane_counts=(2,), block_size=BLOCK,
                   prefill_chunk=16, min_bucket=4)
    try:
        assert len(_collect(eng.submit(list(range(1, 11)), 3)[0])) == 3
        ticks = eng.tick_trace()
    finally:
        eng.close()
    chunk = next(t for t in ticks if t["kind"] == "prefill_chunk")
    assert (chunk["start"], chunk["width"], chunk["tokens"],
            chunk["context_tokens"]) == (0, 16, 10, 10)
    decodes = [t for t in ticks if t["kind"] == "decode"]
    assert [t["context_tokens"] for t in decodes][:2] == [10, 11]
    assert all("window_tokens" not in t for t in ticks)
    # the decoder reads one width for every lane of a call
    assert all(t["attended_tokens"]
               == t["attended_positions"] * len(t["lanes"])
               for t in [chunk] + decodes)
    # and its ticks read in place on the CPU: a step a lane a layer
    assert all((t["kv_steps"], t["kv_steps_full"])
               == (dense.n_layers * len(t["lanes"]), 0) for t in decodes)
    assert eng.prefix_stats().get("enabled") is not False


def test_flops_per_token_counts_every_matrix_and_the_wider_value():
    cfg = sambay.SambaYConfig()
    from benchmark import work_sambay

    published = {"hidden_size": 2560, "intermediate_size": 10240,
                 "num_attention_heads": 40, "num_key_value_heads": 20,
                 "head_dim": 64, "vocab_size": 200064, "num_hidden_layers": 32,
                 "mb_per_layer": 2, "sliding_window": 512,
                 "assumed": {"mamba": {"d_state": 16, "d_conv": 4, "expand": 2,
                                       "dt_rank": 160}}}
    assert sambay.lm_flops_per_token(cfg) == 2 * work_sambay.matmul_params(
        published)
    # 2,000 positions of context: 8 windows of 512 and 8 reads of the cache
    extra = sambay.lm_flops_per_token(cfg, 2000) - sambay.lm_flops_per_token(
        cfg)
    assert extra == 6 * 40 * 64 * (8 * 512 + 8 * 2000)
