"""The ``cohere2_moe`` family (``serve/models/cohere2moe.py``) and its expert
layer (``serve/models/experts.py``) at tiny sizes on the CPU, float32 where
logits are compared: the family's two steps and the engine against the plain
reference's full forward (``benchmark/reference_cohere2moe.py``), the share
of an expert-parallel deployment against the uncut layer, and the expert
layer against a per-token loop under forced imbalance."""

import functools
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_cohere2moe as reference
from benchmark import weights_cohere2moe as weights
from client_tpu.serve.lm import KvBlockPool, LmEngine
from client_tpu.serve.metrics import Registry
from client_tpu.serve.models import cohere2moe, experts

CLOSE = LmEngine.CLOSE
BLOCK = 4
SEED = 5
HELD = (1, 2, 5, 6)

# the published order of layers, three window layers to a full one, with a
# tiny window (8) so that lengths lie on both sides of it
CONFIG = {
    "hidden_size": 32, "intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 97,
    "num_experts": len(HELD), "num_shared_experts": 2,
    "num_experts_per_tok": 2, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "layer_norm_eps": 1e-5, "rope_theta": 50000, "sliding_window": 8,
    "logit_scale": 1,
    "deployment": {"router_experts": 8, "experts_held": list(HELD)},
}
CFG = cohere2moe.Cohere2MoeConfig(
    vocab_size=97, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
    head_dim=16, d_ff=32, n_experts=8, top_k=2, experts_held=HELD,
    n_shared=2, window=8, max_seq=64, dtype="float32")

# float32 through four layers, two formulations of one sum (a running
# softmax over groups of columns against a dense one; a sorted grouped
# product against every expert on every row): rounding alone, measured at
# 1.5e-6; a wrong mask, rotary pair or gate moves a logit by 1e-2 and more
TOL = 2e-5


@functools.lru_cache(maxsize=None)
def _params():
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        weights.cohere2moe_params(CONFIG, SEED))


def _reference_logits(tokens):
    """float32 logits [T, V] of the plain reference's full forward."""
    tokens = np.asarray(tokens, np.int32)[None]
    ends = weights.cohere2moe_ends(CONFIG, SEED)
    at = np.arange(tokens.shape[1], dtype=np.int32)[None]
    hidden = reference.hidden_states(
        CONFIG, tokens, at, ends,
        lambda i: weights.cohere2moe_layer(CONFIG, SEED, i))[0]
    return np.asarray(reference.logits_at(CONFIG, hidden, ends))[0]


_PREFILL = jax.jit(cohere2moe.prefill_step, static_argnums=(7, 8))
_DECODE = jax.jit(cohere2moe.decode_step, static_argnums=(7, 8))


def _paged_forward(tokens, prompt_len, chunk=8):
    """Logits at positions ``prompt_len - 1 ..`` of ``tokens`` through the
    paged cache: the prompt in chunks, then one decode step a token, over a
    table of shuffled blocks."""
    params = _params()
    kv = KvBlockPool(CFG, 32, BLOCK, lanes=1)
    pool_k, pool_v = kv.pools["k"], kv.pools["v"]
    table = jnp.asarray(
        np.random.default_rng(1).permutation(32)[:16] + 1, jnp.int32)
    for start in range(0, prompt_len, chunk):
        n = min(chunk, prompt_len - start)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = tokens[start:start + n]
        logits, pool_k, pool_v, _ = _PREFILL(
            params, jnp.asarray(padded), pool_k, pool_v, table,
            jnp.int32(start), jnp.int32(prompt_len), CFG, BLOCK)
    out = [np.asarray(logits)]
    for pos in range(prompt_len, len(tokens)):
        logits, pool_k, pool_v, _ = _DECODE(
            params, jnp.asarray(tokens[pos:pos + 1], jnp.int32), pool_k,
            pool_v, table[None], jnp.asarray([pos], jnp.int32),
            jnp.asarray([True]), CFG, BLOCK)
        out.append(np.asarray(logits)[0])
    return np.stack(out)


@pytest.mark.parametrize("prompt_len", [3, 8, 13, 21])
def test_chunks_then_decode_agree_with_the_reference(prompt_len):
    """Prefill in chunks of 8 and then decoding through the paged cache
    against the reference's full forward: prompts inside the window of 8,
    exactly at it (and at a block's edge), and past it by one chunk and by
    two, each decoded four tokens on, so that window layers start their
    reads behind position 0 and full layers do not."""
    tokens = np.random.default_rng(prompt_len).integers(
        0, CFG.vocab_size, prompt_len + 4).astype(np.int32)
    want = _reference_logits(tokens)[prompt_len - 1:]
    got = _paged_forward(tokens, prompt_len)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL


# -- the share of an expert-parallel deployment -------------------------------

def _layer(key, n_experts, d=16, ff=8, n_shared=2):
    return experts.init_params(key, d, ff, n_experts, n_experts, n_shared,
                               jnp.float32)


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight experts in four shares of two: the routed parts that the four
    shares compute, each told which two experts it holds under the router
    over all eight, plus the shared experts counted ONCE, are the uncut
    layer as the plain reference computes it with all eight held.  float32:
    the sum's order differs, nothing else (1e-5 of outputs of order 1)."""
    layer = _layer(jax.random.PRNGKey(0), 8)
    h = jax.random.normal(jax.random.PRNGKey(1), (12, 16), jnp.float32)
    real = jnp.ones((12,), bool)
    total = experts.shared(h, layer, 2)
    rows = 0
    for share in range(4):
        held = (2 * share, 2 * share + 1)
        mine = dict(layer, w_gate_up=layer["w_gate_up"][2 * share:][:2],
                    w_down=layer["w_down"][2 * share:][:2])
        part, counts = experts.routed(h, mine, held, 3, real)
        total = total + part
        rows += int(counts[1])
    assert rows == 12 * 3                      # every pair fell on one share
    want = reference.ffn(h, layer, tuple(range(8)), 3, 2)
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 1e-5


# -- the expert layer under forced imbalance -----------------------------------

def _loop(h, layer, held, top_k, real):
    """Token by token, pick by pick, in numpy: (routed sum, counts)."""
    h, w = np.asarray(h, np.float64), jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float64), layer)
    ff = w["w_down"].shape[1]
    out, rows = np.zeros_like(h), np.zeros(len(held), int)
    for t in range(h.shape[0]):
        if not real[t]:
            continue
        scores = 1.0 / (1.0 + np.exp(-(h[t] @ w["router"])))
        picks = np.argsort(-scores, kind="stable")[:top_k]
        for e in picks:
            if e not in held:
                continue
            j = held.index(e)
            gu = h[t] @ w["w_gate_up"][j]
            gate, up = gu[:ff], gu[ff:]
            act = gate / (1.0 + np.exp(-gate)) * up
            out[t] += scores[e] / scores[picks].sum() * (act @ w["w_down"][j])
            rows[j] += 1
    return out, [int((rows > 0).sum()), int(rows.sum()), int(rows.max())]


def _forced(layer, favoured, shunned=()):
    """The layer with a router that puts ``favoured`` first for every
    positive row and never picks ``shunned``."""
    router = 4.0 * np.asarray(layer["router"])
    router[:, list(favoured)] = 10.0 + np.arange(len(favoured))
    router[:, list(shunned)] = -1.0
    return dict(layer, router=jnp.asarray(router))


@pytest.mark.parametrize("case, favoured, shunned, want", [
    # every token's picks are expert 1 (held) and expert 0 (held elsewhere)
    ("all-on-one", (1, 0), (), [1, 10, 10]),
    # free routing among six experts; held expert 5 never gets a row
    ("one-unhit", (), (5, 7), None),
    # every pick falls on experts held elsewhere: nothing computed here
    ("all-absent", (0, 3), (), [0, 0, 0]),
])
def test_expert_layer_against_a_loop_under_imbalance(case, favoured, shunned,
                                                     want):
    """Nothing is dropped and the counts are exact: ten real rows of
    twelve (two are padding and route nowhere), top 2 of 8 experts, held
    (1, 2, 5, 6).  The rows of the sorted buffer that belong to no group
    are never written by the grouped product, so the all-absent case also
    shows that what they hold reaches no output."""
    layer = _forced(
        experts.init_params(jax.random.PRNGKey(2), 16, 8, 8, len(HELD), 2,
                            jnp.float32), favoured, shunned)
    # positive rows (a favoured column then wins for every row), each with
    # some of its dimensions off, so that free routing spreads
    keys = jax.random.split(jax.random.PRNGKey(3))
    h = jnp.abs(jax.random.normal(keys[0], (12, 16))) \
        * jax.random.bernoulli(keys[1], 0.4, (12, 16)) + 0.05
    real = np.arange(12) < 10
    got, counts = experts.routed(h, layer, HELD, 2, jnp.asarray(real))
    loop, loop_counts = _loop(h, layer, HELD, 2, real)
    assert np.asarray(counts).tolist() == loop_counts
    if want is not None:
        assert loop_counts == want
    else:
        assert loop_counts[0] in (2, 3) and loop_counts[1] > loop_counts[2]
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - loop).max() < 1e-5
    assert not np.asarray(got)[10:].any()


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


@functools.lru_cache(maxsize=None)
def _greedy(prompt, n):
    """The reference's own greedy continuation, a full forward a token."""
    tokens = list(prompt)
    for _ in range(n):
        tokens.append(int(_reference_logits(tokens)[-1].argmax()))
    return tokens[len(prompt):]


def _assert_follows_reference(prompt, served):
    """Every served token is the reference's best at its position, or lies
    within float32 rounding of it (a tie)."""
    logits = _reference_logits(list(prompt) + list(served))
    for i, token in enumerate(served):
        row = logits[len(prompt) - 1 + i]
        assert row.max() - row[token] < 1e-4, (i, token)


def test_engine_streams_follow_the_reference_and_count():
    """Two streams at once through ``LmEngine`` (chunked prefill, batched
    ticks, the paged pool), prompts inside and past the window: tokens as
    the reference ranks them, and every entry of ``tick_trace()`` that
    dispatched device work carries the family's fields, the device's counts
    consistent with the host's."""
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
    held = CFG.n_layers * len(HELD)
    for t in ticks:
        assert t["experts_held"] == held
        assert 0 <= t["experts_hit"] <= min(held, t["expert_rows"])
        assert t["expert_rows_max"] <= t["expert_rows"]
        assert t["kv_positions_read"] >= t["kv_positions_live"] > 0
        rows = len(t["lanes"]) if t["kind"] == "decode" else t["tokens"]
        assert t["expert_rows"] <= rows * CFG.top_k * CFG.n_layers
    decode = [t for t in ticks if t["kind"] == "decode"]
    # a lane of length n sees n + 1 positions on the full layer and at most
    # the window on each of the three window layers
    for t in decode:
        assert t["kv_positions_live"] <= t["context_tokens"] + len(
            t["lanes"]) + 3 * 8 * len(t["lanes"])
    assert reg.get("ctpu_lm_expert_rows_total") == sum(
        t["expert_rows"] for t in ticks)
    assert reg.get("ctpu_lm_experts_hit_total") == sum(
        t["experts_hit"] for t in ticks)
    assert reg.get("ctpu_lm_expert_rows_max") is not None


def test_tick_fields_count_what_the_kernel_reads():
    """``kv_positions_live`` and ``kv_positions_read`` by hand: block 4, a
    step of 16 blocks is 64 positions; window 8; three window layers and a
    full one."""
    programs = CFG.family(CFG, BLOCK)
    # a lane of length 72 attends 73 positions: the full layer reads two
    # steps (128); a window layer sees 8, from position 65: the first
    # step's 64 positions are skipped, the second is read (64).  One of
    # length 70 sees from 63, the last position of the first step: both
    got = programs.tick_fields("decode", [72])
    assert got == {"kv_positions_live": 73 + 3 * 8,
                   "kv_positions_read": 128 + 3 * 64,
                   "kv_steps": 2 + 3 * 1, "kv_steps_full": 0}
    assert programs.tick_fields("decode", [70])["kv_positions_read"] == 512
    # of a lane that attends 200 positions (three whole steps and a part)
    # the full layer walks four steps, two of them on the kernel's
    # straight-line path (the step ahead has to be whole too); a window
    # layer walks from position 192, the partial step alone
    got = programs.tick_fields("decode", [199, 72])
    assert (got["kv_steps"], got["kv_steps_full"]) == (4 + 3 + 5, 2)
    assert got["kv_steps"] * 64 == got["kv_positions_read"]
    # a chunk of 8 rows from 72, 5 of them real: the full layer may see 77
    # positions and reads groups 0 and 1; a window layer sees from 65, in
    # group 1
    got = programs.tick_fields("prefill_chunk", [77], start=72, width=8)
    assert got == {"kv_positions_live": 77 + 3 * (77 - 65),
                   "kv_positions_read": 128 + 3 * 64}
    assert programs._tick_reads(np.array([70]), 16) == [128]


def test_prefix_adoption_shares_blocks_for_this_family():
    """A lane IS its blocks here: a second prompt with the same first three
    blocks adopts them, prefills its tail alone and streams what the
    reference ranks first."""
    reg = Registry()
    eng = _engine(registry=reg)
    shared = tuple(range(1, 13))                       # three blocks of 4
    prompts = [shared + (40 + i,) for i in range(2)]
    try:
        served = [_collect(eng.submit(list(p), 4)[0]) for p in prompts]
        stats = eng.prefix_stats()
    finally:
        eng.close()
    for prompt, tokens in zip(prompts, served):
        _assert_follows_reference(prompt, tokens)
    assert stats["hits"] == 3
    assert reg.get("ctpu_lm_prefill_tokens_saved_total") == 12
    assert eng.kv.used_blocks == 0, eng.kv.ref_counts()


def test_preemption_swaps_this_familys_blocks_out_and_back():
    """``tests/test_lm.py``'s scenario for this family: the pool cannot
    hold the high-priority stream beside the low one, so the low lane's
    blocks go to the host and come back; both streams are the reference's
    greedy continuations."""
    eng = _engine(pool_tokens=64, tenant_priority={"hi": 10.0})
    pa, pb = (1, 2, 3), (9, 4)
    try:
        qa, _ = eng.submit(list(pa), 49, tenant="lo")   # 13 of 16 blocks
        first = qa.get(timeout=300)
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
    _assert_follows_reference(pa, got["a"])
    _assert_follows_reference(pb, got["b"])
    assert len(got["a"]) == 49 and len(got["b"]) == 18
    assert stats["preemptions"] >= 1 and "swap" not in stats
    assert stats["resumes"] == stats["preemptions"]
    assert eng.kv.used_blocks == 0, eng.kv.ref_counts()
