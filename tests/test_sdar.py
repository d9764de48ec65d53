"""The ``sdar_moe`` family (``serve/models/sdar.py``: generation by diffusion
over blocks) at tiny sizes on the CPU, float32 where logits are compared:
prefill and block passes through the paged cache and through ``LmEngine``
against the plain reference's logits at EVERY pass of every block
(``benchmark/reference_sdar.py``: the clean sequence followed by the noisy
copies of its blocks), the tick's choice against the published rule, the
block's rows through ``ops/paged_decode`` against ``paged_attention``, the
softmax router, and the engine's schedule: lanes in different phases in one
tick, cancels and budgets that end inside a block, preemption, the prefix
cache."""

import functools
import json
import pathlib
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_sdar as reference
from benchmark import weights_sdar as weights
from client_tpu.ops.paged_decode import paged_decode_attention
from client_tpu.serve.lm import KvBlockPool, LmEngine
from client_tpu.serve.metrics import Registry
from client_tpu.serve.models import cohere2moe, experts, sdar
from client_tpu.serve.models import transformer as tfm

ROOT = pathlib.Path(__file__).resolve().parent.parent
CLOSE = LmEngine.CLOSE
BLOCK = 4       # a pool block: the kernel's step is 16 of them, 64 positions
B = 4           # a diffusion block
SEED = 11
MASK = 96

CONFIG = {
    "hidden_size": 32, "moe_intermediate_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 97,
    "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 3,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "assumed": {"block_length": B, "denoising_steps": 4,
                "mask_token_id": MASK},
}
CFG = sdar.SdarConfig(
    vocab_size=97, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
    head_dim=16, d_ff=16, n_experts=8, top_k=2, experts_held=tuple(range(8)),
    block_length=B, denoising_steps=4, mask_id=MASK, max_seq=96,
    dtype="float32")

# float32 through three layers, two formulations of one sum (a running
# softmax over the kernel's steps or over groups of columns against a dense
# one; a sorted grouped product against every expert on every row): rounding
# alone; a wrong mask, rotary pair, norm or gate moves a logit by 1e-2
TOL = 2e-5


@functools.lru_cache(maxsize=None)
def _params():
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), weights.sdar_params(CONFIG, SEED))


def _reference_logits(prompt, tokens, fixed_at):
    """(clean logits [T,V], noisy logits [B,N,V], first): the reference on
    ``prompt`` followed by the generated ``tokens`` (whole blocks from the
    prompt's last block edge), every block's states rebuilt from
    ``fixed_at``."""
    first = len(prompt) // B * B
    seq = np.asarray(list(prompt) + list(tokens), np.int32)
    assert len(seq) % B == 0
    known = len(prompt) - first
    region = seq[first:]
    fixed = np.concatenate([np.full(known, -1), np.asarray(fixed_at)])
    ids, _ = reference.noisy_copies(CONFIG, region, fixed, first)
    ends = weights.sdar_ends(CONFIG, SEED)
    clean, noisy = reference.hidden_states(
        CONFIG, seq[None], ids[None], [first], ends,
        lambda i: weights.sdar_layer(CONFIG, SEED, i))[0]
    return (np.asarray(reference.logits_at(CONFIG, clean[0], ends)),
            np.asarray(reference.logits_at(CONFIG, noisy[0], ends)), first)


_PREFILL = jax.jit(sdar.prefill_step, static_argnums=(7, 8))
_STEP = jax.jit(sdar.block_step, static_argnums=(7, 8))


def _paged_generation(prompt, n_blocks, chunk=8):
    """``n_blocks`` blocks generated after ``prompt`` through the paged
    cache over a table of shuffled blocks, by the family's own functions:
    the prompt in chunks, then the static schedule's passes, each block's
    commit included.  Returns (tokens, fixed_at, passes): ``passes`` holds
    (block's first position, positions unmasked or None for the commit,
    logits [B,V]) of every pass."""
    params = _params()
    kv = KvBlockPool(CFG, 48, BLOCK, lanes=1)
    pool_k, pool_v = kv.pools["k"], kv.pools["v"]
    table = jnp.asarray(
        np.random.default_rng(1).permutation(48)[:24] + 1, jnp.int32)
    plen = len(prompt)
    for start in range(0, plen, chunk):
        n = min(chunk, plen - start)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = prompt[start:start + n]
        pool_k, pool_v, _ = _PREFILL(
            params, jnp.asarray(padded), pool_k, pool_v, table,
            jnp.int32(start), jnp.int32(plen), CFG, BLOCK)
    state = sdar.first_block(jnp.asarray(padded), jnp.int32(start),
                             jnp.int32(plen), CFG)[None]
    length, live = plen // B * B, jnp.asarray([True])
    tokens, fixed_at, passes = [], [], []
    for _ in range(n_blocks):
        known = int((np.asarray(state)[0, sdar.MASKED] == 0).sum())
        while True:
            masked = np.asarray(state)[0, sdar.MASKED] > 0
            ids = jnp.where(state[:, sdar.MASKED] > 0, MASK,
                            state[:, sdar.TOKENS])
            logits, pool_k, pool_v, _ = _STEP(
                params, ids, pool_k, pool_v, table[None],
                jnp.asarray([length], jnp.int32), live, CFG, BLOCK)
            passes.append((length, int(B - masked.sum()) if masked.any()
                           else None, np.asarray(logits)[0]))
            if not masked.any():
                break
            chosen, conf = sdar.choose(
                logits, jnp.zeros((1, B, 2), jnp.uint32), jnp.zeros((1,)),
                jnp.zeros((1,), jnp.int32))
            state = sdar.denoise(state, chosen, conf, live, CFG)
        done = np.asarray(state)[0]
        tokens += done[sdar.TOKENS, known:].tolist()
        fixed_at += done[sdar.FIXED_AT, known:].tolist()
        state = sdar.denoise(state, chosen, conf, live, CFG)  # the commit
        assert (np.asarray(state)[0, sdar.MASKED] == 1).all()
        length += B
    return tokens, fixed_at, passes


@pytest.mark.parametrize("prompt_len", [8, 13, 31, 58])
def test_every_pass_agrees_with_the_reference(prompt_len):
    """Prefill in chunks of 8 and three blocks' passes through the paged
    cache against the reference's logits AT EVERY PASS: each denoising pass
    against the block's noisy copy at that pass, each commit against the
    clean sequence.  Prompts that end on a block's edge (8: no known token)
    and 1, 3 and 2 positions into one; 13 and 31 cross pool blocks and
    chunks mid-block; 58 generates across the kernel's step edge at 64."""
    prompt = np.random.default_rng(prompt_len).integers(
        0, MASK, prompt_len).astype(np.int32)
    tokens, fixed_at, passes = _paged_generation(prompt, 3)
    known = prompt_len % B
    assert len(tokens) == 3 * B - known
    assert len(passes) == 3 * (B + 1) - known
    clean, noisy, first = _reference_logits(prompt, tokens, fixed_at)
    worst = 0.0
    for start, unmasked, logits in passes:
        rows = slice(start - first, start - first + B)
        want = (clean[start:start + B] if unmasked is None
                else noisy[unmasked, rows])
        worst = max(worst, float(np.abs(logits - want).max()))
    assert worst < TOL
    # and the rule on the reference's own logits fixes what the program did
    _assert_follows_reference(prompt, tokens, fixed_at)


def _assert_follows_reference(prompt, tokens, fixed_at, margin=1e-4):
    """Every served token is the reference's first at the pass that fixed
    it, and every pass fixed the position of the reference's highest
    confidence, each within float32 rounding (a tie).  The stream's last
    block counts only if it was delivered whole."""
    whole = (len(prompt) + len(tokens)) // B * B - len(prompt)
    tokens, fixed_at = list(tokens[:whole]), list(fixed_at[:whole])
    ends = weights.sdar_ends(CONFIG, SEED)
    first = len(prompt) // B * B
    seq = np.asarray(list(prompt) + tokens, np.int32)
    fixed = np.concatenate([np.full(len(prompt) - first, -1), fixed_at])
    ids, _ = reference.noisy_copies(CONFIG, seq[first:], fixed, first)
    _, noisy = reference.hidden_states(
        CONFIG, seq[None], ids[None], [first], ends,
        lambda i: weights.sdar_layer(CONFIG, SEED, i))[0]
    token_gaps, place_gaps = reference.pass_gaps(
        CONFIG, noisy[0], seq[first:], fixed, ends)
    assert len(token_gaps) == len(tokens) == len(place_gaps)
    assert token_gaps.max() < margin and place_gaps.max() < margin


# -- (b) the tick's choice ----------------------------------------------------

def _state(tokens, masked, fixed_at):
    return jnp.asarray([[tokens, masked, fixed_at]], jnp.int32)


def test_denoise_fixes_the_most_confident_masked_position_ties_lowest():
    live = jnp.asarray([True])
    chosen = jnp.asarray([[7, 8, 9, 10]], jnp.int32)
    state = _state([5, 0, 0, 0], [0, 1, 1, 1], [-1, -1, -1, -1])
    # positions 1 and 3 tie at the top: the lower one; position 0 is known
    # and never chosen though its confidence is the largest
    conf = jnp.asarray([[0.99, 0.7, 0.2, 0.7]])
    out = np.asarray(sdar.denoise(state, chosen, conf, live, CFG))[0]
    assert out.tolist() == [[5, 8, 0, 0], [0, 0, 1, 1], [-1, 1, -1, -1]]
    # the next pass: 3 beats 2; then the last mask goes; fixed_at counts
    # the positions that were unmasked when each was fixed
    out = sdar.denoise(jnp.asarray(out)[None], chosen, conf, live, CFG)
    out = sdar.denoise(out, chosen, conf, live, CFG)
    assert np.asarray(out)[0].tolist() == [
        [5, 8, 9, 10], [0, 0, 0, 0], [-1, 1, 3, 2]]
    # no mask left: the commit starts the next block, all masked
    out = np.asarray(sdar.denoise(out, chosen, conf, live, CFG))[0]
    assert out.tolist() == [[0] * 4, [1] * 4, [-1] * 4]
    # a lane that is not in the tick keeps its state
    kept = sdar.denoise(state, chosen, conf, jnp.asarray([False]), CFG)
    assert (np.asarray(kept) == np.asarray(state)).all()


def test_two_positions_a_pass_under_two_denoising_steps():
    cfg = sdar.SdarConfig(**{**CFG.__dict__, "denoising_steps": 2})
    programs = cfg.family(cfg, BLOCK)
    state = _state([0] * 4, [1] * 4, [-1] * 4)
    chosen = jnp.asarray([[7, 8, 9, 10]], jnp.int32)
    conf = jnp.asarray([[0.1, 0.5, 0.5, 0.3]])
    out = np.asarray(sdar.denoise(state, chosen, conf, jnp.asarray([True]),
                                  cfg))[0]
    assert out.tolist() == [[0, 8, 9, 0], [1, 0, 0, 1], [-1, 0, 0, -1]]
    assert programs.advance(8, 4) == ("denoise", 8, 2, False)
    assert programs.advance(8, 2) == ("denoise", 8, 0, True)
    assert programs.advance(8, 1) == ("denoise", 8, 0, True)  # a known head
    assert programs.advance(8, 0) == ("commit", 12, 4, False)


def test_the_tick_applies_the_rule_to_its_own_logits():
    """``sdar_block_tick`` over three lanes in three phases (a fresh block,
    one with two masks left, one to commit) and a lane that is not in the
    tick: the new states are the rule applied, in numpy, to the logits
    ``block_step`` gives for the same inputs."""
    params = _params()
    n = 4
    kv = KvBlockPool(CFG, 32, BLOCK, lanes=n)
    tables = jnp.asarray(
        np.random.default_rng(3).permutation(32)[:n * 6].reshape(n, 6) + 1,
        jnp.int32)
    lens = jnp.asarray([0, 8, 4, 0], jnp.int32)
    live = jnp.asarray([True, True, True, False])
    state = jnp.asarray([
        [[0, 0, 0, 0], [1, 1, 1, 1], [-1, -1, -1, -1]],
        [[11, 0, 13, 0], [0, 1, 0, 1], [-1, -1, 0, -1]],
        [[21, 22, 23, 24], [0, 0, 0, 0], [1, 0, 3, 2]],
        [[31, 0, 0, 0], [0, 1, 1, 1], [-1, -1, -1, -1]]], jnp.int32)
    programs = CFG.family(CFG, BLOCK)
    keys = jnp.zeros((n, 2), jnp.uint32)
    ids = jnp.where(state[:, 1] > 0, MASK, state[:, 0])
    logits = np.asarray(sdar.block_step(
        params, ids, kv.pools["k"], kv.pools["v"], tables, lens, live, CFG,
        BLOCK)[0])
    new, _, counts = programs.tick(
        programs.make_tick(n), params, kv, state, tables, lens, live,
        jnp.zeros((n,)), jnp.zeros((n,), jnp.int32), keys)
    new = np.asarray(new)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    for lane in (0, 1):
        before = np.asarray(state)[lane]
        masked = before[1] > 0
        conf = np.where(masked, probs[lane].max(-1), -1.0)
        (at,) = np.flatnonzero(new[lane, 1] != before[1])   # one was fixed
        # (four [MASK] rows of one lane differ by their rotation alone: the
        # confidences are equal to rounding, which the two softmaxes round
        # apart; ``test_denoise_..._ties_lowest`` holds the exact rule)
        assert masked[at] and conf[at] >= conf.max() - 1e-6
        want = before.copy()
        want[:, at] = [logits[lane, at].argmax(), 0, B - masked.sum()]
        assert new[lane].tolist() == want.tolist()
    assert new[2].tolist() == [[0] * 4, [1] * 4, [-1] * 4]
    assert new[3].tolist() == np.asarray(state)[3].tolist()
    # three lanes of four rows routed twice in each of three layers
    assert np.asarray(counts).tolist()[0] == 3 * 8
    assert np.asarray(counts).tolist()[2] == 3 * B * 2 * 3


# -- (c) the block's rows through the decode kernel ---------------------------

@pytest.mark.parametrize("rep", [2, 8])
def test_block_rows_through_the_kernel_equal_paged_attention(rep):
    """B positions x ``rep`` query heads a KV head go to ``ops/paged_decode``
    as so many rows under one lane length (32 at the published 8 heads a KV
    head) and come back as ``transformer.paged_attention`` gives them with
    every row's sight at the block's last position; lanes across the
    kernel's step edge (64) and one that is not in the tick."""
    cfg = sdar.SdarConfig(**{**CFG.__dict__, "n_heads": 2 * rep,
                             "max_seq": 128})
    n, width = 4, 32
    rng = np.random.default_rng(rep)
    pool_k, pool_v = (jnp.asarray(rng.standard_normal(
        (n * width + 1, 2, BLOCK, 16)), jnp.float32) for _ in range(2))
    tables = jnp.asarray(
        rng.permutation(n * width).reshape(n, width) + 1, jnp.int32)
    lens = jnp.asarray([0, 60, 64, 100], jnp.int32)
    live = jnp.asarray([True, True, True, True])
    q = jnp.asarray(rng.standard_normal((n, B, 2 * rep, 16)), jnp.float32)
    view = sdar._BlockView(cfg, tables, lens, live, BLOCK)
    got = np.asarray(view.attend(q, pool_k, pool_v))
    sight = jnp.broadcast_to((lens + B - 1)[:, None], (n, B))
    want = np.asarray(tfm.paged_attention(
        q, pool_k, pool_v, tables, sight, cfg, BLOCK)).reshape(n, B, -1)
    assert np.abs(got - want).max() < 1e-5
    # the kernel itself at rows = B x rep a KV head, one length a lane
    qg = q.reshape(n, B, 2, rep, 16).transpose(0, 2, 1, 3, 4).reshape(
        n, 2, B * rep, 16) * 0.25
    raw = paged_decode_attention(qg, pool_k, pool_v, tables, lens + B)
    assert raw.shape == (n, 2, B * rep, 16)
    back = np.asarray(raw).reshape(n, 2, B, rep, 16).transpose(
        0, 2, 1, 3, 4).reshape(n, B, -1)
    assert np.abs(back - want).max() < 1e-5
    idle = sdar._BlockView(cfg, tables, lens,
                           jnp.asarray([True, False, True, True]), BLOCK)
    assert not np.asarray(idle.attend(q, pool_k, pool_v))[1].any()


# -- (d) the router's score ---------------------------------------------------

def test_softmax_route_is_the_references_and_sigmoid_is_the_parents():
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((24, 32)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((32, 8)) * 0.5, jnp.float32)
    picks, w = experts.route(h, router, 3, score="softmax")
    want = np.asarray(reference.gates(h, router, 3))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(picks), np.asarray(w), axis=-1)
    assert np.abs(got - want).max() < 1e-6
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    probs = np.asarray(jax.nn.softmax(h @ router, axis=-1))
    assert (np.sort(np.asarray(picks), -1)
            == np.sort(np.argsort(-probs, -1)[:, :3], -1)).all()
    # the sigmoid path, bit for bit what it was before the score was named
    scores = jax.nn.sigmoid(jnp.matmul(
        h, router, preferred_element_type=jnp.float32))
    top, old_picks = jax.lax.top_k(scores, 3)
    for new in (experts.route(h, router, 3),
                experts.route(h, router, 3, score="sigmoid")):
        assert (np.asarray(new[0]) == np.asarray(old_picks)).all()
        assert (np.asarray(new[1]) == np.asarray(
            top / jnp.sum(top, axis=-1, keepdims=True))).all()
    with pytest.raises(KeyError):
        experts.route(h, router, 3, score="tanh")


# -- (a), (e) through the engine ----------------------------------------------

def _engine(**kwargs):
    args = dict(max_slots=4, lane_counts=(4,), block_size=BLOCK,
                prefill_chunk=8, min_bucket=4, pool_tokens=512)
    args.update(kwargs)
    return LmEngine(_params(), CFG, **args)


def _collect(q, timeout=300):
    out = []
    while True:
        token = q.get(timeout=timeout)
        if token is CLOSE:
            return out
        out.append(token)


def _spy(eng, seen):
    """Record, for every tick the engine dispatches, the logits that
    ``block_step`` gives for the tick's own inputs (the pools as the tick
    finds them), with the lanes' prompts."""
    real = eng._programs.tick

    def tick(fn, params, kv, state, tables, lens, live, temps, topks, keys):
        ids = jnp.where(state[:len(lens), 1] > 0, MASK, state[:len(lens), 0])
        logits = _STEP(params, ids, kv.pools["k"], kv.pools["v"], tables,
                       lens, live, CFG, BLOCK)[0]
        seen.append((np.asarray(lens), np.asarray(live),
                     np.asarray(state)[:len(lens)], np.asarray(tables),
                     np.asarray(logits),
                     [lane.prompt for lane in eng._lanes]))
        return real(fn, params, kv, state, tables, lens, live, temps, topks,
                    keys)

    eng._programs.tick = tick


def test_engine_passes_agree_with_the_reference_at_every_pass():
    """Four streams at once through ``LmEngine`` (chunked prefill, batched
    block ticks dispatched ahead, the paged pool): prompts 0, 1, 2 and 3
    positions into a block, budgets that end on a block's edge.  Every
    lane's logits at every pass of every tick agree with the reference's
    for that stream's block in the state the pass found it, lanes in
    different phases share ticks, and the streams are what the rule gives
    on the reference's logits."""
    reg = Registry()
    eng = _engine(registry=reg)
    rng = np.random.default_rng(2)
    sizes = [(8, 8), (13, 11), (30, 6), (59, 9)]
    prompts = [rng.integers(0, MASK, p).tolist() for p, _ in sizes]
    seen = []
    _spy(eng, seen)
    try:
        queues = [eng.submit(p, m)[0] for p, (_, m) in zip(prompts, sizes)]
        served = [_collect(q) for q in queues]
        for _ in range(200):            # the observer fills the counts in
            ticks = eng.tick_trace()
            if all("expert_rows" in t for t in ticks):
                break
            time.sleep(0.02)
        record = {tuple(r["prompt"]): r for r in eng.pass_trace()}
    finally:
        eng.close()
    assert [len(s) for s in served] == [m for _, m in sizes]
    refs = {}
    for prompt, tokens in zip(prompts, served):
        r = record[tuple(prompt)]
        assert r["tokens"] == tokens and len(r["fixed_at"]) == len(tokens)
        refs[tuple(prompt)] = _reference_logits(prompt, tokens, r["fixed_at"])
        _assert_follows_reference(prompt, tokens, r["fixed_at"])
    worst, compared = 0.0, 0
    for lens, live, state, tables, logits, lane_prompts in seen:
        for lane in np.flatnonzero(live):
            clean, noisy, first = refs[tuple(lane_prompts[lane][0].tolist())]
            masked = state[lane, 1] > 0
            rows = slice(lens[lane] - first, lens[lane] - first + B)
            want = (noisy[B - masked.sum(), rows] if masked.any()
                    else clean[lens[lane]:lens[lane] + B])
            worst = max(worst, float(np.abs(logits[lane] - want).max()))
            compared += 1
            # dispatch-ahead never writes past the lane's reservation: the
            # block's last row lies in a block of the lane's own
            assert tables[lane, (lens[lane] + B - 1) // BLOCK] != 0
    # a pass a token, and a commit for every block but a stream's last
    assert worst < TOL and compared == 34 + 6, (worst, compared)
    decode = [t for t in ticks if t["kind"] == "decode"]
    assert {t["kind"] for t in ticks} == {"decode", "prefill_chunk"}
    assert any(t["denoise_lanes"] and t["commit_lanes"] for t in decode)
    for t in decode:
        lanes = len(t["lanes"])
        assert t["block_rows"] == B * lanes
        assert t["denoise_lanes"] + t["commit_lanes"] == lanes
        assert t["denoise_lanes"] <= t["masked_rows"] <= B * t["denoise_lanes"]
        assert t["expert_rows"] == t["block_rows"] * CFG.top_k * CFG.n_layers
        assert t["experts_held"] == CFG.n_layers * 8
        assert t["kv_positions_live"] == CFG.n_layers * (
            t["context_tokens"] + t["block_rows"])
        assert t["kv_positions_read"] >= t["kv_positions_live"]
        assert t["window_tokens"] == t["context_tokens"]
    total = sum(m for _, m in sizes)
    assert sum(t["tokens_out"] for t in decode) == total
    assert reg.get("ctpu_lm_tokens_total") == total
    passes = sum(len(t["lanes"]) for t in decode)
    assert reg.get("ctpu_lm_block_passes_total", {"kind": "denoise"}) \
        + reg.get("ctpu_lm_block_passes_total", {"kind": "commit"}) == passes
    # a stream of k whole blocks after r known tokens: 4 - r + 4 (k - 1)
    # denoising passes and k - 1 commits (the last block is not committed)
    want = sum((p + m) // B - p // B - 1 for p, m in sizes)
    assert reg.get("ctpu_lm_block_passes_total", {"kind": "commit"}) == want
    assert reg.get("ctpu_lm_block_passes_total", {"kind": "denoise"}) == total
    assert eng.kv.used_blocks == 0, eng.kv.ref_counts()


@pytest.mark.parametrize("max_tokens", [5, 6, 7])
def test_a_budget_that_ends_inside_a_block(max_tokens):
    """The stream gets exactly ``max_tokens`` tokens; what the last block
    computes past them is dropped, and no commit follows it."""
    eng = _engine()
    prompt = list(range(1, 7))                     # 2 positions into a block
    try:
        tokens = _collect(eng.submit(prompt, max_tokens)[0])
        record = eng.pass_trace()[-1]
        ticks = [t for t in eng.tick_trace() if t["kind"] == "decode"]
    finally:
        eng.close()
    assert len(tokens) == max_tokens and record["tokens"] == tokens
    assert len(record["fixed_at"]) == max_tokens
    _assert_follows_reference(prompt, tokens, record["fixed_at"])
    blocks = -(-(2 + max_tokens) // B)
    assert sum(t["denoise_lanes"] for t in ticks) == 2 + B * (blocks - 1)
    assert sum(t["commit_lanes"] for t in ticks) == blocks - 1
    assert sum(t["tokens_out"] for t in ticks) == max_tokens
    assert eng.kv.used_blocks == 0


def test_cancel_inside_a_block_frees_the_lane_for_the_next_stream():
    eng = _engine(max_slots=1, lane_counts=(1,))
    seen = []
    _spy(eng, seen)
    try:
        q, handle = eng.submit(list(range(1, 10)), 40)
        first = [q.get(timeout=300) for _ in range(3)]   # the first block
        eng.cancel(handle)
        assert _collect(q) is not None
        prompt = list(range(20, 31))
        tokens = _collect(eng.submit(prompt, 8)[0])
        record = eng.pass_trace()
    finally:
        eng.close()
    assert len(first) == 3 and len(tokens) == 8
    assert record[-1]["tokens"] == tokens
    _assert_follows_reference(prompt, tokens, record[-1]["fixed_at"])
    assert eng.kv.used_blocks == 0, eng.kv.ref_counts()


def test_prefix_adoption_ends_on_a_diffusion_blocks_edge():
    """A lane IS its blocks: a second prompt with the same first three pool
    blocks adopts them (a pool block is whole diffusion blocks), prefills
    its tail alone and streams what the rule gives on the reference."""
    reg = Registry()
    eng = _engine(registry=reg)
    shared = tuple(range(1, 13))
    prompts = [shared + (40 + i, 50 + i) for i in range(2)]
    try:
        served = [_collect(eng.submit(list(p), 6)[0]) for p in prompts]
        stats, record = eng.prefix_stats(), eng.pass_trace()
        spec = eng.spec_stats()
    finally:
        eng.close()
    for prompt, tokens, r in zip(prompts, served, record):
        assert r["tokens"] == tokens
        _assert_follows_reference(prompt, tokens, r["fixed_at"])
    assert stats["hits"] == 3
    assert reg.get("ctpu_lm_prefill_tokens_saved_total") == 12
    assert spec["enabled"] is False and "no draft" in spec["reason"]
    assert eng.kv.used_blocks == 0, eng.kv.ref_counts()


@pytest.mark.parametrize("swap", [None, 0], ids=["swap", "recompute"])
def test_preemption_parks_a_block_lane_and_brings_it_back(swap):
    """``tests/test_lm.py``'s scenario for this family: the pool cannot hold
    the high-priority stream beside the low one, so the low lane goes, by
    the host swap or by recompute, mid-generation, with its block's state,
    and comes back; both streams are what the rule gives on the reference."""
    eng = _engine(pool_tokens=64, tenant_priority={"hi": 10.0},
                  swap_block_limit=swap)
    pa, pb = (1, 2, 3, 4, 5), (9, 4)
    try:
        qa, _ = eng.submit(list(pa), 79, tenant="lo")   # 21 of 24 blocks
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
        record = {tuple(r["prompt"]): r for r in eng.pass_trace()}
    finally:
        eng.close()
    assert len(got["a"]) == 79 and len(got["b"]) == 18
    for prompt, tokens in ((pa, got["a"]), (pb, got["b"])):
        assert record[prompt]["tokens"] == tokens
        _assert_follows_reference(prompt, tokens, record[prompt]["fixed_at"])
    assert stats["preemptions"] >= 1
    assert stats["resumes"] == stats["preemptions"]
    assert eng.kv.used_blocks == 0, eng.kv.ref_counts()


# -- the seam -----------------------------------------------------------------

def test_the_family_answers_what_the_expert_families_answer_and_its_schedule():
    def public(obj):
        return {name for name in dir(obj) if not name.startswith("_")}

    moe = cohere2moe.Cohere2MoeConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=32, n_experts=8, top_k=2, experts_held=(1, 2),
        n_shared=2, window=8, max_seq=64, dtype="float32")
    blocks, other = CFG.family(CFG, BLOCK), moe.family(moe, BLOCK)
    assert public(blocks) - public(other) == {
        "block", "lane_state", "stored", "masks", "advance", "delivered"}
    assert not public(other) - public(blocks)
    assert blocks.block == B and not hasattr(other, "block")
    assert blocks.lane_state(5).shape == (5, 3, B)
    assert [blocks.stored(p) for p in (3, 4, 7, 8)] == [0, 4, 4, 8]
    assert [blocks.masks(4, p) for p in (4, 5, 7)] == [4, 3, 1]
    assert blocks.masks(8, 5) == B
    with pytest.raises(ValueError, match="no multiple"):
        CFG.family(CFG, 6)
    with pytest.raises(ValueError, match="whole blocks"):
        LmEngine(_params(), CFG, block_size=BLOCK, prefill_chunk=6,
                 min_bucket=6)
    with pytest.raises(ValueError, match="no draft"):
        LmEngine(_params(), CFG, block_size=BLOCK,
                 speculative={"k": 2, "drafter": "ngram"})


@pytest.mark.parametrize("program, metric", [
    ("tick", "sdar_decode_roofline_pct"),
    ("chunk", "sdar_prefill_roofline_pct")])
def test_programs_lower_under_the_names_their_metrics_read(program, metric):
    """``benchmark/metrics/sdar_*_roofline_pct.json`` find the tick and the
    chunk in the device trace by XLA module name."""
    n = 2
    programs = CFG.family(CFG, BLOCK)
    params = jax.eval_shape(
        lambda: sdar.init_params(jax.random.PRNGKey(0), CFG))
    kv = KvBlockPool(CFG, 8, BLOCK, lanes=n)
    width = CFG.max_seq // BLOCK
    if program == "tick":
        lowered = programs.make_tick(n).func.lower(
            params, programs.lane_state(n), *kv.pools.values(),
            jnp.zeros((n, width), jnp.int32), jnp.zeros((n,), jnp.int32),
            jnp.ones((n,), bool), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n, 2), jnp.uint32),
            **programs.make_tick(n).keywords)
    else:
        lowered = programs.prefill_jit.lower(
            params, jnp.zeros((1, 8), jnp.int32), *kv.pools.values(),
            jnp.zeros((width,), jnp.int32), jnp.int32(0), jnp.int32(5),
            jnp.zeros((2,), jnp.uint32), jnp.float32(0), jnp.int32(0),
            cfg=CFG, block_size=BLOCK)
    name = lowered.as_text().split("module @", 1)[1].split()[0]
    with open(ROOT / "benchmark" / "metrics" / f"{metric}.json") as f:
        assert name == json.load(f)["params"]["program"]
