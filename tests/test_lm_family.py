"""The seam between ``LmEngine`` and a model family: the configuration hands
out the family's programs (``cfg.family``), the engine imports no model, the
programs lower under the names the benchmark's trace readers look for, and
the decoder's three paged programs (one layer loop, ``transformer.
paged_layers``) agree with the contiguous ``prefill`` + ``decode_step``."""

import ast
import functools
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from client_tpu.serve.lm import KvBlockPool, LmEngine
from client_tpu.serve.models import cohere2moe, sambay
from client_tpu.serve.models import transformer as tfm

ROOT = pathlib.Path(__file__).resolve().parent.parent
BLOCK = 4

# tests/test_lm.py's decoder and tests/test_sambay.py's hybrid
DECODER = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq=96, dtype="float32")
HYBRID = sambay.SambaYConfig(
    vocab_size=97, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
    head_dim=16, d_ff=128, max_seq=64, window=8, d_inner=128, d_state=4,
    d_conv=4, dt_rank=4, dtype="float32")
# tests/test_cohere2moe.py's expert-parallel share
MOE = cohere2moe.Cohere2MoeConfig(
    vocab_size=97, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
    head_dim=16, d_ff=32, n_experts=8, top_k=2, experts_held=(1, 2, 5, 6),
    n_shared=2, window=8, max_seq=64, dtype="float32")


# -- the paged programs against the contiguous path ---------------------------

LENGTHS = (5, 8, 11)    # one lane ends on a block boundary
STEPS = 4               # tokens the reference generates after each prompt


def _tiny(n_rep):
    return tfm.TransformerConfig(
        vocab_size=64, d_model=2 * n_rep * 8, n_layers=2, n_heads=2 * n_rep,
        n_kv_heads=2, d_ff=32, max_seq=32, dtype="float32")


@functools.lru_cache(maxsize=None)
def _reference(n_rep):
    """A lane a prompt through the contiguous cache: ``prefill`` over the
    prompt, then ``decode_step`` fed its own greedy tokens.  Returns
    (cfg, params, [(prompt, tokens g0..g_STEPS, K [layers, S, n_kv, hd],
    V)]): row p of K/V is position p's, the prompt's then g0's, g1's .."""
    cfg = _tiny(n_rep)
    params = tfm.init_params(jax.random.PRNGKey(n_rep), cfg)
    rng = np.random.default_rng(n_rep)
    lanes = []
    for length in LENGTHS:
        prompt = rng.integers(1, cfg.vocab_size, (1, length)).astype(np.int32)
        logits, cache = tfm.prefill(
            params, jnp.asarray(prompt), cfg, tfm.init_cache(cfg, 1))
        tokens = [int(jnp.argmax(logits[0]))]
        for _ in range(STEPS):
            logits, cache = tfm.decode_step(
                params, jnp.asarray(tokens[-1:], jnp.int32), cfg, cache)
            tokens.append(int(jnp.argmax(logits[0])))
        lanes.append((prompt, tokens,
                      np.stack([np.asarray(k[0]) for k in cache["k"]]),
                      np.stack([np.asarray(v[0]) for v in cache["v"]])))
    return cfg, params, lanes


def _paged(cfg, lanes, upto):
    """Pools and tables with each lane's reference rows ``[0, upto(lane))``
    in place, scattered over shuffled blocks; the rest of the pool zero."""
    width = cfg.max_seq // BLOCK
    n_blocks = len(lanes) * width
    tables = np.random.default_rng(7).permutation(
        np.arange(1, n_blocks + 1)).reshape(len(lanes), width).astype(np.int32)
    shape = (n_blocks + 1, BLOCK, cfg.n_kv_heads, cfg.head_dim)
    pool_k = np.zeros((cfg.n_layers,) + shape, np.float32)
    pool_v = np.zeros((cfg.n_layers,) + shape, np.float32)
    for lane, (_, _, k, v) in enumerate(lanes):
        for p in range(upto(lane)):
            at = tables[lane, p // BLOCK], p % BLOCK
            pool_k[(slice(None),) + at] = k[:, p]
            pool_v[(slice(None),) + at] = v[:, p]
    return list(jnp.asarray(pool_k)), list(jnp.asarray(pool_v)), tables


def _rows(pool, table, positions):
    """[layers, len(positions), n_kv, hd]: a lane's rows read back through
    its table."""
    pool = np.stack([np.asarray(layer) for layer in pool])
    return np.stack([pool[:, table[p // BLOCK], p % BLOCK] for p in positions],
                    axis=1)


def _assert_rows(pool_k, pool_v, table, lane, positions):
    _, _, k, v = lane
    np.testing.assert_allclose(
        _rows(pool_k, table, positions), k[:, positions], atol=1e-5)
    np.testing.assert_allclose(
        _rows(pool_v, table, positions), v[:, positions], atol=1e-5)


def _lane_args(lanes):
    n = len(lanes)
    return (np.zeros(n, np.float32), np.zeros(n, np.int32),
            jax.random.split(jax.random.PRNGKey(3), n))


def _run_decode(cfg, params, lanes):
    """Each lane's g0 at its prompt's end: the tick gives g1 and writes the
    row of g0."""
    lens = np.array(LENGTHS, np.int32)
    pool_k, pool_v, tables = _paged(cfg, lanes, lambda lane: LENGTHS[lane])
    tokens, pool_k, pool_v, _ = tfm.paged_decode_tick(
        params, jnp.asarray([t[0] for _, t, _, _ in lanes], jnp.int32),
        pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lens),
        *_lane_args(lanes), cfg=cfg, n=len(lanes), block_size=BLOCK)
    assert np.asarray(tokens).tolist() == [t[1] for _, t, _, _ in lanes]
    for i, lane in enumerate(lanes):
        _assert_rows(pool_k, pool_v, tables[i], lane, [LENGTHS[i]])


def _run_prefill_chunk(cfg, params, lanes):
    """Each prompt in chunks of 8 (the longest takes two, the second from
    position 8): the last chunk gives g0, and the prompt's rows are in the
    pool, the padding's in the trash block."""
    pool_k, pool_v, tables = _paged(cfg, lanes, lambda lane: 0)
    for i, (prompt, tokens, _, _) in enumerate(lanes):
        length = prompt.shape[1]
        padded = np.zeros((1, -(-length // 8) * 8), np.int32)
        padded[:, :length] = prompt
        for start in range(0, padded.shape[1], 8):
            tok, pool_k, pool_v, _ = tfm.paged_prefill_chunk(
                params, jnp.asarray(padded[:, start:start + 8]), pool_k,
                pool_v, jnp.asarray(tables[i]), jnp.int32(start),
                jnp.int32(length), jax.random.PRNGKey(0), jnp.float32(0),
                jnp.int32(0), cfg=cfg, block_size=BLOCK)
        assert int(tok) == tokens[0]
        _assert_rows(pool_k, pool_v, tables[i], lanes[i], list(range(length)))
        beyond = _rows(pool_k, tables[i], range(length, cfg.max_seq))
        assert not beyond.any()


def _run_verify(cfg, params, lanes):
    """Width 4 over g0 and three drafts.  Lane 0's are the reference's own
    (all taken, the correction is g4), lane 1's second is wrong (one taken,
    then g2), lane 2 has one real draft of three (taken, then g2).  The
    rows of every position a lane may keep are the reference's."""
    w, vocab = 4, cfg.vocab_size
    props = np.array([t[1:w] for _, t, _, _ in lanes], np.int32)
    props[1, 1] = (props[1, 1] + 1) % vocab
    props[2, 1:] = 0
    counts = np.array([3, 3, 1], np.int32)
    pool_k, pool_v, tables = _paged(cfg, lanes, lambda lane: LENGTHS[lane])
    out, tokens, pool_k, pool_v, _ = tfm.paged_verify_tick(
        params, jnp.asarray([t[0] for _, t, _, _ in lanes], jnp.int32),
        pool_k, pool_v, jnp.asarray(tables),
        jnp.asarray(np.array(LENGTHS, np.int32)), *_lane_args(lanes),
        jnp.asarray(props), jnp.asarray(counts), cfg=cfg, n=len(lanes),
        width=w, block_size=BLOCK)
    accepted = [3, 1, 1]
    assert np.asarray(out).tolist() == [
        accepted, [t[a + 1] for a, (_, t, _, _) in zip(accepted, lanes)]]
    assert np.asarray(tokens).tolist() == np.asarray(out)[1].tolist()
    for i, lane in enumerate(lanes):
        kept = range(LENGTHS[i], LENGTHS[i] + accepted[i] + 1)
        _assert_rows(pool_k, pool_v, tables[i], lane, list(kept))


@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("program", ["decode", "prefill_chunk", "verify"])
def test_paged_program_agrees_with_the_contiguous_path(program, n_rep):
    """Each of the decoder's three programs, over a tiny float32
    configuration (MHA and 4 query heads a KV head), writes the pool rows
    and chooses the tokens that ``prefill`` + ``decode_step`` give at the
    same positions."""
    run = {"decode": _run_decode, "prefill_chunk": _run_prefill_chunk,
           "verify": _run_verify}[program]
    run(*_reference(n_rep))


# -- the names the benchmark's trace readers look for --------------------------

def _metric_program(name):
    params = json.loads(
        (ROOT / "benchmark" / "metrics" / f"{name}.json").read_text())["params"]
    return params.get("module") or params["program"]


def _lower(fn, *args, **static):
    """``fn`` lowered for ``args``: a jitted function, or a named one with
    its static arguments bound by ``functools.partial``."""
    if isinstance(fn, functools.partial):
        return fn.func.lower(*args, **fn.keywords)
    return fn.lower(*args, **static)


@pytest.mark.parametrize("cfg, program, metric", [
    (DECODER, "tick", "decode_roofline_pct"),
    (DECODER, "chunk", "prefill_roofline_pct"),
    (HYBRID, "tick", "sambay_decode_roofline_pct"),
    (HYBRID, "chunk", "sambay_prefill_roofline_pct"),
    (MOE, "tick", "cohere2moe_decode_roofline_pct"),
    (MOE, "chunk", "cohere2moe_prefill_roofline_pct"),
], ids=lambda v: v if isinstance(v, str) else type(v).__name__)
def test_family_program_lowers_under_the_name_its_metric_reads(
        cfg, program, metric):
    """``benchmark/metrics/*_roofline_pct.json`` find a family's tick and
    chunk in the device trace by XLA module name.  A rename shows here, and
    not as a ``null`` in the ledger that blocks every later PR."""
    n = 2
    programs = cfg.family(cfg, BLOCK)
    params = jax.eval_shape(
        lambda: cfg.family.init_params(jax.random.PRNGKey(0), cfg))
    kv = KvBlockPool(cfg, 8, BLOCK, lanes=n)
    # a family with fixed per-lane state takes it after the pools, and says
    # which lanes (a tick) or which lane from where (a chunk) it is for; one
    # whose expert layers route says which lanes are in the tick
    state = (kv.lane_state,) if kv.lane_state else ()
    named = not isinstance(programs, tfm.DecoderPrograms)
    width = cfg.max_seq // BLOCK
    if program == "tick":
        live = (jnp.ones((n,), bool),) if named else ()
        lowered = _lower(
            programs.make_tick(n), params, jnp.zeros((n,), jnp.int32),
            kv.pools["k"], kv.pools["v"], *state,
            jnp.zeros((n, width), jnp.int32), jnp.zeros((n,), jnp.int32),
            *live, jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.int32),
            jnp.zeros((n, 2), jnp.uint32))
    else:
        slot, fresh = ((jnp.int32(0),), (jnp.bool_(True),)) if state else (
            (), ())
        static = dict(cfg=cfg, block_size=BLOCK) if named else {}
        lowered = _lower(
            programs.prefill_jit, params, jnp.zeros((1, 8), jnp.int32),
            kv.pools["k"], kv.pools["v"], *state,
            jnp.zeros((width,), jnp.int32), *slot, jnp.int32(0), jnp.int32(5),
            *fresh, jnp.zeros((2,), jnp.uint32), jnp.float32(0), jnp.int32(0),
            **static)
    name = lowered.as_text().split("module @", 1)[1].split()[0]
    assert name == _metric_program(metric)


# -- the seam ------------------------------------------------------------------

def test_engine_imports_no_model():
    """``serve/lm/engine.py`` reaches a family through ``cfg.family`` alone:
    nothing under ``serve/models`` is imported, at the top or inside a
    function."""
    tree = ast.parse(
        (ROOT / "client_tpu" / "serve" / "lm" / "engine.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module or "")
            imported.update(f"{node.module}.{alias.name}"
                            for alias in node.names)
    assert not [name for name in imported if "serve.models" in name]


def test_the_families_answer_the_same_questions():
    """Every configuration hands out an object with one attribute set, but
    for what a family alone can answer: ``make_verify`` (the speculative
    verify step rewinds by a pointer into a lane's blocks) where the family
    has the program; the expert family's reason for having none
    (``no_verify``) and what it counts for a ``tick_trace()`` entry
    (``counters`` on the device, ``tick_fields`` on the host)."""
    def public(obj):
        return {name for name in dir(obj) if not name.startswith("_")}

    decoder, hybrid, moe = (cfg.family(cfg, BLOCK)
                            for cfg in (DECODER, HYBRID, MOE))
    assert public(decoder) - public(hybrid) == {"make_verify"}
    assert not public(hybrid) - public(decoder)
    assert public(moe) - public(hybrid) == {
        "no_verify", "counters", "tick_fields"}
    assert not public(hybrid) - public(moe)
    for programs in (decoder, hybrid):
        assert hasattr(programs, "make_verify") == (not programs.recurrent)
    assert not moe.recurrent and moe.no_verify
    assert [name for name, *_ in moe.counters] == [
        "experts_held", "experts_hit", "expert_rows", "expert_rows_max"]
    # what the runner asks before any program exists, on the class
    assert public(DECODER.family) - public(HYBRID.family) == {"make_verify"}
    for cfg in (HYBRID, MOE):
        assert cfg.family.generate is None
        assert cfg.family.quantize_params is None


@pytest.mark.parametrize("cfg, reason", [
    (HYBRID, "recurrent state"), (MOE, "no verify program")],
    ids=lambda v: v if isinstance(v, str) else type(v).__name__)
def test_speculative_is_refused_where_the_family_has_no_verify_program(
        cfg, reason):
    """``LmEngine(speculative=...)`` raises at construction for a family
    without ``make_verify``, each for its own reason, which ``spec_stats()``
    repeats: no family reaches ``_verify_for`` without a program."""
    params = jax.eval_shape(
        lambda: cfg.family.init_params(jax.random.PRNGKey(0), cfg))
    with pytest.raises(ValueError, match=reason):
        LmEngine(params, cfg, block_size=BLOCK,
                 speculative={"k": 2, "drafter": "ngram"})
    eng = LmEngine(params, cfg, block_size=BLOCK)
    try:
        stats = eng.spec_stats()
    finally:
        eng.close()
    assert stats["enabled"] is False and reason in stats["reason"]
    decoder = LmEngine(jax.eval_shape(lambda: tfm.init_params(
        jax.random.PRNGKey(0), DECODER)), DECODER, block_size=BLOCK)
    try:
        assert decoder.spec_stats() == {}
    finally:
        decoder.close()
