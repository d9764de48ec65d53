"""The seam between ``LmEngine`` and a model family: the configuration hands
out the family's programs (``cfg.family``), the engine imports no model, the
programs lower under the names the benchmark's trace readers look for, and
the decoder's three paged programs (one layer loop, ``transformer.
paged_layers``) agree with the contiguous ``prefill`` + ``decode_step``: the
decode tick reading its blocks in place or through the loop, the chunk
writing whole blocks."""

import ast
import functools
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from client_tpu.ops import paged_decode
from client_tpu.serve.lm import KvBlockPool, LmEngine
from client_tpu.serve.models import axk1, cohere2moe, longcat, sambay
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
# tests/test_axk1.py's latent-attention share: one pool, no key/value pair
LATENT = axk1.AxK1Config(
    vocab_size=97, d_model=32, n_layers=4, n_heads=4, q_lora_rank=16,
    kv_lora_rank=24, nope_dim=8, rope_dim=8, v_dim=8, first_dense=1,
    d_dense=48, d_ff=16, n_experts=8, top_k=2, experts_held=(1, 2, 5, 6),
    n_shared=1, rope_factor=4.0, rope_original=32, beta_fast=4.0,
    max_seq=64, dtype="float32")
# tests/test_longcat.py's double layers: two latent pools a layer, zero slots
SHORTCUT = longcat.LongcatConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, q_lora_rank=16,
    kv_lora_rank=24, nope_dim=8, rope_dim=8, v_dim=8, d_dense=48, d_ff=16,
    n_experts=32, n_zero=16, top_k=6, experts_held=tuple(range(1, 32, 2)),
    rope_theta=10000.0, max_seq=64, dtype="float32")


# -- the paged programs against the contiguous path ---------------------------

LENGTHS = (5, 8, 11)    # one lane ends on a block boundary
STEPS = 4               # tokens the reference generates after each prompt
# (query heads a KV head, head size): MHA and grouped queries at a toy head
# size, and grouped queries at the head size the chip's kernel takes
SHAPES = [(1, 8), (4, 8), (4, 128)]


def _tiny(n_rep, hd=8, max_seq=32):
    return tfm.TransformerConfig(
        vocab_size=64, d_model=2 * n_rep * hd, n_layers=2, n_heads=2 * n_rep,
        n_kv_heads=2, d_ff=32, max_seq=max_seq, dtype="float32")


@functools.lru_cache(maxsize=None)
def _reference(n_rep, hd=8, lengths=LENGTHS, max_seq=32):
    """A lane a prompt through the contiguous cache: ``prefill`` over the
    prompt (a lane of length 0 has none), then ``decode_step`` fed its own
    greedy tokens.  Returns (cfg, params, [(prompt, tokens g0..g_STEPS, K
    [layers, S, n_kv, hd], V)]): row p of K/V is position p's, the
    prompt's then g0's, g1's .."""
    cfg = _tiny(n_rep, hd, max_seq)
    params = tfm.init_params(jax.random.PRNGKey(n_rep), cfg)
    rng = np.random.default_rng(n_rep)
    lanes = []
    for length in lengths:
        prompt = rng.integers(1, cfg.vocab_size, (1, length)).astype(np.int32)
        cache = tfm.init_cache(cfg, 1)
        if length:
            logits, cache = tfm.prefill(params, jnp.asarray(prompt), cfg,
                                        cache)
        else:   # no prompt: the first token is given, not chosen
            logits = jax.nn.one_hot(jnp.array([1]), cfg.vocab_size)
        tokens = [int(jnp.argmax(logits[0]))]
        for _ in range(STEPS):
            logits, cache = tfm.decode_step(
                params, jnp.asarray(tokens[-1:], jnp.int32), cfg, cache)
            tokens.append(int(jnp.argmax(logits[0])))
        lanes.append((prompt, tokens,
                      np.stack([np.asarray(k[0]) for k in cache["k"]]),
                      np.stack([np.asarray(v[0]) for v in cache["v"]])))
    return cfg, params, lanes


def _paged(cfg, lanes, upto, block=BLOCK):
    """Pools [n_blocks+1, n_kv, block, hd] and tables with each lane's
    reference rows ``[0, upto(lane))`` in place, scattered over shuffled
    blocks; the rest of the pool zero."""
    width = cfg.max_seq // block
    n_blocks = len(lanes) * width
    tables = np.random.default_rng(7).permutation(
        np.arange(1, n_blocks + 1)).reshape(len(lanes), width).astype(np.int32)
    shape = (n_blocks + 1, cfg.n_kv_heads, block, cfg.head_dim)
    pool_k = np.zeros((cfg.n_layers,) + shape, np.float32)
    pool_v = np.zeros((cfg.n_layers,) + shape, np.float32)
    for lane, (_, _, k, v) in enumerate(lanes):
        for p in range(upto(lane)):
            pool_k[:, tables[lane, p // block], :, p % block] = k[:, p]
            pool_v[:, tables[lane, p // block], :, p % block] = v[:, p]
    return list(jnp.asarray(pool_k)), list(jnp.asarray(pool_v)), tables


def _rows(pool, table, positions, block=BLOCK):
    """[layers, len(positions), n_kv, hd]: a lane's rows read back through
    its table."""
    pool = np.stack([np.asarray(layer) for layer in pool])
    return np.stack(
        [pool[:, table[p // block], :, p % block] for p in positions], axis=1)


def _assert_rows(pool_k, pool_v, table, lane, positions, block=BLOCK):
    _, _, k, v = lane
    np.testing.assert_allclose(
        _rows(pool_k, table, positions, block), k[:, positions], atol=1e-5)
    np.testing.assert_allclose(
        _rows(pool_v, table, positions, block), v[:, positions], atol=1e-5)


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
        jnp.ones(len(lanes), bool), *_lane_args(lanes), cfg=cfg,
        n=len(lanes), block_size=BLOCK)
    assert np.asarray(tokens).tolist() == [t[1] for _, t, _, _ in lanes]
    for i, lane in enumerate(lanes):
        _assert_rows(pool_k, pool_v, tables[i], lane, [LENGTHS[i]])


def _run_prefill_chunk(cfg, params, lanes):
    """Each prompt in chunks of 8 (the longest takes two, the second from
    position 8), two whole blocks each: the last chunk gives g0, and the
    prompt's rows are in the pool; a block of padding alone is in the trash
    block, and the padding behind a prompt that ends inside a block (5, 11)
    is in that block, where nothing reads it."""
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
        edge = -(-length // BLOCK) * BLOCK
        assert not _rows(pool_k, tables[i], range(edge, cfg.max_seq)).any()
        assert (edge == length
                or _rows(pool_k, tables[i], range(length, edge)).any())


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


@pytest.mark.parametrize("n_rep, hd", SHAPES)
@pytest.mark.parametrize("program", ["decode", "prefill_chunk", "verify"])
def test_paged_program_agrees_with_the_contiguous_path(program, n_rep, hd):
    """Each of the decoder's three programs, over a tiny float32
    configuration (``SHAPES``), writes the pool rows and chooses the tokens
    that ``prefill`` + ``decode_step`` give at the same positions: the
    decode tick through the kernel that reads the blocks in place, the
    chunk and the verify tick at width 4 through the loop."""
    run = {"decode": _run_decode, "prefill_chunk": _run_prefill_chunk,
           "verify": _run_verify}[program]
    run(*_reference(n_rep, hd))


# -- the decode tick in place and the chunk's whole blocks, at head size 128 ----

WIDE = 16                                   # the chat cell's block
STEP = paged_decode.STEP_BLOCKS * WIDE      # positions a step of the kernel
# lanes of one tick, by their length before it: none yet, inside a block, on
# both sides of a block's edge (the tick's row ends a block, or opens one)
# and of a step's (the row ends the kernel's first step, or opens a second),
# and past it; then a lane that is not in the tick
TICK_LENS = (0, 5, WIDE - 1, WIDE, STEP - 1, STEP, STEP + 44)


def _tick_case():
    """(cfg, params, lanes, pool_k, pool_v, tables, lens, live) of one decode
    tick over ``TICK_LENS`` and an idle lane, grouped queries (4 rows a KV
    head) at head size 128, blocks of 16."""
    cfg, params, lanes = _reference(4, 128, TICK_LENS, STEP + 64)
    pool_k, pool_v, tables = _paged(
        cfg, lanes, lambda lane: TICK_LENS[lane], WIDE)
    tables = np.concatenate([tables, np.zeros_like(tables[:1])])
    lens = np.array(TICK_LENS + (0,), np.int32)
    return (cfg, params, lanes, pool_k, pool_v, tables, lens,
            np.arange(len(lens)) < len(lanes))


@pytest.mark.parametrize("reader", ["in_place", "loop"])
def test_decode_tick_at_head_size_128_agrees_with_the_contiguous_path(
        reader, monkeypatch):
    """Lanes of unlike lengths in one tick, through the kernel and through
    the loop: each live lane's next token and new row are ``decode_step``'s,
    the idle lane's row lands in the trash block, and the kernel is handed
    each lane's own length, 0 for the idle one: what ``_tick_reads`` turns
    into the engine's count."""
    cfg, params, lanes, pool_k, pool_v, tables, lens, live = _tick_case()
    handed = []
    if reader == "loop":
        monkeypatch.setattr(tfm, "reads_in_place", lambda pool: False)
    in_place = tfm._attend_in_place
    monkeypatch.setattr(
        tfm, "_attend_in_place",
        lambda *a: handed.append(np.asarray(a[4])) or in_place(*a))
    n = len(lens)
    tokens, new_k, new_v, _ = tfm.paged_decode_tick(
        params, jnp.asarray([t[0] for _, t, _, _ in lanes] + [0], jnp.int32),
        pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lens),
        jnp.asarray(live), np.zeros(n, np.float32), np.zeros(n, np.int32),
        jax.random.split(jax.random.PRNGKey(3), n), cfg=cfg, n=n,
        block_size=WIDE)
    assert np.asarray(tokens)[live].tolist() == [t[1] for _, t, _, _ in lanes]
    for i, lane in enumerate(lanes):
        _assert_rows(new_k, new_v, tables[i], lane, [TICK_LENS[i]], WIDE)
    # nothing else moved: a lane's block at its length, and the trash block
    written = {int(tables[i, TICK_LENS[i] // WIDE])
               for i in range(len(lanes))} | {KvBlockPool.TRASH}
    for before, after in zip(pool_k + pool_v, new_k + new_v):
        moved = np.flatnonzero(
            (np.asarray(before) != np.asarray(after)).any(axis=(1, 2, 3)))
        assert set(moved.tolist()) <= written
    programs = tfm.DecoderPrograms(cfg, WIDE)
    if reader == "loop":
        assert not handed
        assert programs._tick_reads(lens[live], tables.shape[1]) is None
        assert programs.tick_fields("decode", lens[live]) == {}
        return
    assert [h.tolist() for h in handed] == cfg.n_layers * [
        [n + 1 for n in TICK_LENS] + [0]]
    assert programs._tick_reads(lens[live], tables.shape[1]) == (
        paged_decode.steps_read(handed[0][live], WIDE) * STEP).tolist() == [
            STEP] * 5 + [2 * STEP] * 2
    # the same steps on the entry, over every layer: none of these lanes
    # holds a whole step with a whole one after it
    assert programs.tick_fields("decode", lens[live]) == {
        "kv_steps": cfg.n_layers * 9, "kv_steps_full": 0}
    assert programs.tick_fields("prefill_chunk", lens[live]) == {}


def test_decode_tick_in_place_gives_the_loops_hidden_state():
    """``paged_layers`` over the same tick with and without the lanes'
    lengths: the kernel's read and the loop's give the same final-normed
    ``x`` for every live lane, and leave the same pools."""
    cfg, params, lanes, pool_k, pool_v, tables, lens, live = _tick_case()
    n = len(lens)
    x = jnp.take(params["embed"], jnp.asarray(
        [t[0] for _, t, _, _ in lanes] + [0]), axis=0)[:, None, :]
    blk = np.where(live, tables[np.arange(n), lens // WIDE], 0)
    run = functools.partial(
        tfm.paged_layers, params, x, pool_k, pool_v, jnp.asarray(tables),
        jnp.asarray(lens)[:, None],
        lambda pool, rows: tfm._write_rows(
            pool, jnp.asarray(blk), jnp.asarray(lens % WIDE), rows),
        cfg, WIDE)
    x_loop, *loop_pools = run()
    x_kernel, *kernel_pools = run(lengths=jnp.where(live, lens + 1, 0))
    np.testing.assert_allclose(np.asarray(x_kernel)[live],
                               np.asarray(x_loop)[live], atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(kernel_pools),
                    jax.tree_util.tree_leaves(loop_pools)):
        live_blocks = np.arange(a.shape[0]) != KvBlockPool.TRASH
        np.testing.assert_allclose(np.asarray(a)[live_blocks],
                                   np.asarray(b)[live_blocks], atol=1e-5)


def _as_rows(pool, rows, blks):
    """``_write_blocks`` spelled as the row scatter it takes the place of."""
    block = pool.shape[2]
    return tfm._write_rows(
        pool, jnp.repeat(blks, block),
        jnp.tile(jnp.arange(block), blks.shape[0]), rows)


@pytest.mark.parametrize("prompt_len", [300, 512, 700])
def test_prefill_chunk_writes_whole_blocks_where_rows_went(
        prompt_len, monkeypatch):
    """A prompt through 512-wide chunks at head size 128 and blocks of 16
    (300: one chunk that ends inside a block, thirteen blocks of padding
    behind it; 512: the chunk's and a block's edge; 700: a second chunk at
    ``start`` 512).  The chunk gives g0; the prompt's rows are
    ``prefill``'s; every block wholly past the prompt is untouched, its
    rows in the trash block; and the same chunks writing rows leave the
    same blocks."""
    cfg, params, (lane,) = _reference(4, 128, (prompt_len,), 1024 + WIDE)
    prompt, tokens = lane[:2]
    padded = np.zeros((1, -(-prompt_len // 512) * 512), np.int32)
    padded[:, :prompt_len] = prompt

    def run():
        pool_k, pool_v, tables = _paged(cfg, [lane], lambda lane: 0, WIDE)
        for start in range(0, padded.shape[1], 512):
            tok, pool_k, pool_v, _ = tfm.paged_prefill_chunk(
                params, jnp.asarray(padded[:, start:start + 512]), pool_k,
                pool_v, jnp.asarray(tables[0]), jnp.int32(start),
                jnp.int32(prompt_len), jax.random.PRNGKey(0), jnp.float32(0),
                jnp.int32(0), cfg=cfg, block_size=WIDE)
        return int(tok), pool_k, pool_v, tables[0]

    tok, pool_k, pool_v, table = run()
    assert tok == tokens[0]
    _assert_rows(pool_k, pool_v, table, lane, list(range(prompt_len)), WIDE)
    edge = -(-prompt_len // WIDE) * WIDE
    assert not _rows(pool_k, table, range(edge, cfg.max_seq), WIDE).any()
    padding = padded.shape[1] > edge
    assert np.asarray(pool_k[0][KvBlockPool.TRASH]).any() == padding
    monkeypatch.setattr(tfm, "_write_blocks", _as_rows)
    tok_rows, rows_k, rows_v, _ = run()
    assert tok_rows == tok
    held = table[:edge // WIDE]
    for a, b in zip(pool_k + pool_v, rows_k + rows_v):
        np.testing.assert_allclose(np.asarray(a)[held], np.asarray(b)[held],
                                   atol=1e-5)


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
    (LATENT, "tick", "axk1_decode_roofline_pct"),
    (LATENT, "chunk", "axk1_prefill_roofline_pct"),
    (SHORTCUT, "tick", "longcat_decode_roofline_pct"),
    (SHORTCUT, "chunk", "longcat_prefill_roofline_pct"),
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
    # which lane from where a chunk is for; every tick is told which lanes
    # are in it
    state = (kv.lane_state,) if kv.lane_state else ()
    named = not isinstance(programs, tfm.DecoderPrograms)
    width = cfg.max_seq // BLOCK
    if program == "tick":
        lowered = _lower(
            programs.make_tick(n), params, jnp.zeros((n,), jnp.int32),
            *kv.pools.values(), *state,
            jnp.zeros((n, width), jnp.int32), jnp.zeros((n,), jnp.int32),
            jnp.ones((n,), bool), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n, 2), jnp.uint32))
    else:
        slot, fresh = ((jnp.int32(0),), (jnp.bool_(True),)) if state else (
            (), ())
        static = dict(cfg=cfg, block_size=BLOCK) if named else {}
        lowered = _lower(
            programs.prefill_jit, params, jnp.zeros((1, 8), jnp.int32),
            *kv.pools.values(), *state,
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
    for what a family alone can answer: ``make_verify`` and ``verify`` (the
    speculative verify step rewinds by a pointer into a lane's blocks; the
    family dispatches it over its own pools, as it does ``tick``) where the
    family has the program; the expert families' reason for having none
    (``no_verify``) and what they count for a ``tick_trace()`` entry
    (``counters`` on the device, ``tick_fields`` on the host).  The
    latent-attention family answers what the expert family answers."""
    def public(obj):
        return {name for name in dir(obj) if not name.startswith("_")}

    decoder, hybrid, moe, latent = (cfg.family(cfg, BLOCK) for cfg in (
        DECODER, HYBRID, MOE, LATENT))
    assert public(decoder) - public(hybrid) == {"make_verify", "verify"}
    assert public(latent) == public(moe)
    assert [name for name, *_ in latent.counters] == [
        name for name, *_ in moe.counters]
    assert not latent.recurrent and latent.no_verify
    assert not public(hybrid) - public(decoder)
    assert public(moe) - public(hybrid) == {"no_verify", "counters"}
    assert not public(hybrid) - public(moe)
    for programs in (decoder, hybrid):
        assert hasattr(programs, "make_verify") == (not programs.recurrent)
    assert not moe.recurrent and moe.no_verify
    assert [name for name, *_ in moe.counters] == [
        "experts_held", "experts_hit", "expert_rows", "expert_rows_max"]
    # what the runner asks before any program exists, on the class
    assert public(DECODER.family) - public(HYBRID.family) == {
        "make_verify", "verify"}
    for cfg in (HYBRID, MOE, LATENT):
        assert cfg.family.generate is None
        assert cfg.family.quantize_params is None
        assert cfg.family.serving_params is None


@pytest.mark.parametrize("cfg, reason", [
    (HYBRID, "recurrent state"), (MOE, "no verify program"),
    (LATENT, "no verify program")],
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
