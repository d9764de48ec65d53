"""``client_tpu.ops.paged_decode``: the kernel (under the TPU interpreter,
which models its copies in flight, their semaphores and a scratch that
starts as NaN) against a plain gather and softmax written here, and ``sambay.diff_attention``'s two shared halves, which carry the
kernel's result into a layer, against the whole function as PR 31 had it.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from client_tpu.ops import paged_decode
from client_tpu.serve.lm import KvBlockPool
from client_tpu.serve.models import sambay

STEP = paged_decode.STEP_BLOCKS
WIDTH = 2 * STEP + 3  # table columns: two whole steps and a part of one

# (dtype, KV heads, block, width of a row, tolerance): the tests' tiny
# shape in float32, and a block of the reason cell's pool in bfloat16
SHAPES = {
    "float32-tiny": ("float32", 1, 4, 32, 2e-5),
    "bfloat16-cell": ("bfloat16", 10, 16, 128, 2e-2),
}
LENGTHS = {
    "0": lambda block: 0,
    "1": lambda block: 1,
    "block-1": lambda block: block - 1,
    "block": lambda block: block,
    "block+1": lambda block: block + 1,
    "step": lambda block: STEP * block,
    "step+1": lambda block: STEP * block + 1,
    "table": lambda block: WIDTH * block,
}


def _plain(q, pool_k, pool_v, tables, lengths, starts=None):
    """Gather a lane's blocks, softmax over its live positions (from its
    start, where it has one), weigh."""
    out = np.zeros(q.shape, np.float32)
    for lane, length in enumerate(lengths):
        if not length:
            continue
        first = 0 if starts is None else starts[lane]
        k, v = (np.concatenate([np.asarray(pool[b], np.float32)
                                for b in tables[lane]],
                               axis=1)[:, first:length]
                for pool in (pool_k, pool_v))
        s = np.einsum("grd,gtd->grt", np.asarray(q[lane], np.float32), k)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        out[lane] = np.einsum("grt,gtd->grd",
                              p / p.sum(axis=-1, keepdims=True), v)
    return out


@pytest.fixture(scope="module")
def case():
    """(q, pools, tables) a (shape, rows), made once: the lengths are what a
    test varies, so a shape compiles once.  Four lanes: a whole table (so
    that the copy ahead crosses from its last step into the next lane's
    first), the lane under test, a lane that is not in the tick, a short
    one.  Every lane's blocks lie permuted through the pool; the columns
    past what a lane can hold point at the trash block, which all share."""
    made = {}

    def make(shape, rows):
        if (shape, rows) not in made:
            dtype, heads, block, wide, _ = SHAPES[shape]
            rng = np.random.default_rng(len(made))
            n_blocks = 4 * WIDTH
            pools = [jnp.asarray(rng.normal(size=(
                n_blocks + 1, heads, block, wide)), dtype) for _ in "kv"]
            q = jnp.asarray(rng.normal(size=(4, heads, rows, wide))
                            * wide ** -0.5, dtype)
            tables = (rng.permutation(n_blocks) + 1).reshape(4, WIDTH)
            tables[3, 2:] = KvBlockPool.TRASH
            made[shape, rows] = q, pools, tables.astype(np.int32)
        return made[shape, rows]

    return make


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("rows", [2, 4])
@pytest.mark.parametrize("length", list(LENGTHS))
def test_kernel_matches_a_plain_gather_and_softmax(case, length, rows, shape):
    _, _, block, _, tol = SHAPES[shape]
    q, (pool_k, pool_v), tables = case(shape, rows)
    held = LENGTHS[length](block)
    tables = tables.copy()
    tables[1, -(-held // block):] = KvBlockPool.TRASH  # never read
    lengths = np.array([WIDTH * block, held, 0, block + 3], np.int32)
    before = [np.asarray(pool) for pool in (pool_k, pool_v)]
    out = np.asarray(paged_decode.paged_decode_attention(
        q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lengths),
        interpret=pltpu.InterpretParams()))
    assert out.dtype == np.float32 and out.shape == q.shape
    np.testing.assert_allclose(
        out, _plain(q, pool_k, pool_v, tables, lengths), atol=tol, rtol=tol)
    # a lane that is not in the tick gets zeros, and nothing writes a pool
    assert not out[2].any() and (held or not out[1].any())
    for pool, was in zip((pool_k, pool_v), before):
        np.testing.assert_array_equal(np.asarray(pool), was)


# where the lane under test starts its read, for a length of two steps and
# seven positions: nowhere special, the last position of the first step,
# the first of the second, inside the third, the last position alone
STARTS = {
    "0": lambda span: 0,
    "3": lambda span: 3,
    "step-1": lambda span: span - 1,
    "step": lambda span: span,
    "2step+5": lambda span: 2 * span + 5,
    "length-1": lambda span: 2 * span + 6,
}


@pytest.mark.parametrize("shape, rows", [("float32-tiny", 2),
                                         ("bfloat16-cell", 16)])
@pytest.mark.parametrize("start", list(STARTS))
def test_kernel_with_first_positions_matches_the_gathered_form(
        case, start, shape, rows):
    """A window layer's read: each lane from its own first position.  The
    steps before it are not walked (their table columns point at the trash
    block here, which holds other values than the lane's blocks did), the
    positions of the first step before it are masked, and the copy ahead
    crosses from a lane's last step into the next lane's FIRST one."""
    _, _, block, _, tol = SHAPES[shape]
    span = STEP * block
    q, (pool_k, pool_v), tables = case(shape, rows)
    held = 2 * span + 7
    first = STARTS[start](span)
    lengths = np.array([WIDTH * block, held, 0, block + 3], np.int32)
    starts = np.array([span + 1, first, 0, 2], np.int32)
    want = _plain(q, pool_k, pool_v, tables, lengths, starts)
    tables = tables.copy()
    tables[1, :first // span * STEP] = KvBlockPool.TRASH  # never read
    tables[0, :STEP] = KvBlockPool.TRASH
    out = np.asarray(paged_decode.paged_decode_attention(
        q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lengths),
        jnp.asarray(starts), interpret=pltpu.InterpretParams()))
    np.testing.assert_allclose(out, want, atol=tol, rtol=tol)
    assert not out[2].any()


# -- latent rows: one pool, key and value at once ------------------------------

# (dtype, block, width of a row, its leading columns that are values, query
# rows, tolerance): a float32 row of two tiles whose first is the value, and
# a bfloat16 block as the chip takes it, the value three tiles of four
LATENT = {
    "float32-tiny": ("float32", 4, 256, 128, 4, 2e-5),
    "bfloat16-tiles": ("bfloat16", 16, 512, 384, 8, 2e-2),
}


def _plain_latent(q, pool, tables, lengths, value_width, starts=None):
    out = np.zeros(q.shape[:-1] + (value_width,), np.float32)
    for lane, length in enumerate(lengths):
        if not length:
            continue
        first = 0 if starts is None else starts[lane]
        rows = np.concatenate([np.asarray(pool[b], np.float32) for b in
                               tables[lane]], axis=1)[:, first:length]
        s = np.einsum("grd,gtd->grt", np.asarray(q[lane], np.float32), rows)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        out[lane] = np.einsum("grt,gtd->grd",
                              p / p.sum(axis=-1, keepdims=True),
                              rows[:, :, :value_width])
    return out


LATENT_STEP = paged_decode.LATENT_STEP_BLOCKS
LATENT_WIDTH = 2 * LATENT_STEP + 3  # two whole steps and a part of one


@pytest.mark.parametrize("shape", list(LATENT))
@pytest.mark.parametrize("length", ["0", "1", "block+1", "step", "step+1",
                                    "table"])
def test_latent_kernel_reads_one_pool_as_keys_and_as_values(shape, length):
    """``pool_v`` None: every query row of a lane against ONE row a
    position, all of it as key and its first ``value_width`` columns as
    value, each lane to its own length: a lane of the whole table, the lane
    under test (nothing, one row, across a block's and a step's edge, the
    latent step of ``LATENT_STEP`` blocks), a lane that is not in the tick
    and a short one, blocks permuted through the pool, under the
    interpreter that models the copies in flight."""
    dtype, block, wide, value_width, rows, tol = LATENT[shape]
    rng = np.random.default_rng(block)
    n_blocks = 4 * LATENT_WIDTH
    pool = jnp.asarray(rng.normal(size=(n_blocks + 1, 1, block, wide)), dtype)
    q = jnp.asarray(rng.normal(size=(4, 1, rows, wide)) * wide ** -0.5, dtype)
    tables = (rng.permutation(n_blocks) + 1).reshape(
        4, LATENT_WIDTH).astype(np.int32)
    held = {"step": LATENT_STEP * block, "step+1": LATENT_STEP * block + 1,
            "table": LATENT_WIDTH * block}.get(length) \
        or LENGTHS[length](block)
    tables[1, -(-held // block):] = KvBlockPool.TRASH  # never read
    tables[3, 2:] = KvBlockPool.TRASH
    lengths = np.array([LATENT_WIDTH * block, held, 0, block + 3], np.int32)
    before = np.asarray(pool)
    out = np.asarray(paged_decode.paged_decode_attention(
        q, pool, None, jnp.asarray(tables), jnp.asarray(lengths),
        value_width=value_width, interpret=pltpu.InterpretParams()))
    assert out.dtype == np.float32
    assert out.shape == (4, 1, rows, value_width)
    np.testing.assert_allclose(
        out, _plain_latent(q, pool, tables, lengths, value_width),
        atol=tol, rtol=tol)
    assert not out[2].any() and (held or not out[1].any())
    np.testing.assert_array_equal(np.asarray(pool), before)


# -- the full-step path --------------------------------------------------------

# lengths (and first positions) of up to four lanes, from the kernel's step
# of ``span`` positions: what a full step's straight-line body and its one
# wait have to get right beside the loops they stand for
FULL = {
    # whole steps and nothing else: a lane's last step hands over from the
    # loops' path to the next lane's full steps
    "k-span": lambda span: ([3 * span, 2 * span, span, 4 * span], None),
    "k-span-1": lambda span: ([3 * span - 1, 2 * span - 1, span - 1], None),
    "k-span+1": lambda span: ([3 * span + 1, 2 * span + 1, span + 1], None),
    "span-1-and-1": lambda span: ([span - 1, 1, 3 * span, 1], None),
    # the copy ahead of a lane's last step crosses the empty lane
    "empty-between-full": lambda span: ([3 * span, 0, 3 * span + 2], None),
    "partial-then-full": lambda span: ([span // 2, 4 * span, 5], None),
    "full-then-partial": lambda span: ([4 * span, span // 2, 3 * span], None),
    "start-inside-a-full-step": lambda span: (
        [4 * span + 3, 3 * span, 4 * span], [span + 5, 7, 2 * span + 1]),
    "start-on-a-steps-edge": lambda span: (
        [4 * span + 3, 3 * span, 4 * span], [2 * span, span, 3 * span - 1]),
}
FULL_STEPS = 5  # table columns, in steps


@pytest.fixture(scope="module")
def walks():
    """``walk(form, windowed)``: the kernel, and the kernel with no step
    counted full, so that every step takes the loops of a run-time trip
    count and their wait a block (the step as the parent commit had it,
    whole), both under the interpreter that models the copies in flight,
    their semaphores and a scratch that starts as NaN; with their inputs
    ``(q, pools, tables)``.  Each compiles once: a case varies lengths."""
    made = {}

    def attend(*args, **keywords):
        return paged_decode.paged_decode_attention(
            *args, interpret=pltpu.InterpretParams(), **keywords)

    def walk(form, windowed):
        if (form, windowed) not in made:
            latent = form == "latent"
            block, wide = 4, 256 if latent else 32
            rng = np.random.default_rng(len(made))
            width = FULL_STEPS * paged_decode.step_blocks(latent)
            n_blocks = 4 * width
            pools = [jnp.asarray(rng.normal(size=(
                n_blocks + 1, 1 if latent else 2, block, wide)), "float32")
                for _ in ("k" if latent else "kv")]
            q = jnp.asarray(rng.normal(size=(
                4, pools[0].shape[1], 4, wide)) * wide ** -0.5, "float32")
            tables = jnp.asarray((rng.permutation(n_blocks) + 1).reshape(
                4, width).astype(np.int32))
            keywords = {"value_width": 128} if latent else {}
            # two wrappers, so that each is traced for itself
            full, loops = (jax.jit(functools.partial(attend, **keywords))
                           for _ in range(2))
            made[form, windowed] = (
                full, loops, (q, pools[0], None if latent else pools[1],
                              tables))
        return made[form, windowed]

    return walk


@pytest.mark.parametrize("form", ["latent", "two-pool"])
@pytest.mark.parametrize("case", list(FULL))
def test_a_full_steps_straight_line_and_one_wait_give_what_the_loops_give(
        walks, monkeypatch, case, form):
    """BIT-EQUAL: the order of the products and of the running softmax is
    the loops', and the one wait against a whole half of the buffer is held
    by the modelled semaphore (a byte short and the contraction would meet
    the NaN the scratch starts as, or the step before's rows)."""
    latent = form == "latent"
    span = paged_decode.step_blocks(latent) * 4
    lengths, starts = FULL[case](span)
    lengths = np.array(lengths + [0] * (4 - len(lengths)), np.int32)
    if starts is not None:
        starts = np.array(starts + [0] * (4 - len(starts)), np.int32)
    full, loops, (q, pool_k, pool_v, tables) = walks(form, starts is not None)
    args = (q, pool_k, pool_v, tables, jnp.asarray(lengths)) + (
        () if starts is None else (jnp.asarray(starts),))
    assert paged_decode.full_steps(lengths, 4, starts, latent).any()
    got = np.asarray(full(*args))
    with monkeypatch.context() as patch:
        patch.setattr(paged_decode, "full_steps",
                      lambda lengths, *_: lengths * 0)
        want = np.asarray(loops(*args))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    plain = _plain_latent(
        q, pool_k, np.asarray(tables), lengths, 128, starts) if latent \
        else _plain(q, pool_k, pool_v, np.asarray(tables), lengths, starts)
    np.testing.assert_allclose(got, plain, atol=2e-5, rtol=2e-5)


def test_full_steps_is_the_kernels_branch_on_host_and_device(monkeypatch):
    """Every step but the last of those that lie whole under the length,
    from the step that holds a lane's first position; and the kernel asks
    this function, once a lane, with the lane's own length and start."""
    span = STEP * 16
    assert [int(paged_decode.full_steps(n, 16)) for n in (
        0, 1, span, 2 * span - 1, 2 * span, 3 * span + 1)] == [
            0, 0, 0, 0, 1, 2]
    np.testing.assert_array_equal(
        paged_decode.full_steps(jnp.array([0, 2 * span, 5 * span + 7]), 16),
        [0, 1, 4])
    np.testing.assert_array_equal(
        paged_decode.full_steps(
            np.array([5 * span + 7, 5 * span + 7, 5 * span, 3]), 16,
            np.array([span - 1, span, 4 * span + 9, 0])), [4, 3, 0, 0])
    # no more than the steps the kernel takes, whatever the lane holds
    lengths = np.arange(0, 6 * span, 37)
    for starts in (None, lengths // 3):
        first = 0 if starts is None else starts // span
        taken = paged_decode.steps_read(lengths, 16) - first
        full = paged_decode.full_steps(lengths, 16, starts)
        assert (full >= 0).all() and (full <= np.maximum(taken - 1, 0)).all()
    asked = []
    real = paged_decode.full_steps
    monkeypatch.setattr(
        paged_decode, "full_steps",
        lambda *args, **kw: asked.append(args) or real(*args, **kw))
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(9, 1, 4, 32)), "float32")
    q = jnp.asarray(rng.normal(size=(2, 1, 2, 32)), "float32")
    tables = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4) + 1)
    for starts in ((), (jnp.array([2, 0]),)):
        asked.clear()
        paged_decode.paged_decode_attention(
            q, pool, pool, tables, jnp.array([16, 3]), *starts)
        (length, block, start, latent), = asked
        assert block == 4 and (start is None) == (not starts) and not latent
        assert isinstance(length, jax.core.Tracer)
    # a latent pool's step is twice as long, for the host as for the kernel
    assert paged_decode.step_blocks(latent=True) == 2 * STEP
    assert int(paged_decode.full_steps(4 * span, 16, latent=True)) == 1
    assert int(paged_decode.steps_read(4 * span + 1, 16, latent=True)) == 3
    asked.clear()
    paged_decode.paged_decode_attention(
        q, pool, None, tables, jnp.array([16, 3]), value_width=32)
    assert asked[0][3] is True


def test_without_first_positions_the_reason_cells_tick_lowers_as_before():
    """``starts`` is optional and ``sambay.py`` passes none: its decode
    tick has to lower to the module it lowered to before the argument
    existed.  The digest is of the tick's lowered text at
    ``tests/test_lm_family.py``'s tiny SambaY configuration (jax 0.9.0, the
    one installation): the interpreted kernel is traced into that text, so
    a change to its body shows, as does one to the rest of the tick.  Taken
    anew in PR 38, whose full-step path changed the kernel's body for every
    caller (420,231 characters before it)."""
    import hashlib

    cfg = sambay.SambaYConfig(
        vocab_size=97, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, max_seq=64, window=8, d_inner=128, d_state=4,
        d_conv=4, dt_rank=4, dtype="float32")
    n, block = 2, 4
    programs = cfg.family(cfg, block)
    params = jax.eval_shape(
        lambda: cfg.family.init_params(jax.random.PRNGKey(0), cfg))
    kv = KvBlockPool(cfg, 8, block, lanes=n)
    tick = programs.make_tick(n)
    text = tick.func.lower(
        params, jnp.zeros((n,), jnp.int32), kv.pools["k"], kv.pools["v"],
        kv.lane_state, jnp.zeros((n, 16), jnp.int32),
        jnp.zeros((n,), jnp.int32), jnp.ones((n,), bool),
        jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.int32),
        jnp.zeros((n, 2), jnp.uint32), **tick.keywords).as_text()
    assert len(text) == 645403
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "6c6df9f18776fa170393a19eb99ab242c190f2610d8250bbcb15a2f644253dbf")


def test_steps_read_is_the_kernels_trip_count_on_host_and_device():
    span = STEP * 16
    assert [int(paged_decode.steps_read(n, 16))
            for n in (0, 1, span, span + 1)] == [0, 1, 1, 2]
    np.testing.assert_array_equal(
        paged_decode.steps_read(jnp.array([0, span - 1, 3 * span]), 16),
        [0, 1, 3])


@pytest.mark.parametrize("backend,block,wide,dtype,whole", [
    ("cpu", 4, 32, "float32", True),        # interpreted: any shape
    ("tpu", 4, 32, "float32", False),
    ("tpu", 16, 128, "bfloat16", True),     # the reason cell's pool
    ("tpu", 8, 128, "bfloat16", False),     # half a bfloat16 tile
    ("tpu", 8, 128, "float32", True),
    ("tpu", 16, 64, "bfloat16", False),
])
def test_the_kernel_takes_a_pool_whose_blocks_are_whole_tiles(
        monkeypatch, backend, block, wide, dtype, whole):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    pool = jax.ShapeDtypeStruct((9, 2, block, wide), jnp.dtype(dtype))
    assert paged_decode.reads_in_place(pool) is whole


# -- differential attention's halves ------------------------------------------

def _parent_diff_attention(q, kk, vv, valid, mixer, l0, cfg):
    """``sambay.diff_attention`` as PR 31 had it, in one piece."""
    b, t = q.shape[:2]
    hd = cfg.head_dim
    g = cfg.n_kv_heads // 2
    r = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, t, g, r, 2, hd)
    zeros = jnp.zeros_like(qg[..., 0, :])
    qg = jnp.stack([jnp.concatenate([qg[..., 0, :], zeros], axis=-1),
                    jnp.concatenate([zeros, qg[..., 1, :]], axis=-1)],
                   axis=-2)
    s = jnp.einsum("btgrme,bgse->bgrmts", qg, kk,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    s = jnp.where(valid[:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bgrmts,bgse->btgrme", p.astype(vv.dtype), vv,
                   preferred_element_type=jnp.float32)
    lam = (jnp.exp(jnp.sum(mixer["lq1"] * mixer["lk1"]))
           - jnp.exp(jnp.sum(mixer["lq2"] * mixer["lk2"])) + l0)
    diff = a[..., 0, :] - lam * a[..., 1, :]
    var = jnp.mean(diff * diff, axis=-1, keepdims=True)
    out = diff * lax.rsqrt(var + cfg.norm_eps) \
        * mixer["subln"].astype(jnp.float32) * (1.0 - l0)
    return out.reshape(b, t, cfg.n_heads * hd).astype(q.dtype)


def _diff_case(n_heads, t):
    cfg = sambay.SambaYConfig(
        vocab_size=64, d_model=64, n_layers=4, n_heads=n_heads, n_kv_heads=4,
        head_dim=16, d_ff=64, d_inner=64, dt_rank=4, dtype="float32")
    keys = jax.random.split(jax.random.PRNGKey(t), 8)
    b, s_len = 3, 24
    pairs, wide = cfg.kv_row
    q = jax.random.normal(keys[0], (b, t, cfg.n_heads, cfg.head_dim),
                          cfg.jdtype)
    kk, vv = (jax.random.normal(key, (b, pairs, s_len, wide), cfg.jdtype)
              for key in keys[1:3])
    valid = jnp.arange(s_len)[None, None, :] <= jnp.array(
        [5, 11, 23])[:, None, None] + jnp.arange(t)[None, :, None] - t + 1
    mixer = {name: 0.1 * jax.random.normal(key, (cfg.head_dim,))
             for name, key in zip(("lq1", "lk1", "lq2", "lk2"), keys[3:])}
    mixer["subln"] = 1 + 0.1 * jax.random.normal(keys[7], (wide,), cfg.jdtype)
    return cfg, q, kk, vv, valid, mixer


@pytest.mark.parametrize("t", [1, 5])
@pytest.mark.parametrize("n_heads", [4, 8])  # one and two query heads a KV head
def test_diff_attentions_halves_make_the_whole_it_was(n_heads, t):
    """The query lay-out and the tail as two functions, around the same
    contractions, give what the one function gave, and so do they around
    weighted sums that something else made, as the kernel's are.  (In
    float32: the CPU has no bfloat16 product into float32 outside a kernel.)"""
    cfg, q, kk, vv, valid, mixer = _diff_case(n_heads, t)
    l0, tol = cfg.lambda_init(3), 1e-5
    want = np.asarray(_parent_diff_attention(
        q, kk, vv, valid, mixer, l0, cfg))
    got = sambay.diff_attention(q, kk, vv, valid, mixer, l0, cfg)
    assert got.dtype == q.dtype and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), want, atol=tol, rtol=tol)
    # the halves around a softmax written here
    qg = sambay._pair_queries(q, cfg)
    assert qg.shape == q.shape[:2] + (2, n_heads // 4, 2, 32)
    assert qg.dtype == q.dtype
    s = np.einsum("btgrme,bgse->bgrmts", np.asarray(qg, np.float32),
                  np.asarray(kk, np.float32))
    s = np.where(np.asarray(valid)[:, None, None, None], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    a = np.einsum("bgrmts,bgse->btgrme", p / p.sum(axis=-1, keepdims=True),
                  np.asarray(vv, np.float32))
    out = sambay._diff_out(jnp.asarray(a), mixer, l0, cfg)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), want, atol=tol, rtol=tol)


def test_a_decode_tick_gathers_where_the_kernel_cannot_take_the_pool(
        monkeypatch):
    """Which reader a tick takes follows from the pool it is given: with
    blocks that are no whole tiles on a chip it is the gather of PR 31,
    and both give the same logits and leave the same cache."""
    cfg = sambay.SambaYConfig(
        vocab_size=61, d_model=32, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=64, max_seq=32, window=8, d_inner=64, d_state=4,
        dt_rank=4, dtype="float32")
    block, n = 4, 3
    params = sambay.init_params(jax.random.PRNGKey(1), cfg)
    pool = KvBlockPool(cfg, n_blocks=24, block_size=block, lanes=n)
    rng = np.random.default_rng(2)
    pool_k, pool_v = (jnp.asarray(rng.normal(size=p[0].shape), p[0].dtype)
                      for p in (pool.pools["k"], pool.pools["v"]))
    state = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape) * 0.1, a.dtype),
        pool.lane_state)
    tables = jnp.asarray(
        (rng.permutation(24) + 1).reshape(n, 8).astype(np.int32))
    args = (params, jnp.array([5, 9, 2]), [pool_k], [pool_v], state, tables,
            jnp.array([13, 0, 31]), jnp.array([True, False, True]), cfg, block)
    seen = []
    monkeypatch.setattr(
        sambay, "paged_decode_attention",
        lambda *a, **kw: seen.append(a) or
        paged_decode.paged_decode_attention(*a, **kw))
    in_place = sambay.decode_step(*args)
    assert len(seen) == cfg.kinds.count("full") + cfg.kinds.count("cross")
    np.testing.assert_array_equal(np.asarray(seen[0][4]), [14, 0, 32])
    monkeypatch.setattr(sambay, "reads_in_place", lambda pool: False)
    gathered = sambay.decode_step(*args)
    assert len(seen) == 2
    live = np.array([0, 2])
    np.testing.assert_allclose(np.asarray(in_place[0])[live],
                               np.asarray(gathered[0])[live],
                               atol=2e-4, rtol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(in_place[1]),
                    jax.tree_util.tree_leaves(gathered[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)
