"""``client_tpu.ops.paged_decode``: the kernel (under the TPU interpreter,
which models its copies in flight, their semaphores and a scratch that
starts as NaN) against a plain gather and softmax written here, and ``sambay.diff_attention``'s two shared halves, which carry the
kernel's result into a layer, against the whole function as PR 31 had it.
"""

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


def test_without_first_positions_the_reason_cells_tick_lowers_as_before():
    """``starts`` is optional and ``sambay.py`` passes none: its decode
    tick has to lower to the module it lowered to before the argument
    existed.  The digest is of the tick's lowered text at
    ``tests/test_lm_family.py``'s tiny SambaY configuration, taken on the
    parent commit (PR 32's tree; jax 0.9.0, the one installation): the
    interpreted kernel is traced into that text, so a change to its body
    shows, as does one to the rest of the tick."""
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
    assert len(text) == 420231
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "a0befcd2ced72789bb11bfb09874dd9d0d293c84a56443500a3c10524fc39f61")


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
