"""``transformer.paged_attention``: the grouped contraction against a plain
float32 reference, and the shape of the decode tick it leaves behind (no
repeat of the gathered cache to every query head, no float32 copy of it) in
the traced program and in the module the v5e's compiler makes of it."""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from client_tpu.serve.lm import KvBlockPool
from client_tpu.serve.lm.engine import _decode_tick
from client_tpu.serve.models import transformer as tfm

BLOCK = 4
WIDTH = 16          # table columns: 64 logical positions a lane
HEAD_DIM = 16
N_KV = 2
# lane lengths by batch size: unequal, one of them ending on a block boundary
LENGTHS = {4: (1, 8, 13, 64), 3: (5, 12, 30), 1: (16,), 2: (32, 47)}


def _cfg(n_rep, dtype, n_kv=N_KV, hd=HEAD_DIM, **kw):
    return tfm.TransformerConfig(
        d_model=n_kv * n_rep * hd, n_heads=n_kv * n_rep, n_kv_heads=n_kv,
        dtype=dtype, **kw)


def _paged_case(b, t, n_rep, dtype, seed):
    """Random q, contiguous K/V, and the same K/V scattered over a pool by a
    shuffled table whose unused columns point at the trash block."""
    rng = np.random.default_rng(seed)
    cfg = _cfg(n_rep, dtype)
    s_len = WIDTH * BLOCK
    lengths = np.maximum(np.array(LENGTHS[b]), t)
    q = rng.standard_normal((b, t, cfg.n_heads, HEAD_DIM), np.float32)
    k = rng.standard_normal((b, s_len, N_KV, HEAD_DIM), np.float32)
    v = rng.standard_normal((b, s_len, N_KV, HEAD_DIM), np.float32)
    n_blocks = b * WIDTH
    # the trash block holds what padding wrote there: finite and large, so a
    # key that slips past the mask shows
    pool_k = np.full((n_blocks + 1, BLOCK, N_KV, HEAD_DIM), 50.0, np.float32)
    pool_v = np.full((n_blocks + 1, BLOCK, N_KV, HEAD_DIM), -50.0, np.float32)
    tables = np.full((b, WIDTH), KvBlockPool.TRASH, np.int32)
    free = rng.permutation(np.arange(1, n_blocks + 1))
    for lane, length in enumerate(lengths):
        used = -(-int(length) // BLOCK)
        tables[lane, :used], free = free[:used], free[used:]
        for col in range(used):
            rows = slice(col * BLOCK, (col + 1) * BLOCK)
            pool_k[tables[lane, col]] = k[lane, rows]
            pool_v[tables[lane, col]] = v[lane, rows]
    # the last t positions of each lane ask, as a verify tick's do
    pos = (lengths[:, None] - t + np.arange(t)[None, :]).astype(np.int32)
    cast = lambda a: jnp.asarray(a, dtype)
    return cfg, cast(q), cast(k), cast(v), cast(pool_k), cast(pool_v), \
        jnp.asarray(tables), jnp.asarray(pos)


def _reference(q, k, v, pos, n_rep):
    """Plain float32 attention over contiguous K/V: explicit repeat to every
    query head, HIGHEST precision, keys 0..pos of each query position."""
    q, k, v = (np.asarray(a, np.float32) for a in (q, k, v))
    k, v = np.repeat(k, n_rep, axis=2), np.repeat(v, n_rep, axis=2)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) * q.shape[-1] ** -0.5
    valid = np.arange(k.shape[1])[None, None, :] <= np.asarray(pos)[:, :, None]
    p = jax.nn.softmax(jnp.where(valid[:, None], s, -jnp.inf), axis=-1)
    return np.asarray(jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=hi))


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("b,t", [(4, 1), (3, 5), (1, 16), (2, 32)])
def test_paged_attention_matches_plain_reference(b, t, n_rep, dtype, tol):
    """(2, 32) is a chunk of 2 x head_dim query rows: the per-head side of
    ``paged_attention``'s choice; the others contract a group at a time."""
    cfg, q, k, v, pool_k, pool_v, tables, pos = _paged_case(
        b, t, n_rep, dtype, seed=100 * b + 10 * t + n_rep)
    out = tfm.paged_attention(q, pool_k, pool_v, tables, pos, cfg, BLOCK)
    assert out.shape == q.shape and out.dtype == q.dtype
    want = _reference(q, k, v, pos, n_rep)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), want, rtol=tol, atol=tol)


# -- the decode tick's program ------------------------------------------------

def _decode_tick_args(cfg, n, table_width, block_size, n_blocks):
    """Shapes of one ``_decode_tick`` call (no arrays: nothing runs)."""
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    pool = [sds((n_blocks + 1, block_size, cfg.n_kv_heads, cfg.head_dim),
                cfg.jdtype) for _ in range(cfg.n_layers)]
    return (params, sds((n,), jnp.int32), pool, pool,
            sds((n, table_width), jnp.int32), sds((n,), jnp.int32),
            sds((n,), jnp.float32), sds((n,), jnp.int32),
            sds((n, 2), jnp.uint32))


def _intermediates(jaxpr):
    """Every value an equation of ``jaxpr`` produces, nested jaxprs too."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield eqn.primitive.name, var.aval
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _intermediates(sub)


def _assert_cache_kept_at_its_width(values, cfg, n, positions):
    """``values``: (label, dtype name, elements) of everything a tick makes.
    The gather of ``n`` lanes' ``positions`` is among them; nothing has it
    once for every QUERY head, and nothing of float32 is as large as it."""
    gathered = n * positions * cfg.n_kv_heads * cfg.head_dim
    repeated = gathered * (cfg.n_heads // cfg.n_kv_heads)
    values = list(values)
    assert any(size == gathered for _, _, size in values)
    for label, dtype, size in values:
        assert size < repeated, label
        assert not (dtype == "float32" and size >= gathered), label


def test_decode_tick_never_repeats_or_widens_the_gathered_cache():
    """At a GQA configuration (4 query heads a KV head, bf16) nothing in the
    tick has a lane's keys once for every QUERY head, and nothing of float32
    is as large as the gathered blocks.  Sized so that weights, logits and
    scores are all smaller than either."""
    n, width, block = 4, 16, 8
    cfg = _cfg(4, "bfloat16", n_kv=2, hd=8, vocab_size=64, d_ff=64,
               n_layers=2, max_seq=width * block)
    args = _decode_tick_args(cfg, n, width, block, n_blocks=n * width)
    jaxpr = jax.make_jaxpr(functools.partial(
        _decode_tick, cfg=cfg, n=n, block_size=block))(*args)
    _assert_cache_kept_at_its_width(
        ((f"{name}: {aval}", str(aval.dtype), aval.size)
         for name, aval in _intermediates(jaxpr.jaxpr)),
        cfg, n, width * block)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described (not attached) v5e host: the TPU's compiler is
    installed here and compiles for it.  Described inside the fixture, after
    collection, because one process at a time may load the TPU's library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_decode_tick_compiled_for_v5e_keeps_the_cache_at_its_width(one_chip):
    """The chat cell's tick (Mistral-7B widths but for the vocabulary, 16
    lanes, a table of 2,048 positions; one layer) as the v5e's compiler
    leaves it: no operation with 16 x 2,048 x 32 x 128 elements of either
    width, and no float32 tensor of the gathered blocks' size.  The parent's
    module had two of the first (broadcasts of 256 and 512 MB a layer) and
    two of the second."""
    from jax.experimental.compilation_cache import compilation_cache

    n, width, block = 16, 128, 16
    cfg = tfm.TransformerConfig(
        vocab_size=4096, d_model=4096, n_layers=1, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq=width * block, rope_theta=1e6, dtype="bfloat16")
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        _decode_tick_args(cfg, n, width, block, n_blocks=2048))
    tick = jax.jit(functools.partial(
        _decode_tick, cfg=cfg, n=n, block_size=block), donate_argnums=(2, 3))
    # a module compiled for a described chip cannot be read back from the
    # persistent cache: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = tick.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    names = {"f32": "float32", "bf16": "bfloat16"}
    _assert_cache_kept_at_its_width(
        ((f"{dtype}[{dims}]", names[dtype],
          int(np.prod([int(d) for d in dims.split(",")])))
         for dtype, dims in set(re.findall(r"\b(f32|bf16)\[([\d,]+)\]", text))),
        cfg, n, width * block)
