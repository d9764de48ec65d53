"""``transformer.paged_attention``: the grouped contraction against a plain
float32 reference, at every table width it chooses among, and the shape of
the decode tick it leaves behind where the tick gathers (no repeat of the
gathered cache to every query head, no float32 copy of it) in the traced
program; and the chat cell's tick and chunk as the v5e's compiler leaves
them: the tick reads the pool in place through one kernel a layer, the chunk
writes whole blocks, neither copies a layer's pool."""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from client_tpu.serve.lm import KvBlockPool
from client_tpu.serve.lm.policy import attention_width_index, attention_widths
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


def _paged_case(lengths, t, n_rep, dtype, seed, unused=KvBlockPool.TRASH):
    """Random q, contiguous K/V, and the same K/V scattered over a pool by a
    shuffled table whose unused columns point at block ``unused``."""
    rng = np.random.default_rng(seed)
    cfg = _cfg(n_rep, dtype)
    s_len = WIDTH * BLOCK
    b = len(lengths)
    lengths = np.maximum(np.array(lengths), t)
    q = rng.standard_normal((b, t, cfg.n_heads, HEAD_DIM), np.float32)
    k = rng.standard_normal((b, s_len, N_KV, HEAD_DIM), np.float32)
    v = rng.standard_normal((b, s_len, N_KV, HEAD_DIM), np.float32)
    n_blocks = b * WIDTH
    # the trash block holds what padding wrote there: finite and large, so a
    # key that slips past the mask shows
    pool_k = np.full((n_blocks + 1, N_KV, BLOCK, HEAD_DIM), 50.0, np.float32)
    pool_v = np.full((n_blocks + 1, N_KV, BLOCK, HEAD_DIM), -50.0, np.float32)
    tables = np.full((b, WIDTH), unused, np.int32)
    free = rng.permutation(np.arange(1, n_blocks + 1))
    for lane, length in enumerate(lengths):
        used = -(-int(length) // BLOCK)
        tables[lane, :used], free = free[:used], free[used:]
        for col in range(used):
            rows = slice(col * BLOCK, (col + 1) * BLOCK)
            pool_k[tables[lane, col]] = k[lane, rows].swapaxes(0, 1)
            pool_v[tables[lane, col]] = v[lane, rows].swapaxes(0, 1)
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
        LENGTHS[b], t, n_rep, dtype, seed=100 * b + 10 * t + n_rep)
    out = tfm.paged_attention(q, pool_k, pool_v, tables, pos, cfg, BLOCK)
    assert out.shape == q.shape and out.dtype == q.dtype
    want = _reference(q, k, v, pos, n_rep)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), want, rtol=tol, atol=tol)


# -- the width chosen on the device -------------------------------------------

WIDTHS = attention_widths(WIDTH)    # 2, 4 .. 16 columns: 8, 16 .. 64 positions
# the longest lane's last position on each side of every width's edge, by the
# index of the width it has to take: the last position a width holds and the
# first it does not (the table's own last position has no other side)
EDGES = [(k, w * BLOCK - 1) for k, w in enumerate(WIDTHS)] + [
    (k + 1, w * BLOCK) for k, w in enumerate(WIDTHS[:-1])]


@functools.lru_cache(maxsize=None)
def _jitted_attention(n_rep, dtype):
    """One executable a (shape, type): the width is data, not shape."""
    return jax.jit(functools.partial(
        tfm.paged_attention, cfg=_cfg(n_rep, dtype), block_size=BLOCK))


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("index,max_pos,t", [
    (index, max_pos, t) for index, max_pos in sorted(EDGES)
    for t in (1, 5, 2 * HEAD_DIM) if max_pos >= t - 1])  # t rows end there
def test_paged_attention_reads_the_width_its_longest_position_reaches(
        index, max_pos, t, dtype, tol):
    """Three lanes, the longest ending at ``max_pos``: the rule picks width
    ``index`` of eight, the answer is the whole table's, and no column past
    that width is read.  Those columns point at a block of NaNs, which the
    mask alone does not keep out of the weighted sum (0 x NaN).  The shorter
    lanes have whole groups of columns with no key to see."""
    assert len(WIDTHS) == 8
    assert attention_width_index(max_pos, WIDTH, BLOCK) == index
    longest = max_pos + 1
    cfg, q, k, v, pool_k, pool_v, tables, pos = _paged_case(
        (longest, max(longest // 2, 1), 1), t, 4, dtype, seed=max_pos + t)
    assert int(pos.max()) == max_pos
    poison = pool_k.shape[0]
    nans = jnp.full((1,) + pool_k.shape[1:], jnp.nan, pool_k.dtype)
    pool_k = jnp.concatenate([pool_k, nans])
    pool_v = jnp.concatenate([pool_v, nans])
    tables = tables.at[:, WIDTHS[index]:].set(poison)
    out = _jitted_attention(4, dtype)(q, pool_k, pool_v, tables, pos)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), _reference(q, k, v, pos, 4),
        rtol=tol, atol=tol)
    if index + 1 < len(WIDTHS):
        # one pass over the whole table on the same arguments does read them
        _, _, whole = tfm._attend_columns(
            q, pool_k, pool_v, tables, pos, 0, cfg, BLOCK)
        assert np.isnan(np.asarray(whole)).any()


@pytest.mark.parametrize("width,group", [(1, 0), (5, 1), (13, 2), (WIDTH, 2)])
def test_paged_attention_loops_only_where_the_table_has_widths(width, group):
    """A table of one width is one pass, no loop and no branch in it at all;
    a wider one is gathered a group of columns at a time, inside a loop."""
    cfg = _cfg(4, "bfloat16")
    sds = jax.ShapeDtypeStruct
    pool = sds((9, N_KV, BLOCK, HEAD_DIM), cfg.jdtype)
    jaxpr = jax.make_jaxpr(functools.partial(
        tfm.paged_attention, cfg=cfg, block_size=BLOCK))(
            sds((2, 1, cfg.n_heads, HEAD_DIM), cfg.jdtype), pool, pool,
            sds((2, width), jnp.int32), sds((2, 1), jnp.int32))
    outer = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert outer.count("while") == (1 if group else 0)
    assert "cond" not in outer
    gathered = [aval.shape for name, aval in _intermediates(jaxpr.jaxpr)
                if name == "gather" and aval.shape[-1] == HEAD_DIM]
    assert gathered == 2 * [(2, group or width, N_KV, BLOCK, HEAD_DIM)]


# -- the decode tick's program ------------------------------------------------

def _decode_tick_args(cfg, n, table_width, block_size, n_blocks):
    """Shapes of one ``paged_decode_tick`` call (no arrays: nothing runs)."""
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    pool = [sds((n_blocks + 1, cfg.n_kv_heads, block_size, cfg.head_dim),
                cfg.jdtype) for _ in range(cfg.n_layers)]
    return (params, sds((n,), jnp.int32), pool, pool,
            sds((n, table_width), jnp.int32), sds((n,), jnp.int32),
            sds((n,), jnp.bool_), sds((n,), jnp.float32),
            sds((n,), jnp.int32), sds((n, 2), jnp.uint32))


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


def _assert_cache_kept_at_its_width(values, cfg, n, group, table):
    """``values``: (label, dtype name, dims) of everything a tick makes.
    The gather of ``n`` lanes' ``group`` positions (one group of the table's
    columns) is among them.  Of what spans a group's positions or the whole
    ``table``'s, nothing has a lane's keys once for every QUERY head, nothing
    of float32 is as large as the gathered blocks, and nothing gathers the
    whole table."""
    lanes_keys = n * cfg.n_kv_heads * cfg.head_dim      # at one position
    n_rep = cfg.n_heads // cfg.n_kv_heads
    values = [(label, dtype, dims, int(np.prod(dims)))
              for label, dtype, dims in values]
    assert any(size == group * lanes_keys and group in dims
               for _, _, dims, size in values)
    for label, dtype, dims, size in values:
        for positions in {group, table} & set(dims):
            assert size < positions * lanes_keys * n_rep, label
            assert not (dtype == "float32"
                        and size >= positions * lanes_keys), label
        assert not (table in dims and size >= table * lanes_keys), label


def test_decode_tick_never_repeats_or_widens_the_gathered_cache(monkeypatch):
    """At a GQA configuration (4 query heads a KV head, bf16) whose pool
    the kernel cannot take as it lies (on a chip: a head of 8), nothing in
    the tick has a lane's keys once for every QUERY head, nothing of
    float32 is as large as the gathered blocks, and the gather is a group
    of columns wide (an eighth of the table), never the table."""
    monkeypatch.setattr(tfm, "reads_in_place", lambda pool: False)
    n, width, block = 4, 128, 8
    cfg = _cfg(4, "bfloat16", n_kv=2, hd=8, vocab_size=64, d_ff=64,
               n_layers=2, max_seq=width * block)
    args = _decode_tick_args(cfg, n, width, block, n_blocks=64)
    jaxpr = jax.make_jaxpr(functools.partial(
        tfm.paged_decode_tick, cfg=cfg, n=n, block_size=block))(*args)
    _assert_cache_kept_at_its_width(
        ((f"{name}: {aval}", str(aval.dtype), aval.shape)
         for name, aval in _intermediates(jaxpr.jaxpr)),
        cfg, n, attention_widths(width)[0] * block, width * block)


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


def _compiled_for_v5e(program, args, donate=(2, 3), **static):
    """The text of ``program`` jitted with its pools (and a family's fixed
    state) donated and compiled for the described chip.  A module compiled
    for a described chip cannot be read back from the persistent cache: it
    is kept out."""
    from jax.experimental.compilation_cache import compilation_cache

    jitted = jax.jit(functools.partial(program, **static),
                     donate_argnums=donate)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jitted.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _chat_cell(n_layers, width=128, block=16):
    """Mistral-7B's widths but for the vocabulary, a table of 2,048
    positions over a pool of 2,048 blocks."""
    return tfm.TransformerConfig(
        vocab_size=4096, d_model=4096, n_layers=n_layers, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq=width * block, rope_theta=1e6,
        dtype="bfloat16")


def _assert_pools_stay_in_place(text, cfg, block, n_blocks=2048):
    """Every pool is an output that aliases its donated argument, and
    nothing copies or transposes one (67 MB), in place or through another
    memory space."""
    pool = rf"bf16\[{n_blocks + 1},{cfg.n_kv_heads},{block},{cfg.head_dim}\]"
    copied = re.findall(
        rf"= \(?{pool}[^=]* (?:copy|copy-start|slice-start|transpose)\(.*",
        text)
    assert not copied, copied[:2]
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    assert aliases.group(1).count("-alias") == 2 * cfg.n_layers


@pytest.mark.parametrize("n_layers", [1, 16])
def test_decode_tick_compiled_for_v5e_reads_the_pool_in_place(
        one_chip, monkeypatch, n_layers):
    """The chat cell's tick (16 lanes; one layer, and the cell's sixteen) as
    the v5e's compiler leaves it: a call of the paged decode kernel a layer
    and no loop over the table's columns (PR 30's, which gathered 256
    positions a lane a trip); nothing has a lane's gathered blocks, in
    either order of heads and positions; the new rows go in as a scatter of
    rows of 128, which leaves the pool as the kernel reads it."""
    n, width, block = 16, 128, 16
    cfg = _chat_cell(n_layers, width, block)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        _decode_tick_args(cfg, n, width, block, n_blocks=2048))
    # the step asks the backend whether the kernel can take the pool as it
    # lies: here it is compiled for the chip that is described
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_for_v5e(tfm.paged_decode_tick, args, cfg=cfg, n=n,
                             block_size=block)
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) \
        == n_layers
    assert not re.findall(r" while\(", text)
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\b(?:f32|bf16)\[([\d,]+)\]", text)}
    group = attention_widths(width)[0]
    gathered = {s for s in shapes if s[0] == n and set(s[1:]) in (
        {kv, group * block, hd}, {kv, group, block, hd},
        {kv, width * block, hd}, {kv, width, block, hd})}
    assert not gathered, gathered
    _assert_pools_stay_in_place(text, cfg, block)


@pytest.mark.parametrize("chunk", [128, 512])
def test_prefill_chunk_compiled_for_v5e_writes_whole_blocks(one_chip, chunk):
    """The chat cell's chunk (one layer; its narrowest and its widest
    bucket) as the v5e's compiler leaves it: the new K and V go in as one
    scatter a pool whose window is a whole block, ``chunk / 16`` of them,
    and not ``chunk x 8`` rows of 128; the read is the loop over the
    table's column groups."""
    width, block = 128, 16
    cfg = _chat_cell(1, width, block)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params, _, pool_k, pool_v, *_ = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        _decode_tick_args(cfg, 1, width, block, n_blocks=2048))
    args = (params, sds((1, chunk), jnp.int32), pool_k, pool_v,
            sds((width,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
            sds((2,), jnp.uint32), sds((), jnp.float32), sds((), jnp.int32))
    text = _compiled_for_v5e(tfm.paged_prefill_chunk, args, cfg=cfg,
                             block_size=block)
    scatters = re.findall(r" scatter\(.*update_window_dims=\{([\d,]*)\}", text)
    assert scatters == 2 * ["1,2,3"]
    blocks = rf"bf16\[{chunk // block},{cfg.n_kv_heads},{block},{cfg.head_dim}\]"
    assert re.search(blocks, text)
    assert len(re.findall(r" while\(", text)) == 1
    _assert_pools_stay_in_place(text, cfg, block)


def test_sambay_decode_tick_compiled_for_v5e_reads_the_pool_in_place(
        one_chip, monkeypatch):
    """The reason cell's tick (Phi-4-mini-flash-reasoning's widths and 32
    layers but for the vocabulary, 32 lanes, a table of 192 columns) as the
    v5e's compiler leaves it: the full layer and the seven cross layers
    are eight calls of the one kernel, which take the pool in the layout
    the scatter leaves it (a copy would be 0.5 GB a tick); nothing has a
    lane's whole logical cache or the gathered blocks (PR 31 had both, 251
    MB each, for keys and for values), and the pools and every lane's
    state are still outputs that alias their donated arguments."""
    from client_tpu.serve.models import sambay

    n, width, block, n_blocks = 32, 192, 16, 6144
    cfg = sambay.SambaYConfig(vocab_size=4096, max_seq=width * block)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    _, blocks, lane_spec = cfg.state_spec
    in_block = blocks["k"]
    pool = shaped((n_blocks + 1,) + tuple(
        block if d is None else d for d in in_block), cfg.jdtype)
    state = {name: [shaped((n,) + tuple(shape), dtype)
                    for shape, dtype in layers]
             for name, layers in lane_spec.items()}
    params = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(functools.partial(sambay.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), n))
    args = (params, shaped((n,), "int32"), [pool], [pool], state,
            shaped((n, width), "int32"), shaped((n,), "int32"),
            shaped((n,), "bool"), shaped((n,), "float32"),
            shaped((n,), "int32"), shaped(keys.shape, keys.dtype))
    # the step asks the backend whether to compile the kernel or interpret
    # it: here it is compiled for the chip that is described
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_for_v5e(sambay.sambay_decode_tick, args,
                             donate=(2, 3, 4), cfg=cfg, n=n, block_size=block)
    readers = cfg.kinds.count(sambay.FULL) + cfg.kinds.count(sambay.CROSS)
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) \
        == readers == 8
    pairs, wide = cfg.kv_row
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\b(?:f32|bf16)\[([\d,]+)\]", text)}
    gathered = {s for s in shapes
                if s[-3:] == (pairs, width * block, wide)
                or s == (n * width, pairs, block, wide)
                or s == (n, pairs, width, block, wide)
                or s == (n, width, pairs, block, wide)}
    assert not gathered, gathered
    pool_text = rf"bf16\[{n_blocks + 1},{pairs},{block},{wide}\]"
    copied = re.findall(
        rf"= \(?{pool_text}[^=]* (?:copy|copy-start|slice-start)\(.*", text)
    assert not copied, copied[:2]
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    assert aliases.group(1).count("-alias") == 2 + sum(
        len(layers) for layers in state.values())


def test_cohere2moe_decode_tick_compiled_for_v5e_keeps_its_kernels(
        one_chip, monkeypatch):
    """The rag cell's tick (Command A+'s widths, one period of four layers,
    16 of 128 experts held, 32 lanes, a table of 560 columns; a small
    vocabulary) as the v5e's compiler leaves it: four calls of the paged
    decode kernel, three of them with first positions, and eight of the
    grouped expert product (gate-and-up and down a layer), which take the
    pool and the expert stacks as they lie; nothing has a lane's gathered
    table, and the pools are outputs that alias their donated arguments."""
    from client_tpu.serve.models import cohere2moe

    n, width, block, n_blocks = 32, 560, 16, 12288
    cfg = cohere2moe.Cohere2MoeConfig(vocab_size=4096, max_seq=width * block)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    layers, blocks, _ = cfg.state_spec
    in_block = blocks["k"]
    pools = [shaped((n_blocks + 1,) + tuple(
        block if d is None else d for d in in_block), cfg.jdtype)
        for _ in range(layers)]
    params = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(functools.partial(cohere2moe.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), n))
    args = (params, shaped((n,), "int32"), pools, pools,
            shaped((n, width), "int32"), shaped((n,), "int32"),
            shaped((n,), "bool"), shaped((n,), "float32"),
            shaped((n,), "int32"), shaped(keys.shape, keys.dtype))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_for_v5e(cohere2moe.cohere2moe_decode_tick, args,
                             cfg=cfg, n=n, block_size=block)
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) \
        == layers + 2 * layers == 12
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\b(?:f32|bf16)\[([\d,]+)\]", text)}
    gathered = {s for s in shapes
                if s[-3:] == (kv, width * block, hd)
                or s == (n * width, kv, block, hd)
                or s == (n, width, kv, block, hd)}
    assert not gathered, gathered
    pool_text = rf"bf16\[{n_blocks + 1},{kv},{block},{hd}\]"
    copied = re.findall(
        rf"= \(?{pool_text}[^=]* (?:copy|copy-start|slice-start)\(.*", text)
    assert not copied, copied[:2]
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    assert aliases.group(1).count("-alias") == 2 * layers


def _axk1_cell_args(one_chip, width=1072, block=16, n_blocks=34304):
    """The longdoc cell's shapes (A.X-K1's widths, the dense layer and ONE
    expert layer of its four, 12 of 192 experts held, a table of 1,072
    columns over a pool of 34,304 blocks; a small vocabulary): (cfg, params,
    the latent pools, a shaper)."""
    from client_tpu.serve.models import axk1

    cfg = axk1.AxK1Config(vocab_size=4096, n_layers=2, max_seq=width * block)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    layers, blocks, _ = cfg.state_spec
    assert list(blocks) == ["latent"]
    pools = [shaped((n_blocks + 1,) + tuple(
        block if d is None else d for d in blocks["latent"]), cfg.jdtype)
        for _ in range(layers)]
    params = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(functools.partial(axk1.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)))
    return cfg, params, pools, shaped


def _assert_latent_pool_stays_in_place(text, cfg, n_blocks, block):
    """No copy of a latent pool, no padded copy of an expert stack (the
    grouped product pads a K its tile does not divide: 7168 under 4096),
    and the pools are outputs that alias their donated arguments."""
    pool_text = rf"bf16\[{n_blocks + 1},1,{block},{cfg.row_width}\]"
    copied = re.findall(
        rf"= \(?{pool_text}[^=]* (?:copy|copy-start|slice-start)\(.*", text)
    assert not copied, copied[:2]
    assert not re.findall(r"= bf16\[12,\d+,\d+\]\S* (?:pad|copy)\(", text)
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    assert aliases.group(1).count("-alias") == cfg.n_layers


def test_axk1_decode_tick_compiled_for_v5e_reads_the_latent_pool_in_place(
        one_chip, monkeypatch):
    """The longdoc cell's tick (32 lanes) as the v5e's compiler leaves it:
    a call of the paged decode kernel a layer over ONE pool (a table of
    32 x 1,072 columns prefetched as scalars, 137 KB), two of the grouped
    expert product in the expert layer, nothing with a lane's gathered
    table, no per-head key or value of the cache's positions."""
    from client_tpu.serve.models import axk1

    n, width, block, n_blocks = 32, 1072, 16, 34304
    cfg, params, pools, shaped = _axk1_cell_args(one_chip)
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), n))
    args = (params, shaped((n,), "int32"), pools,
            shaped((n, width), "int32"), shaped((n,), "int32"),
            shaped((n,), "bool"), shaped((n,), "float32"),
            shaped((n,), "int32"), shaped(keys.shape, keys.dtype))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_for_v5e(axk1.axk1_decode_tick, args, donate=(2,),
                             cfg=cfg, n=n, block_size=block)
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) \
        == cfg.n_layers + 2
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\b(?:f32|bf16)\[([\d,]+)\]", text)}
    gathered = {s for s in shapes
                if width * block in s or s[:2] == (n, width)
                or s[:1] == (n * width,)}
    assert not gathered, gathered
    _assert_latent_pool_stays_in_place(text, cfg, n_blocks, block)


@pytest.mark.parametrize("chunk", [128, 512])
def test_axk1_prefill_chunk_compiled_for_v5e_attends_in_the_kernel(
        one_chip, monkeypatch, chunk):
    """The longdoc cell's chunk, its narrowest and its widest bucket: a call
    of ``ops/latent_prefill`` a layer where the expanded form's loop over
    groups of table columns was (the one ``while`` left is the grouped
    product's search for its group edges), the expert layer's two grouped
    products, no rebuilt keys or values of a group (512 positions x 64
    heads) and no scores of one outside the kernel, and the pools stay in
    place."""
    from client_tpu.ops import latent_prefill
    from client_tpu.serve.models import axk1

    width, block, n_blocks = 1072, 16, 34304
    cfg, params, pools, shaped = _axk1_cell_args(one_chip)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    args = (params, shaped((1, chunk), "int32"), pools,
            shaped((width,), "int32"), shaped((), "int32"),
            shaped((), "int32"), shaped(key.shape, key.dtype),
            shaped((), "float32"), shaped((), "int32"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_for_v5e(axk1.axk1_prefill_chunk, args, donate=(2,),
                             cfg=cfg, block_size=block)
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) \
        == cfg.n_layers + 2
    loops = [line for line in text.splitlines() if " while(" in line]
    assert all("searchsorted" in line for line in loops), loops[:1]
    span = latent_prefill.group_span(block)
    typed = {(kind, tuple(int(d) for d in dims.split(",")))
             for kind, dims in re.findall(r"\b(f32|bf16)\[([\d,]+)\]", text)}
    shapes = {s for _, s in typed}
    heads = cfg.n_heads
    assert not {s for kind, s in typed
                if kind == "f32" and sorted(s) == sorted((heads, chunk, span))}
    if chunk != span:   # (else a group's rebuilt rows have the queries' shape)
        weights = {(heads, cfg.nope_dim, cfg.kv_lora_rank),
                   (heads, cfg.kv_lora_rank, cfg.v_dim)}
        assert not {s for s in shapes
                    if heads in s and span in s and s not in weights}
    assert not {s for s in shapes if width * block in s}
    _assert_latent_pool_stays_in_place(text, cfg, n_blocks, block)


def test_sdar_block_tick_compiled_for_v5e_reads_the_pool_in_place(
        one_chip, monkeypatch):
    """The fixedgen cell's tick (SDAR-30B-A3B-Chat's widths, two of its
    seven layers, all 128 experts held, 32 lanes of a block of four, a
    table of 160 columns over a pool of 5,120 blocks; a small vocabulary)
    as the v5e's compiler leaves it: a call of the paged decode kernel a
    layer, which takes the block's 4 x 8 = 32 query rows a KV head under
    one lane length, and two of the grouped expert product; nothing has a
    lane's gathered table, the pools are outputs that alias their donated
    arguments, and the lanes' block states come back in their own shape."""
    from client_tpu.serve.models import sdar

    n, width, block, n_blocks = 32, 160, 16, 5120
    cfg = sdar.SdarConfig(vocab_size=4096, n_layers=2, mask_id=4000,
                          max_seq=width * block)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    layers, blocks, _ = cfg.state_spec
    pools = [shaped((n_blocks + 1,) + tuple(
        block if d is None else d for d in blocks["k"]), cfg.jdtype)
        for _ in range(layers)]
    params = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, a.dtype),
        jax.eval_shape(functools.partial(sdar.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), n))
    args = (params, shaped((n, 3, cfg.block_length), "int32"), pools, pools,
            shaped((n, width), "int32"), shaped((n,), "int32"),
            shaped((n,), "bool"), shaped((n,), "float32"),
            shaped((n,), "int32"), shaped(keys.shape, keys.dtype))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_for_v5e(sdar.sdar_block_tick, args, cfg=cfg, n=n,
                             block_size=block)
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) \
        == 3 * layers
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    rows = cfg.block_length * cfg.n_heads // kv
    assert re.search(rf"f32\[{n},{kv},{rows},{hd}\]", text)  # the kernel's out
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\b(?:f32|bf16)\[([\d,]+)\]", text)}
    gathered = {s for s in shapes
                if s[-3:] == (kv, width * block, hd)
                or s == (n * width, kv, block, hd)
                or s == (n, width, kv, block, hd)}
    assert not gathered, gathered
    # A pool of this cell is 84 MB a layer, which fits the v5e's 128 MiB of
    # VMEM, and the compiler's memory-space assignment takes the VALUES' pool
    # of every layer after the first there for the scatter and the kernel's
    # read, and copies it back (`copy-start(%pool_v_...)`, then one of the
    # scatter's result: 0.57 ms of a tick's 16.3 on the chip, PERF.md
    # section 5).  The keys' pools stay in HBM, and no pool is gathered or
    # sliced.
    pool_text = rf"bf16\[{n_blocks + 1},{kv},{block},{hd}\]"
    copied = re.findall(
        rf"= \(?{pool_text}[^=]* (?:copy|copy-start|slice-start)\((\S+)", text)
    assert all("pool_v" in c or "fusion" in c for c in copied), copied[:2]
    assert len(copied) <= 2 * (layers - 1)
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    assert aliases.group(1).count("-alias") == 2 * layers
