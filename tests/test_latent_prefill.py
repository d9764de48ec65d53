"""``client_tpu.ops.latent_prefill``: the kernel that attends a prefill
chunk over a paged latent pool (interpreted here, the code the chip runs)
against a plain float32 softmax over keys and values rebuilt from the
latents, written out below: starts at nothing, at a whole group, at whole
blocks that are no whole group (an adopted prefix) and off any block, a last
position on either side of a group's edge, a whole and a short bucket, two
head blocks (so that the copy ahead crosses from one into the next), and a
table whose columns past the chunk's last block point at a poisoned block.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from client_tpu.ops import latent_prefill
from client_tpu.ops.latent_prefill import GROUP_BLOCKS, HEAD_BLOCK

# (dtype, heads, nope, rope, v, latent, row, block, tolerance): the tests'
# tiny shape in float32 (a latent that is no whole tile, two head blocks),
# and the longdoc cell's widths in bfloat16 (one head block)
SHAPES = {
    "float32-tiny": ("float32", 2 * HEAD_BLOCK, 8, 8, 8, 24, 128, 4, 2e-5),
    "bfloat16-cell": ("bfloat16", HEAD_BLOCK, 128, 64, 128, 512, 640, 16,
                      2e-2),
}
WIDTH = 3 * GROUP_BLOCKS + 5   # table columns: three groups and a part
POISON = 0                     # the block that dead columns point at


def _plain(q, pool, table, start, w_uk, w_uv, latent, rope):
    """Every row the chunk may see, gathered; each head's keys and values
    rebuilt; a softmax a query row over rows ``0 .. start + r``."""
    t, heads, _ = q.shape
    block = pool.shape[2]
    n = start + t
    rows = np.concatenate(
        [np.asarray(pool[b, 0], np.float32)
         for b in np.asarray(table)[:-(-n // block)]])[:n]
    c_kv, k_pe = rows[:, :latent], rows[:, latent:latent + rope]
    q, w_uk, w_uv = (np.asarray(a, np.float32) for a in (q, w_uk, w_uv))
    out = np.zeros((t, heads, w_uv.shape[-1]), np.float32)
    for h in range(heads):
        k = np.concatenate([c_kv @ w_uk[h].T, k_pe], axis=-1)
        s = q[:, h] @ k.T
        s[np.arange(n)[None, :] > start + np.arange(t)[:, None]] = -np.inf
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        out[:, h] = p / p.sum(axis=-1, keepdims=True) @ (c_kv @ w_uv[h])
    return out.reshape(t, -1)


@pytest.fixture(scope="module")
def case():
    """(pool, table order, w_uk, w_uv) a shape, made once.  Block 0 is NaN
    throughout; the lane's blocks lie permuted through the rest."""
    made = {}

    def make(shape):
        if shape not in made:
            dtype, heads, nope, rope, v, latent, row, block, _ = SHAPES[shape]
            rng = np.random.default_rng(len(made))
            pool = np.zeros((WIDTH + 1, 1, block, row), np.float32)
            pool[..., :latent + rope] = rng.normal(
                size=pool.shape[:-1] + (latent + rope,))
            pool[POISON] = np.nan
            order = (rng.permutation(WIDTH) + 1).astype(np.int32)
            w_uk = rng.normal(size=(heads, nope, latent)) * latent ** -0.5
            w_uv = rng.normal(size=(heads, latent, v)) * latent ** -0.5
            made[shape] = (jnp.asarray(pool, dtype), order,
                           jnp.asarray(w_uk, dtype), jnp.asarray(w_uv, dtype))
        return made[shape]

    return make


def _run(case, shape, start, t, interpret=None):
    dtype, heads, nope, rope, _, latent, _, block, tol = SHAPES[shape]
    pool, order, w_uk, w_uv = case(shape)
    rng = np.random.default_rng(start * 31 + t)
    q = jnp.asarray(rng.normal(size=(t, heads, nope + rope))
                    * (nope + rope) ** -0.5, dtype)
    table = order.copy()
    table[(start + t - 1) // block + 1:] = POISON          # never read
    out = np.asarray(latent_prefill.latent_prefill_attention(
        q, pool, jnp.asarray(table), jnp.int32(start), w_uk, w_uv,
        interpret=interpret))
    assert out.dtype == np.float32 and out.shape == (t, heads * w_uv.shape[2])
    want = _plain(q, pool, table, start, w_uk, w_uv, latent, rope)
    np.testing.assert_allclose(out, want, atol=tol, rtol=tol)


SPAN = latent_prefill.group_span(4)   # a group of the tiny shape: 128 positions
STARTS = {
    "0": 0,
    "a-whole-group": SPAN,
    "whole-blocks-no-whole-group": SPAN + 4,      # an adopted prefix
    "off-any-block": 2 * SPAN + 5,
    "last-position-ends-a-group": SPAN - 8,
    "last-position-opens-a-group": SPAN - 7,
}


@pytest.mark.parametrize("t", [8, 4])             # a whole and a short bucket
@pytest.mark.parametrize("start", list(STARTS))
def test_kernel_matches_a_plain_softmax_over_rebuilt_keys(case, start, t):
    _run(case, "float32-tiny", STARTS[start], t)


def test_a_short_bucket_that_ends_a_group_reads_no_further(case):
    """Width 4 from ``SPAN - 4``: the last position is the group's last, and
    the next group's columns are poisoned."""
    _run(case, "float32-tiny", SPAN - 4, 4)


def test_one_query_row_as_the_absorbed_forms_test_asks(case):
    _run(case, "float32-tiny", SPAN + 70, 1)


def test_kernel_under_the_tpu_interpreter(case):
    """Copies in flight, their semaphores and a scratch that starts as NaN
    modelled: two groups of which the second is copied in part, two head
    blocks."""
    _run(case, "float32-tiny", SPAN + 4, 8,
         interpret=pltpu.InterpretParams())


def test_kernel_at_the_cells_widths_in_bfloat16(case):
    """The longdoc cell's row of 640, block of 16 and head widths, a bucket
    of 128 rows from an adopted prefix of 33 blocks: the tile-aligned slices
    the chip takes."""
    _run(case, "bfloat16-cell", 33 * 16, 128)


def test_groups_read_is_the_kernels_trip_count_on_host_and_device():
    span = GROUP_BLOCKS * 16
    for max_pos, want in ((0, 1), (span - 1, 1), (span, 2), (16383, 32)):
        assert latent_prefill.groups_read(max_pos, 16) == want
        assert int(jax.jit(latent_prefill.groups_read, static_argnums=1)(
            jnp.int32(max_pos), 16)) == want
    assert latent_prefill.groups_read(np.array([5, span]), 16).tolist() == [
        1, 2]


def test_heads_that_are_no_whole_blocks_are_refused(case):
    pool, order, w_uk, w_uv = case("float32-tiny")
    heads = HEAD_BLOCK + 1
    with pytest.raises(ValueError, match="whole blocks"):
        latent_prefill.latent_prefill_attention(
            jnp.zeros((4, heads, 16)), pool, jnp.asarray(order), jnp.int32(0),
            w_uk[:1].repeat(heads, 0), w_uv[:1].repeat(heads, 0))
