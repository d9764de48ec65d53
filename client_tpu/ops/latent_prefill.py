"""Latent attention of a prefill chunk in its EXPANDED form as a Pallas TPU
kernel: one lane's chunk of ``T`` query rows over a paged pool of latent
rows, read where they lie.

The cache keeps ONE row a position, ``[c_kv | k_pe | zeros]`` (multi-head
latent attention, ``serve/models/axk1.py``): a head's key is ``[c_kv w_uk^T
| k_pe]``, its value ``c_kv w_uv``, and neither is stored.  The XLA form of
this read gathered a group of table columns, rebuilt every head's keys and
values of the group in HBM and carried scores ``[heads, T, group]`` and the
running sums through HBM at every step of a ``fori_loop``.  Here grid
``(head block,)``: a head block's ``w_uk``, ``w_uv`` and queries are in VMEM,
and its program walks the lane's table in groups of ``GROUP_BLOCKS`` columns
up to the group that holds the chunk's last position (``groups_read``).  A
group's blocks under that position are copied, each one contiguous DMA
``pool[table[j]] -> VMEM``, into one of two buffers while the group before
it is contracted from the other; the copy ahead crosses from a head block's
last group into the next one's first, so only the call's first group waits
for HBM with nothing to do.  From the buffer, a head at a time: ``k_nope``
and ``v`` in the type they are stored in out of a float32 accumulation, the
row's tail (the shared rotary key, then zeros up to whole lane tiles) beside
``k_nope`` for every head alike, scores in float32, the mask ``at <= pos``,
running maximum, sum and weighted sum in float32 (the streaming softmax of
``client_tpu.ops.flash_attention``), ``p`` cast to the values' type before
the product.  Nothing of shape ``[heads, T, group]`` and no rebuilt key or
value exists outside VMEM; the latents cross from HBM once a head block.

KEYS DOWN, QUERIES ACROSS: scores are held ``[group, T]``, a key a sublane
row and a query a lane, and the weighted sum ``[v, T]``.  The softmax's
maximum and sum over keys are then elementwise over registers with one
short reduction at the end, and the running maximum and sum are ``[1, T]``
rows; with a query a row they were a reduction across lanes for every eight
rows and a column padded to a whole lane tile, and the softmax cost a fifth
of the kernel's time (PERF.md section 6, PR 36).

Off-TPU the kernel runs in interpret mode, so CPU tests exercise the code
the chip runs (``tests/test_latent_prefill.py``; one case under
``pltpu.InterpretParams()``, which models the copies in flight and a
scratch that starts as NaN).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from client_tpu.ops.paged_decode import STEP_BLOCKS

_NEG = -1e30  # -inf stand-in that keeps exp() NaN-free

# Table columns whose keys and values a step of the kernel rebuilds and
# contracts at once: 512 positions at the cell's block of 16, a chunk's own
# width
GROUP_BLOCKS = 2 * STEP_BLOCKS
# Heads whose weights and queries are resident at once: chosen on the chip
# (PERF.md section 6, PR 36)
HEAD_BLOCK = 16


def group_span(block_size):
    """Positions of a group: what a step of the kernel rebuilds."""
    return GROUP_BLOCKS * block_size


def groups_read(max_pos, block_size):
    """Groups the kernel walks for a chunk whose largest query position is
    ``max_pos`` (an int, an array, or the kernel's own scalar): whole groups
    of ``group_span`` positions up to the one that holds it.  The kernel's
    trip count and, times the span, the engine's ``kv_positions_read`` /
    ``kv_rows_rebuilt`` are both this."""
    return max_pos // group_span(block_size) + 1


def _kernel(table_ref, start_ref, q_ref, w_uk_ref, w_uv_ref, pool, o_ref,
            buf, sems, m_ref, l_ref, *, width):
    """One head block.  Groups are numbered through the whole call (``done``
    before this block), and a group's buffer is its number's parity: the
    block before this one has already started this block's first copies."""
    hb, n = pl.program_id(0), pl.num_programs(0)
    heads, _, t = q_ref.shape
    latent = w_uk_ref.shape[-1]
    span = buf.shape[1]
    block = span // GROUP_BLOCKS
    start = start_ref[0]
    last_pos = start + t - 1
    trips = groups_read(last_pos, block)
    held = jnp.minimum(last_pos // block + 1, width)
    done = hb * trips

    def each_copy(group, slot, act):
        """``act`` (``"start"`` or ``"wait"``) on the copy of every block of
        ``group`` that lies under the chunk's last position."""
        first = group * GROUP_BLOCKS

        def one(j, _):
            rows = pl.ds(pl.multiple_of(j * block, block), block)
            getattr(pltpu.make_async_copy(
                pool.at[table_ref[first + j], 0], buf.at[slot, rows, :],
                sems.at[slot]), act)()
            return _

        lax.fori_loop(0, jnp.minimum(held - first, GROUP_BLOCKS), one, None)

    @pl.when(hb == 0)
    def _first():
        # the last group's blocks past ``held`` are not copied: what the
        # buffer held there is rebuilt and meets a weight of exactly 0,
        # which only a finite value survives
        buf[...] = jnp.zeros_like(buf)
        each_copy(0, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    o_ref[...] = jnp.zeros_like(o_ref)

    def step(g, _):
        slot = (done + g) % 2
        last = g + 1 == trips

        @pl.when(jnp.logical_not(last) | (hb + 1 < n))
        def _ahead():
            each_copy(jnp.where(last, 0, g + 1), 1 - slot, "start")

        each_copy(g, slot, "wait")
        # key row r sits at position g * span + r, query column c at start + c
        live = (lax.broadcasted_iota(jnp.int32, (span, t), 0)
                - lax.broadcasted_iota(jnp.int32, (span, t), 1)
                <= start - g * span)

        def head(h, _):
            c_kv = buf[slot, :, :latent]
            k_nope = lax.dot_general(
                c_kv, w_uk_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(buf.dtype)
            v = jnp.dot(c_kv, w_uv_ref[h],
                        preferred_element_type=jnp.float32).astype(buf.dtype)
            k = jnp.concatenate([k_nope, buf[slot, :, latent:]], axis=-1)
            s = jnp.dot(k, q_ref[h], preferred_element_type=jnp.float32)
            s = jnp.where(live, s, _NEG)                      # [span, t]
            m = m_ref[h]                                      # [1, t]
            new_m = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            corr = jnp.exp(m - new_m)
            p = jnp.exp(s - new_m)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=0, keepdims=True)
            o_ref[h] = o_ref[h] * corr + lax.dot_general(
                v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [v, t]
            m_ref[h] = new_m
            return _

        lax.fori_loop(0, heads, head, None)
        return _

    lax.fori_loop(0, trips, step, None)
    o_ref[...] = o_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _vmem_bytes(heads, t, span, row, latent, nope, v_dim, itemsize):
    """What the kernel keeps in VMEM: the two latent buffers, the head
    block's queries, weights and output (each double-buffered by the
    pipeline), the running maximum and sum (a row pads to eight sublanes),
    and a head's intermediates at a group (scores, mask and weights in
    float32, the rebuilt keys and values)."""
    q_wide = nope + row - latent
    resident = (2 * span * row * itemsize
                + 2 * heads * t * q_wide * itemsize
                + 2 * heads * latent * (nope + v_dim) * itemsize
                + 2 * heads * t * v_dim * 4
                + 2 * heads * 8 * t * 4)
    a_head = 4 * t * span * 4 + span * (q_wide + nope + v_dim) * 4
    return resident + a_head


def latent_prefill_attention(q, pool, table, start, w_uk, w_uv,
                             interpret=None):
    """Softmax attention of one lane's chunk over its paged latent cache, in
    the expanded form.

    Args:
      q: [T, heads, nope + rope] queries at positions ``start .. start + T -
        1``, the rotary part roped, scaled.
      pool: [n_blocks + 1, 1, block, row_width] latent blocks; a row is
        ``[c_kv | k_pe | zeros]``.
      table: [width] int32, the lane's blocks in order; columns past the
        block that holds position ``start + T - 1`` are never read.
      start: int32 scalar: any position (prefix adoption hands over starts
        that are whole blocks, not whole groups).
      w_uk: [heads, nope, latent]; w_uv: [heads, latent, v].

    Returns [T, heads * v] float32: position ``start + r`` attends rows
    ``0 .. start + r``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, n_heads, _ = q.shape
    _, nope, latent = w_uk.shape
    v_dim = w_uv.shape[-1]
    block, row = pool.shape[2:]
    span = group_span(block)
    heads = min(HEAD_BLOCK, n_heads)
    if n_heads % heads:
        raise ValueError(f"{n_heads} heads are no whole blocks of {heads}")
    # heads outermost, a query a column, and the rotary part padded to the
    # row's tail: the columns behind the rotary key are zeros on both sides
    tail = row - latent
    q = jnp.pad(q, ((0, 0), (0, 0), (0, nope + tail - q.shape[-1])))
    q = q.transpose(1, 2, 0).astype(pool.dtype)

    def head_block(*last):
        return pl.BlockSpec((heads,) + last, lambda hb, *_: (hb, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, width=table.shape[0]),
        out_shape=jax.ShapeDtypeStruct((n_heads, v_dim, t), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_heads // heads,),
            in_specs=[head_block(nope + tail, t), head_block(nope, latent),
                      head_block(latent, v_dim),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=head_block(v_dim, t),
            scratch_shapes=[
                pltpu.VMEM((2, span, row), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((heads, 1, t), jnp.float32),
                pltpu.VMEM((heads, 1, t), jnp.float32),
            ],
        ),
        # twice the reckoning: the compiler's own temporaries beside ours
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * _vmem_bytes(
                heads, t, span, row, latent, nope, v_dim,
                pool.dtype.itemsize)),
        interpret=interpret,
    )(table.astype(jnp.int32), jnp.reshape(start, (1,)).astype(jnp.int32),
      q, w_uk, w_uv, pool)
    return out.transpose(2, 0, 1).reshape(t, n_heads * v_dim)
