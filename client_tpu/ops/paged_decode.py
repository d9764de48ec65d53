"""Paged decode attention as a Pallas TPU kernel: one query position a lane
over a paged K/V cache, read where it lies.

The cache is a pool of blocks ``[n_blocks + 1, heads, block, width]`` (what
``serve/lm/kv.KvBlockPool`` holds for a configuration whose ``state_spec``
puts the heads outside a block's positions: all three families,
``transformer``, ``sambay`` and ``cohere2moe``, hold it so) and a lane's
logical cache is its row of a block table.  The XLA form of this read gathers ``pool[tables]``
into a copy as long as the table, whatever the lanes hold, and contracts the
copy; here the pool never leaves HBM whole: grid ``(lane,)``, and a lane's
program walks its own table in steps of ``STEP_BLOCKS`` blocks up to its own
length.  A step copies only the blocks under the length, each one contiguous
DMA ``pool[tables[lane, j]] -> VMEM``, into one of two buffers while the
step before it is contracted from the other; the copy ahead crosses from a
lane's last step into the next lane's first, so only the call's first step
waits for HBM with nothing to do.  Scores, running maximum, sum and weighted
sum are float32 (the streaming softmax of ``client_tpu.ops.flash_attention``);
keys, values and queries go to the matrix unit in the type they are stored
in.  A lane of length 0 copies nothing and gets zeros.

A lane may also say where its read STARTS (``starts``: a window layer's
``length - window``): its walk then begins at the step that holds that
position, the steps before it are neither copied nor contracted, and the
positions of that step before the start are masked like those past the
length.  Without ``starts`` the call lowers to the module it was before the
argument existed: every use of it below is a Python ``None`` test.

To the kernel this is plain grouped attention: ``rows`` query rows a KV
head.  What the rows mean (differential attention's two maps, in
``serve/models/sambay.py``) is the caller's.

Off-TPU the kernel runs in interpret mode, so CPU tests exercise the code
the chip runs.  The plain interpreter carries a copy out where it is
started; ``interpret=pltpu.InterpretParams()`` models the copies in flight,
their semaphores and a scratch that starts as NaN, for a twentyfold of the
time a call: ``tests/test_paged_decode.py`` holds the kernel to that one.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # -inf stand-in that keeps exp() NaN-free

# Blocks a step of the kernel copies and contracts at once: chosen on the
# chip (PERF.md section 6, PR 32)
STEP_BLOCKS = 16


def steps_read(lengths, block_size):
    """Steps the kernel takes over a lane of ``lengths`` positions (an int,
    an array, or the kernel's own scalar): ``STEP_BLOCKS * block_size``
    positions each, the last one partly masked.  The kernel's trip count
    and the engine's ``attended_tokens`` are both this."""
    span = STEP_BLOCKS * block_size
    return (lengths + span - 1) // span


def reads_in_place(pool):
    """Whether the kernel can take this pool ``[.., block, width]`` as it
    lies: compiled for the chip, a block's minor two dimensions have to be
    whole tiles (width a multiple of 128, the block of the type's sublane
    count: 8 float32, 16 bfloat16); interpreted, any shape does."""
    if jax.default_backend() != "tpu":
        return True
    block, wide = pool.shape[-2:]
    return wide % 128 == 0 and block % (32 // pool.dtype.itemsize) == 0


def _kernel(*refs, width, block, windowed):
    """One lane.  ``at_ref`` [2] carries from lane to lane which buffer
    the next step reads and whether a step before it has already started
    that step's copies.  ``windowed``: a third prefetched vector gives each
    lane's first position."""
    if windowed:
        tables_ref, lengths_ref, starts_ref, *refs = refs
    else:
        (tables_ref, lengths_ref, *refs), starts_ref = refs, None
    (q_ref, pool_k, pool_v, o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref,
     at_ref) = refs
    lane, n = pl.program_id(0), pl.num_programs(0)
    span = STEP_BLOCKS * block
    length = lengths_ref[lane]
    trips = steps_read(length, block)

    def first_step(of_lane):
        """The step that holds ``of_lane``'s first position."""
        return 0 if starts_ref is None else starts_ref[of_lane] // span

    def each_copy(of_lane, step, slot, act):
        """``act`` (``"start"`` or ``"wait"``) on the copy of every block
        of ``of_lane``'s ``step`` that lies under the lane's length."""
        first = step * STEP_BLOCKS
        held = (lengths_ref[of_lane] + block - 1) // block

        def one(j, _):
            blk = tables_ref[of_lane * width + first + j]
            rows = pl.ds(pl.multiple_of(j * block, block), block)
            for which, (pool, buf) in enumerate(((pool_k, k_buf),
                                                 (pool_v, v_buf))):
                getattr(pltpu.make_async_copy(
                    pool.at[blk], buf.at[slot, :, rows, :],
                    sems.at[which, slot]), act)()
            return _

        lax.fori_loop(0, jnp.minimum(held - first, STEP_BLOCKS), one, None)

    @pl.when(lane == 0)
    def _first():
        at_ref[0] = 0
        at_ref[1] = 0
        # a step's dead blocks are not copied: what the buffer held there
        # meets a weight of exactly 0, which only a finite value survives
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when((trips > 0) & (at_ref[1] == 0))
    def _prime():
        each_copy(lane, first_step(lane), at_ref[0], "start")

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def next_lane():
        """The first lane after this one that has a length, else ``n``."""
        return lax.fori_loop(
            lane + 1, n,
            lambda j, found: jnp.where(
                (found == n) & (lengths_ref[j] > 0), j, found),
            n)

    def step(i, _):
        slot = at_ref[0]
        last = i + 1 == trips
        ahead_lane = lax.cond(last, next_lane, lambda: lane)
        if starts_ref is None:
            ahead_step = jnp.where(last, 0, i + 1)
        else:  # (no lane follows the last: clamped, and never started)
            ahead_step = jnp.where(
                last, first_step(jnp.minimum(ahead_lane, n - 1)), i + 1)

        @pl.when(ahead_lane < n)
        def _ahead():
            each_copy(ahead_lane, ahead_step, 1 - slot, "start")

        at_ref[1] = (ahead_lane < n).astype(jnp.int32)
        each_copy(lane, i, slot, "wait")

        q = q_ref[0]                                   # [heads, rows, wide]
        s = jnp.einsum("grd,gtd->grt", q, k_buf[slot],
                       preferred_element_type=jnp.float32)
        at = i * span + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        live = at < length
        if starts_ref is not None:
            live &= at >= starts_ref[lane]
        s = jnp.where(live, s, _NEG)
        m = m_ref[...]
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "grt,gtd->grd", p.astype(v_buf.dtype), v_buf[slot],
            preferred_element_type=jnp.float32)
        m_ref[...] = new_m
        at_ref[0] = 1 - slot
        return _

    lax.fori_loop(first_step(lane), trips, step, None)
    # a lane of length 0 took no step: 0 / tiny
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def paged_decode_attention(q, pool_k, pool_v, tables, lengths, starts=None,
                           interpret=None):
    """Softmax attention of one query position a lane over its paged cache.

    Args:
      q: [n, heads, rows, width] queries, ``rows`` to a KV head, scaled.
      pool_k, pool_v: [n_blocks + 1, heads, block, width] block pools.
      tables: [n, table_width] int32, a lane's blocks in order; columns at
        or past ``ceil(length / block)`` are never read.
      lengths: [n] int32; positions ``0 .. length - 1`` are attended.  A
        lane with 0 gets zeros.
      starts: None, or [n] int32 under ``lengths``: positions ``start ..
        length - 1`` are attended, and the steps of ``STEP_BLOCKS`` blocks
        that lie wholly before ``start`` are not read.

    Returns [n, heads, rows, width] float32 weighted sums.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, heads, rows, wide = q.shape
    block = pool_k.shape[2]
    span = STEP_BLOCKS * block
    lane_block = pl.BlockSpec((1, heads, rows, wide),
                              lambda lane, *_: (lane, 0, 0, 0))
    prefetched = [tables.reshape(-1), lengths] + (
        [] if starts is None else [starts])
    return pl.pallas_call(
        functools.partial(_kernel, width=tables.shape[1], block=block,
                          windowed=starts is not None),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(n,),
            in_specs=[lane_block,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=lane_block,
            scratch_shapes=[
                pltpu.VMEM((2, heads, span, wide), pool_k.dtype),
                pltpu.VMEM((2, heads, span, wide), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, rows, 1), jnp.float32),
                pltpu.VMEM((heads, rows, 1), jnp.float32),
                pltpu.VMEM((heads, rows, wide), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*(a.astype(jnp.int32) for a in prefetched), q, pool_k, pool_v)
