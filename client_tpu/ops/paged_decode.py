"""Paged decode attention as a Pallas TPU kernel: one query position a lane
over a paged K/V cache, read where it lies.

The cache is a pool of blocks ``[n_blocks + 1, heads, block, width]`` (what
``serve/lm/kv.KvBlockPool`` holds for a configuration whose ``state_spec``
puts the heads outside a block's positions: the three families that keep
per-head keys and values, ``transformer``, ``sambay`` and ``cohere2moe``,
hold a ``k`` and a ``v`` pool so; the latent-attention family, ``axk1``,
holds ONE pool of one shared "head", below) and a lane's
logical cache is its row of a block table.  The XLA form of this read gathers ``pool[tables]``
into a copy as long as the table, whatever the lanes hold, and contracts the
copy; here the pool never leaves HBM whole: grid ``(lane,)``, and a lane's
program walks its own table in steps of ``step_blocks()`` blocks up to its own
length.  A step copies only the blocks under the length, each one contiguous
DMA ``pool[tables[lane, j]] -> VMEM``, into one of two buffers while the
step before it is contracted from the other; the copy ahead crosses from a
lane's last step into the next lane's first, so only the call's first step
waits for HBM with nothing to do.  Scores, running maximum, sum and weighted
sum are float32 (the streaming softmax of ``client_tpu.ops.flash_attention``);
keys, values and queries go to the matrix unit in the type they are stored
in.  A lane of length 0 copies nothing and gets zeros.

A FULL step (``full_steps``: the step in hand and the one after it lie
whole under the lane's length) takes a straight-line body: the copies of the
step ahead are so many starts in a row, no loop, no branch, into a
half of the buffer that the body knows at trace time; all of a pool's copies
signal one semaphore, which counts bytes, so ONE wait against the whole half
stands for the step in hand; then the contraction.  Every other step (a
lane's last two at most, and the hand-over to the next lane) walks the same
copies in loops of a run-time trip count, block by block.  The arithmetic is
the same on both paths.

A lane may also say where its read STARTS (``starts``: a window layer's
``length - window``): its walk then begins at the step that holds that
position, the steps before it are neither copied nor contracted, and the
positions of that step before the start are masked like those past the
length.  Every use of it below is a Python ``None`` test: a call without
``starts`` lowers to a module that knows nothing of them.

To the kernel this is plain grouped attention: ``rows`` query rows a KV
head.  What the rows mean (differential attention's two maps, in
``serve/models/sambay.py``) is the caller's.

LATENT ROWS (``pool_v`` None): a cache that keeps one row a position which
is key and value at once (multi-head latent attention in its absorbed form,
``serve/models/axk1.py``: the normed latent, then the shared rotary key).
There is one pool, one buffer and one copy of a block: a step's rows meet
the queries over their whole width as keys and give their first
``value_width`` columns as values, so a position's bytes cross from HBM
once, and a step is ``LATENT_STEP_BLOCKS`` blocks.  Every use of it below
is a Python ``None`` test, as ``starts`` is.

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

# Blocks a step of the kernel copies and contracts at once, chosen on the
# chip: over a K/V pair, where a step's bytes set its pace (PERF.md section
# 6, PR 32), and over latent rows, where the contraction's own latencies do
# and a longer step spreads them over twice the rows (PR 38)
STEP_BLOCKS = 16
LATENT_STEP_BLOCKS = 32


def step_blocks(latent=False):
    """Blocks a step of the kernel takes over a pool of this form."""
    return LATENT_STEP_BLOCKS if latent else STEP_BLOCKS


def steps_read(lengths, block_size, latent=False):
    """Steps the kernel takes over a lane of ``lengths`` positions (an int,
    an array, or the kernel's own scalar): ``step_blocks(latent) *
    block_size`` positions each, the last one partly masked.  The kernel's
    trip count and the engine's ``attended_tokens`` are both this."""
    span = step_blocks(latent) * block_size
    return (lengths + span - 1) // span


def full_steps(lengths, block_size, starts=None, latent=False):
    """Of a lane's ``steps_read`` steps, those that take the kernel's
    straight-line path: every step but the last of those that lie whole
    under ``lengths`` (the step ahead has to be whole too, and the lane's
    own), counted from the step that holds ``starts`` where a lane has one.
    The kernel's branch and the engine's ``kv_steps_full`` are both this."""
    span = step_blocks(latent) * block_size
    whole = lengths // span - 1 - (0 if starts is None else starts // span)
    return whole * (whole > 0)


def tick_steps(lengths, block_size, calls=1, starts=None, latent=False):
    """For a decode tick's ``tick_trace()`` entry, on the host: the steps
    that ``calls`` calls of the kernel take over lanes that attend
    ``lengths`` positions each (an array; from ``starts``, where given),
    ``kv_steps``, and those of them on the straight-line path,
    ``kv_steps_full``."""
    span = step_blocks(latent) * block_size
    first = 0 if starts is None else starts // span
    return {
        "kv_steps": calls * int(
            (steps_read(lengths, block_size, latent) - first).sum()),
        "kv_steps_full": calls * int(
            full_steps(lengths, block_size, starts, latent).sum()),
    }


def reads_in_place(pool):
    """Whether the kernel can take this pool ``[.., block, width]`` as it
    lies: compiled for the chip, a block's minor two dimensions have to be
    whole tiles (width a multiple of 128, the block of the type's sublane
    count: 8 float32, 16 bfloat16); interpreted, any shape does."""
    if jax.default_backend() != "tpu":
        return True
    block, wide = pool.shape[-2:]
    return wide % 128 == 0 and block % (32 // pool.dtype.itemsize) == 0


def _kernel(*refs, width, block, windowed, latent, unrolled):
    """One lane.  ``at_ref`` [2] carries from lane to lane which buffer
    the next step reads and whether a step before it has already started
    that step's copies.  ``windowed``: a third prefetched vector gives each
    lane's first position.  ``latent``: one pool, whose rows' first
    ``o_ref.shape[-1]`` columns are the values.  ``unrolled``: a full
    step's starts laid out one by one (the compiled kernel; interpreted,
    the same body in a loop, which keeps a CPU test's module small)."""
    if windowed:
        tables_ref, lengths_ref, starts_ref, *refs = refs
    else:
        (tables_ref, lengths_ref, *refs), starts_ref = refs, None
    if latent:
        (q_ref, pool_k, o_ref, k_buf, sems, m_ref, l_ref, acc_ref,
         at_ref) = refs
        v_buf = k_buf                 # the rows that were keys are values
        copied = ((pool_k, k_buf),)
    else:
        (q_ref, pool_k, pool_v, o_ref, k_buf, v_buf, sems, m_ref, l_ref,
         acc_ref, at_ref) = refs
        copied = ((pool_k, k_buf), (pool_v, v_buf))
    lane, n = pl.program_id(0), pl.num_programs(0)
    per_step = step_blocks(latent)
    span = per_step * block
    length = lengths_ref[lane]
    trips = steps_read(length, block, latent)

    def first_step(of_lane):
        """The step that holds ``of_lane``'s first position."""
        return 0 if starts_ref is None else starts_ref[of_lane] // span

    def each_copy(of_lane, step, slot, act, whole=False):
        """``act`` (``"start"`` or ``"wait"``) on the copy of every block
        of ``of_lane``'s ``step`` that lies under the lane's length; of a
        ``whole`` step, on all its blocks one after another (compiled: no
        loop, no branch, static offsets)."""
        first = step * per_step
        held = (lengths_ref[of_lane] + block - 1) // block

        def one(j, _):
            blk = tables_ref[of_lane * width + first + j]
            rows = pl.ds(pl.multiple_of(j * block, block), block)
            for which, (pool, buf) in enumerate(copied):
                getattr(pltpu.make_async_copy(
                    pool.at[blk], buf.at[slot, :, rows, :],
                    sems.at[which, slot]), act)()
            return _

        if whole:
            lax.fori_loop(0, per_step, one, None, unroll=unrolled)
        else:
            lax.fori_loop(0, jnp.minimum(held - first, per_step), one, None)

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

    def contract(i, slot):
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
            "grt,gtd->grd", p.astype(v_buf.dtype),
            v_buf[slot, :, :, :o_ref.shape[-1]] if latent else v_buf[slot],
            preferred_element_type=jnp.float32)
        m_ref[...] = new_m
        at_ref[0] = 1 - slot

    def partial_step(i):
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
        contract(i, slot)

    def full_step(i, slot):
        """``slot`` is a Python int: the copies of step ``i + 1``, whole
        and this lane's, started in a row into the other half; then ONE
        wait for this half's bytes, which a step's copies all signalled."""
        each_copy(lane, i + 1, 1 - slot, "start", whole=True)
        at_ref[1] = 1
        for which, (_, buf) in enumerate(copied):
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sems.at[which, slot]).wait()
        contract(i, slot)

    first = first_step(lane)
    full = full_steps(length, block,
                      None if starts_ref is None else starts_ref[lane], latent)

    def step(i, _):
        lax.switch(jnp.where(i - first < full, 1 + at_ref[0], 0),
                   [lambda: partial_step(i), lambda: full_step(i, 0),
                    lambda: full_step(i, 1)])
        return _

    lax.fori_loop(first, trips, step, None)
    # a lane of length 0 took no step: 0 / tiny
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def paged_decode_attention(q, pool_k, pool_v, tables, lengths, starts=None,
                           interpret=None, value_width=None):
    """Softmax attention of one query position a lane over its paged cache.

    Args:
      q: [n, heads, rows, width] queries, ``rows`` to a KV head, scaled.
      pool_k, pool_v: [n_blocks + 1, heads, block, width] block pools; or
        ``pool_v`` None and ``value_width`` given: the rows of ``pool_k``
        are keys over their whole width and values over their first
        ``value_width`` columns (whole tiles of 128 on the chip).
      tables: [n, table_width] int32, a lane's blocks in order; columns at
        or past ``ceil(length / block)`` are never read.
      lengths: [n] int32; positions ``0 .. length - 1`` are attended.  A
        lane with 0 gets zeros.
      starts: None, or [n] int32 under ``lengths``: positions ``start ..
        length - 1`` are attended, and the steps of ``STEP_BLOCKS`` blocks
        that lie wholly before ``start`` are not read.

    Returns [n, heads, rows, width] float32 weighted sums (``value_width``
    wide over a latent pool).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, heads, rows, wide = q.shape
    block = pool_k.shape[2]
    latent = pool_v is None
    span = step_blocks(latent) * block
    pools = [pool_k] if latent else [pool_k, pool_v]
    out_wide = (value_width or wide) if latent else wide

    def lane_block(last):
        return pl.BlockSpec((1, heads, rows, last),
                            lambda lane, *_: (lane, 0, 0, 0))

    prefetched = [tables.reshape(-1), lengths] + (
        [] if starts is None else [starts])
    return pl.pallas_call(
        functools.partial(_kernel, width=tables.shape[1], block=block,
                          windowed=starts is not None, latent=latent,
                          unrolled=not interpret),
        out_shape=jax.ShapeDtypeStruct((n, heads, rows, out_wide),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(n,),
            in_specs=[lane_block(wide)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=lane_block(out_wide),
            scratch_shapes=[
                pltpu.VMEM((2, heads, span, wide), p.dtype) for p in pools
            ] + [
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, rows, 1), jnp.float32),
                pltpu.VMEM((heads, rows, 1), jnp.float32),
                pltpu.VMEM((heads, rows, out_wide), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*(a.astype(jnp.int32) for a in prefetched), q, *pools)
