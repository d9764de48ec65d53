"""Causal ring attention over a sequence-parallel mesh axis.

Long-context support: Q/K/V are sharded along the sequence dimension across
the ``sp`` mesh axis.  Each device keeps its Q block resident and rotates the
K/V blocks around the ring with ``lax.ppermute`` (ICI neighbor exchange),
accumulating softmax results blockwise with the numerically-stable
flash-attention recurrence (running max ``m``, running denominator ``l``,
running weighted sum ``o``).  After ``sp`` steps every Q block has seen every
KV block and no device ever materialized the full [T, T] score matrix or the
full-length K/V.

Causality is enforced per block-pair: a KV block strictly "in the future" of
the Q block contributes nothing (fully masked); the diagonal block gets the
usual triangular mask.  All accumulation is float32 regardless of input dtype.

This is the framework's long-context primitive (the reference client has none
— SURVEY.md §5.7); it is used by the transformer model family's
sequence-parallel training/prefill path.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

_NEG = -1e30  # stand-in for -inf that keeps exp() NaN-free


def _block_accumulate(o, m, l, q, kb, vb, q_pos, kv_pos, scale, causal):
    """One flash-attention accumulation step against KV block (kb, vb).

    Layouts: q [B,H,Tq,D]; kb/vb [B,H,Tk,D]; o [B,H,Tq,D] f32;
    m/l [B,H,Tq,1] f32.  q_pos [Tq], kv_pos [Tk] are global token positions.
    """
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, kb, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        mask = q_pos[:, None] >= kv_pos[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
    blk_max = jnp.max(s, axis=-1, keepdims=True)
    new_m = jnp.maximum(m, blk_max)
    corr = jnp.exp(m - new_m)
    p = jnp.exp(s - new_m)
    new_l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum(
        "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    new_o = o * corr + pv
    return new_o, new_m, new_l


def _ring_schedule(state, k, v, axis_name, causal, step_fn):
    """THE ring schedule, shared by both impls: rotate KV around the ring
    with ppermute, calling ``step_fn(state, kb, vb, kv_idx, idx)`` for every
    non-future shard pair (under causality, strictly-future KV shards are
    skipped — they contribute exactly nothing).  ``state`` is any pytree;
    step_fn owns the accumulate/merge semantics."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    kb, vb = k, v
    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        kv_idx = (idx - step) % n
        if causal:
            # kv_idx is device-constant under SPMD: each device runs only
            # its selected branch, so the skip really saves the compute
            state = lax.cond(
                kv_idx > idx,
                lambda st, *_: st,
                lambda st, kb_, vb_: step_fn(st, kb_, vb_, kv_idx, idx),
                state, kb, vb,
            )
        else:
            state = step_fn(state, kb, vb, kv_idx, idx)
        if step != n - 1:
            kb = lax.ppermute(kb, axis_name, perm)
            vb = lax.ppermute(vb, axis_name, perm)
    return state


def _varying_full(q, shapes_dtypes):
    """Constant-filled accumulators (shape, dtype, fill triples) marked with
    q's device-varying axes so the lax.cond branches' varying-axis types
    agree under shard_map."""
    arrs = [jnp.full(sh, fill, dt) for sh, dt, fill in shapes_dtypes]
    varying = tuple(jax.typeof(q).vma) if hasattr(jax, "typeof") else ()
    if varying:
        arrs = [lax.pcast(a, varying, to="varying") for a in arrs]
    return arrs


def ring_attention(q, k, v, axis_name="sp", causal=True, scale=None,
                   impl="plain"):
    """Per-shard ring attention body; call inside ``jax.shard_map``.

    Args:
      q, k, v: [B, T_local, H, D] — the sequence dimension is the local shard
        of a global sequence laid out contiguously across ``axis_name``.
      axis_name: mesh axis carrying the sequence shards.
      causal: apply the causal mask using *global* token positions.
      scale: score scale; defaults to D**-0.5.
      impl: "plain" — the per-step block accumulate is XLA einsums
        materializing one [Tloc, Tloc] score block; "flash" — each ring
        step runs the Pallas kernel (client_tpu.ops) over the local pair
        and steps merge by log-sum-exp, so per-step memory is O(block)
        even at long local shards.  Block-causality makes the two modes
        line up exactly: the diagonal step is the kernel's own causal
        mask, past steps are unmasked, future steps are skipped.

    Returns [B, T_local, H, D] in q's dtype.
    """
    if impl == "flash":
        return _ring_attention_flash(q, k, v, axis_name, causal, scale)
    b, t_loc, h, d = q.shape
    if scale is None:
        scale = d ** -0.5

    qh = q.transpose(0, 2, 1, 3)  # [B,H,T,D]
    o, m, l = _varying_full(q, [
        (qh.shape, jnp.float32, 0.0),
        ((b, h, t_loc, 1), jnp.float32, _NEG),
        ((b, h, t_loc, 1), jnp.float32, 0.0),
    ])
    # transpose KV once; the schedule rotates whatever layout it is given
    q_pos = lax.axis_index(axis_name) * t_loc + jnp.arange(t_loc)

    def step_fn(state, kb, vb, kv_idx, idx):
        o, m, l = state
        kv_pos = kv_idx * t_loc + jnp.arange(t_loc)
        return _block_accumulate(
            o, m, l, qh, kb, vb, q_pos, kv_pos, scale, causal,
        )

    o, m, l = _ring_schedule(
        (o, m, l), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        axis_name, causal, step_fn,
    )
    out = (o / l).astype(q.dtype)
    return out.transpose(0, 2, 1, 3)


def _ring_attention_flash(q, k, v, axis_name, causal, scale):
    """Ring schedule with the Pallas flash kernel as the per-step engine.

    Each step computes a self-contained (out_s, lse_s) for the resident Q
    shard against the rotating KV shard; partial results merge with the
    exact softmax-combine ``o ← o·α + o_s·α_s`` where the α's renormalize
    by ``logaddexp(lse, lse_s)``.  Future KV shards are skipped (their lse
    is −inf and contributes nothing, so the cond is purely a compute save).
    """
    from client_tpu.ops.flash_attention import flash_attention_with_lse

    b, t_loc, h, d = q.shape
    if scale is None:
        scale = d ** -0.5

    acc, lse = _varying_full(q, [
        ((b, h, t_loc, d), jnp.float32, 0.0),
        ((b, h, t_loc, 1), jnp.float32, _NEG),
    ])

    def merge(acc, lse, out_s, lse_s):
        new_lse = jnp.logaddexp(lse, lse_s)
        return (
            acc * jnp.exp(lse - new_lse) + out_s * jnp.exp(lse_s - new_lse),
            new_lse,
        )

    def step_fn(state, kb, vb, kv_idx, idx):
        acc, lse = state

        def run(step_causal, a, l, kb_, vb_):
            out_s, lse_s = flash_attention_with_lse(
                q, kb_, vb_, causal=step_causal, scale=scale
            )
            out_s = out_s.transpose(0, 2, 1, 3).astype(jnp.float32)
            return merge(a, l, out_s, lse_s)

        if causal:
            # the diagonal shard uses the kernel's local causal mask; past
            # shards attend fully (global positions never needed — the
            # schedule already skipped strictly-future shards)
            return lax.cond(
                kv_idx == idx,
                functools.partial(run, True),
                functools.partial(run, False),
                acc, lse, kb, vb,
            )
        return run(False, acc, lse, kb, vb)

    acc, lse = _ring_schedule((acc, lse), k, v, axis_name, causal, step_fn)
    return acc.astype(q.dtype).transpose(0, 2, 1, 3)


def plain_attention(q, k, v, causal=True, scale=None):
    """Single-shard reference attention; same [B,T,H,D] interface."""
    b, t, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        kt = k.shape[1]
        # offset so the last q row attends to the full kv length (decode case)
        pos_q = jnp.arange(t) + (kt - t)
        mask = pos_q[:, None] >= jnp.arange(kt)[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, causal=True, scale=None,
                           impl="plain"):
    """shard_map wrapper: global [B,T,H,D] arrays, T sharded over ``sp``.

    Batch rides ``dp``; heads ride ``tp``; D is replicated.  The body sees
    local blocks and exchanges KV over the ring; ``impl="flash"`` runs each
    ring step through the Pallas kernel (O(block) per-step memory).
    """
    spec = P("dp", "sp", "tp", None)
    # check_vma: Pallas INTERPRET mode (the off-TPU test path) lowers to
    # dynamic_slice with invariant index operands, which the varying-axis
    # checker rejects — disable it only there; compiled TPU runs keep the
    # checker for both impls.
    interpret = jax.default_backend() != "tpu"
    fn = jax.shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, "sp", causal, scale, impl),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=not (impl == "flash" and interpret),
    )
    return fn(q, k, v)
