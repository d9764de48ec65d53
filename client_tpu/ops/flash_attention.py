"""Causal flash attention as Pallas TPU kernels (forward + fused backward).

Forward — grid (batch·head, Q blocks, KV blocks): the KV dimension is the
innermost, sequentially-iterated ("arbitrary") grid axis, so only ONE
[Bk, D] K block and V block are VMEM-resident at a time — Pallas
double-buffers the block DMAs while the streaming-softmax state (running
max / denominator / f32 accumulator) persists in VMEM scratch across the KV
sweep.  VMEM use is O(Bq·D + Bk·D) regardless of sequence length, so the
kernel compiles at any T the HBM can hold; the [T, T] score matrix never
exists anywhere.  Causal masking skips the compute (not just the scores) of
fully-past-diagonal blocks via ``pl.when``.  Alongside the output the
forward emits the per-row log-sum-exp, the one O(T) residual the backward
needs.

Backward — the standard two-kernel flash-attention-2 scheme, both streaming
the same way as the forward:
- dQ kernel: grid (BH, Q blocks, KV blocks), dQ accumulated in VMEM
  scratch across the KV sweep; scores recomputed blockwise from q/k and the
  saved LSE (p = exp(s − lse)), never materialized globally.
- dK/dV kernel: grid (BH, KV blocks, Q blocks), dK and dV accumulated in
  scratch across the Q sweep.
Both use delta = rowsum(dO ⊙ O) (computed once, O(T)) for the softmax
Jacobian, so memory stays O(block) end to end — no O(T²) anywhere in
training either.

Off-TPU (CPU tests, the 8-device virtual mesh) the kernels run in Pallas
interpret mode automatically, so every test exercises the same code path
the chip runs compiled.

Reference has no analog (client-only stack); layout conventions follow
client_tpu.parallel.ring_attention (same [B, T, H, D] interface as
``plain_attention``).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # -inf stand-in that keeps exp() NaN-free


def _block_scores(q_ref, k_ref, qi, ki, scale, block_q, block_k, causal):
    """Recompute one [Bq, Bk] score block (f32, scaled, causally masked)."""
    q = q_ref[0].astype(jnp.float32)
    kb = k_ref[0].astype(jnp.float32)
    s = lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_pos = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0
        )
        kv_pos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1
        )
        s = jnp.where(q_pos >= kv_pos, s, _NEG)
    return s


def _block_dscores(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, glse_ref,
                   qi, ki, scale, block_q, block_k, causal):
    """Backward softmax-Jacobian for one block pair: returns (p, ds, do32).

    p = exp(s − lse) recomputed from the saved LSE;
    ds = p·(dO·Vᵀ − delta + dLSE)·scale — the dLSE term carries the
    cotangent of the forward's log-sum-exp output (∂lse_i/∂s_ij = p_ij),
    zero when only the attention output is differentiated.  Shared verbatim
    by the dQ and dK/dV kernels.
    """
    s = _block_scores(q_ref, k_ref, qi, ki, scale, block_q, block_k, causal)
    p = jnp.exp(s - lse_ref[...].reshape(-1, 1))  # [Bq, Bk]
    do = do_ref[0].astype(jnp.float32)
    vb = v_ref[0].astype(jnp.float32)
    dp = lax.dot_general(
        do, vb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Bq, Bk]
    row = (dp - delta_ref[...].reshape(-1, 1)
           + glse_ref[...].reshape(-1, 1))
    ds = p * row * scale
    return p, ds, do


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
               scale, block_q, block_k, causal):
    """Forward: one (batch·head, q-block, kv-block) program.

    Block shapes: q_ref/o_ref [1, block_q, D]; k_ref/v_ref [1, block_k, D];
    lse_ref [1, block_q, 1] (trailing singleton keeps the block 2D-tileable
    on TPU).  acc/m/l scratch persists across the KV axis.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    # a KV block strictly past this Q block's last row contributes nothing —
    # skip its matmuls entirely
    diag_ok = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(diag_ok)
    def _accumulate():
        s = _block_scores(q_ref, k_ref, qi, ki, scale, block_q, block_k,
                          causal)
        vb = v_ref[0].astype(jnp.float32)
        m = m_ref[:]
        blk_max = jnp.max(s, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = new_m

    @pl.when(ki == n_kv - 1)
    def _finish():
        # every real row saw at least its own diagonal key, so l > 0; the
        # guard only shields padded Q rows, whose output is sliced off
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[...] = (m_ref[:] + jnp.log(l)).reshape(1, -1, 1)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-mesh-axes of ``like`` so
    pallas_call outputs type-check under shard_map's check_vma."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _fa_forward(q, k, v, scale, block_q, block_k, causal, interpret):
    """[BH, T, D] inputs → ([BH, T, D] out, [BH, T, 1] lse)."""
    bh, t, d = q.shape
    grid = (bh, t // block_q, t // block_k)
    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal,
    )
    return pl.pallas_call(
        kernel,
        out_shape=(
            _sds((bh, t, d), q.dtype, q),
            _sds((bh, t, 1), jnp.float32, q),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, glse_ref,
               dq_ref, acc_ref, *, scale, block_q, block_k, causal):
    """dQ: one (batch·head, q-block, kv-block) program; dQ in scratch."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    diag_ok = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(diag_ok)
    def _accumulate():
        _, ds, _ = _block_dscores(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, glse_ref,
            qi, ki, scale, block_q, block_k, causal,
        )
        kb = k_ref[0].astype(jnp.float32)
        acc_ref[:] += lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_kv - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, glse_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, block_q, block_k,
                causal):
    """dK/dV: one (batch·head, kv-block, q-block) program; both in scratch."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    diag_ok = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(diag_ok)
    def _accumulate():
        p, ds, do = _block_dscores(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, glse_ref,
            qi, ki, scale, block_q, block_k, causal,
        )
        dv_acc[:] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Bk, D]
        qb = q_ref[0].astype(jnp.float32)
        dk_acc[:] += lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Bk, D]

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fa_backward(q, k, v, out, lse, g, g_lse, scale, block_q, block_k,
                 causal, interpret):
    """Fused flash backward on [BH, T, D] arrays → (dq, dk, dv).

    ``g_lse`` is the cotangent of the forward's lse output ([BH, T, 1];
    pass zeros when only the attention output is differentiated).
    """
    bh, t, d = q.shape
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # [BH, T, 1]

    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec_dq = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    rowspec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
            causal=causal,
        ),
        out_shape=_sds((bh, t, d), q.dtype, q),
        grid=(bh, t // block_q, t // block_k),
        in_specs=[qspec, kspec_dq, kspec_dq, qspec, rowspec, rowspec,
                  rowspec],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, g, lse, delta, g_lse)

    # kv-major grid: q-row inputs are indexed by the INNER axis here
    qspec_kv = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0))
    kspec_kv = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    rowspec_kv = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
            causal=causal,
        ),
        out_shape=(
            _sds((bh, t, d), k.dtype, q),
            _sds((bh, t, d), v.dtype, q),
        ),
        grid=(bh, t // block_k, t // block_q),
        in_specs=[qspec_kv, kspec_kv, kspec_kv, qspec_kv, rowspec_kv,
                  rowspec_kv, rowspec_kv],
        out_specs=(kspec_kv, kspec_kv),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, g, lse, delta, g_lse)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fa(q, k, v, scale, block_q, block_k, causal, interpret):
    out, _ = _fa_forward(q, k, v, scale, block_q, block_k, causal, interpret)
    return out


def _fa_fwd(q, k, v, scale, block_q, block_k, causal, interpret):
    out, lse = _fa_forward(q, k, v, scale, block_q, block_k, causal,
                           interpret)
    return out, (q, k, v, out, lse)


def _fa_bwd(scale, block_q, block_k, causal, interpret, res, g):
    q, k, v, out, lse = res
    return _fa_backward(q, k, v, out, lse, g, jnp.zeros_like(lse), scale,
                        block_q, block_k, causal, interpret)


_fa.defvjp(_fa_fwd, _fa_bwd)


def _prep(t, d, scale, interpret, block_q, block_k):
    """Shared wrapper defaults: score scale, interpret-mode autodetect, and
    sublane-aligned block clamps (Mosaic tiling: never clamp to a ragged t)."""
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    align = 32
    block_q = min(block_q, -(-t // align) * align)
    block_k = min(block_k, -(-t // align) * align)
    return scale, interpret, block_q, block_k


def _fold(x, b, t, h, d):
    """[B,T,H,D] -> [B*H, T, D]."""
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _reference_lse(q, k, v, causal, scale):
    """(out, lse) on [BH, T, D] via plain einsums — bwd recompute path for
    the lse-exposing variant."""
    s = jnp.einsum(
        "bqd,bkd->bqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None], s, _NEG)
    lse = jax.scipy.special.logsumexp(s, axis=-1)[..., None]
    p = jnp.exp(s - lse)
    out = jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v).astype(q.dtype)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fa_lse(q, k, v, scale, block_q, block_k, causal, interpret):
    return _fa_forward(q, k, v, scale, block_q, block_k, causal, interpret)


def _fa_lse_fwd(q, k, v, scale, block_q, block_k, causal, interpret):
    out, lse = _fa_forward(q, k, v, scale, block_q, block_k, causal,
                           interpret)
    return (out, lse), (q, k, v, out, lse)


def _fa_lse_bwd(scale, block_q, block_k, causal, interpret, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    return _fa_backward(q, k, v, out, lse, g_out,
                        g_lse.astype(jnp.float32), scale, block_q, block_k,
                        causal, interpret)


_fa_lse.defvjp(_fa_lse_fwd, _fa_lse_bwd)


def flash_attention_with_lse(q, k, v, causal=True, scale=None, block_q=128,
                             block_k=128, interpret=None):
    """Flash attention returning ``(out, lse)`` on the [B, T, H, D] layout.

    ``lse`` is [B, H, T, 1] f32 — the per-row log-sum-exp that lets partial
    attention results over disjoint KV shards merge exactly:
    ``o = Σ_s o_s · exp(lse_s − logaddexp_s lse_s)``.  This is the building
    block ring attention uses to run each ring step through the kernel.
    T must tile by the (aligned) block sizes — ring shards are powers of
    two, so no padding path is carried here.
    """
    b, t, h, d = q.shape
    scale, interpret, block_q, block_k = _prep(
        t, d, scale, interpret, block_q, block_k
    )
    qf = _fold(q, b, t, h, d)
    kf = _fold(k, b, t, h, d)
    vf = _fold(v, b, t, h, d)
    if t % block_q or t % block_k:
        # sub-block / ragged shard: the einsum reference is exact and cheap
        # at small sizes, but it is O(T²) — refuse silently degrading a
        # long-context shard (pad the global sequence upstream instead)
        if t > 1024:
            raise ValueError(
                f"shard length {t} does not tile by blocks "
                f"({block_q},{block_k}) and is too long for the dense "
                "fallback; pad the sequence so shards tile"
            )
        out, lse = _reference_lse(qf, kf, vf, causal, scale)
    else:
        out, lse = _fa_lse(
            qf, kf, vf, scale, block_q, block_k, causal, interpret
        )
    out = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return out, lse.reshape(b, h, t, 1)


def flash_attention(q, k, v, causal=True, scale=None, block_q=128,
                    block_k=128, interpret=None):
    """Flash attention with the ``plain_attention`` interface.

    Args:
      q, k, v: [B, T, H, D] (same head count — repeat GQA KV first, as the
        transformer's attention block already does).
      causal: apply the causal mask (q and kv must be the same length).
      scale: score scale; defaults to D**-0.5.
      block_q, block_k: kernel tile sizes (clamped to the padded length).
      interpret: force Pallas interpret mode; default: on for any backend
        without a real TPU.

    Returns [B, T, H, D] in q's dtype.
    """
    b, t, h, d = q.shape
    scale, interpret, block_q, block_k = _prep(
        t, d, scale, interpret, block_q, block_k
    )
    # padded length must tile by BOTH block sizes
    pad = (-t) % math.lcm(block_q, block_k)

    if pad and not causal:
        # non-causal has no positional mask to neutralize padded keys; the
        # ragged remainder is small — use the plain formulation directly
        from client_tpu.parallel.ring_attention import plain_attention

        return plain_attention(q, k, v, causal=False, scale=scale)

    qf = _fold(q, b, t, h, d)
    kf = _fold(k, b, t, h, d)
    vf = _fold(v, b, t, h, d)
    if pad:
        # padded KV rows sit in the causal future of every real Q row (the
        # position mask zeroes them); padded Q rows are sliced off below
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))

    out = _fa(qf, kf, vf, scale, block_q, block_k, causal, interpret)
    if pad:
        out = out[:, :t]
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
