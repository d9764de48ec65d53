"""Plain reference of the SambaY decoder-hybrid-decoder (arXiv:2507.06607,
``Phi-4-mini-flash-reasoning``): float32 ``jax.numpy`` at ``HIGHEST``
precision, written from the layer equations of ISSUE 29 and the paper.  No
cache, no chunks, no kernels: the scan is a ``lax.scan`` over time, every
attention a dense masked softmax over the whole sequence.  It takes the
weights as data and imports nothing of the program.  A layer is on the
device at a time, rows pass it in blocks, and the 200,064-wide logits exist
for a block of positions at a time only.

Layer ``i`` of L, ``x += mixer(LN(x)); x += MLP(LN(x))``; with ``h = LN(x)``:

- ``i < L/2``, ``i`` a multiple of ``mb_per_layer``, and ``i = L/2``: Mamba-1.
  ``[xs, z] = h W_in``; ``xs = silu(conv(xs) + b_conv)``, a causal depthwise
  convolution over ``d_conv`` positions (``conv_w[:, d_conv-1]`` weighs the
  position itself); ``[dt_r, B, C] = xs W_x``; ``dt = softplus(dt_r W_dt +
  b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(dt_t A) s_(t-1) + (dt_t xs_t)
  B_t``; ``y_t = s_t . C_t + D xs_t``; out ``(y silu(z)) W_out``.  Layer L/2
  hands ``y`` on as the memory ``m``.
- ``i < L/2`` otherwise: differential attention over keys ``t-window+1 .. t``.
- ``i = L/2 + 1``: differential attention over keys ``0 .. t``.
- ``i > L/2 + 1``, ``i`` a multiple of ``mb_per_layer``: ``(silu(h W_in) m)
  W_out``.
- ``i > L/2 + 1`` otherwise: differential attention with the layer's own
  ``W_q`` over the keys and values that layer L/2 + 1 computed.

Differential attention (arXiv:2410.05258): query heads ``2p+m`` and KV heads
``2g+m`` (``m`` in 0, 1), ``g = p // (H / KV)``; ``a_m = softmax(q_(2p+m)
k_(2g+m)^T / sqrt(hd) + mask) [v_(2g) | v_(2g+1)]``; ``lambda = exp(lq1 .
lk1) - exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6 exp(-0.3 i)``; ``out_p =
RMSNorm(a_0 - lambda a_1) subln (1 - l0)``.  No rotary embedding.  The head
is the final LayerNorm, then the embedding transposed.

``quant`` is ``reference.fp8``'s hook, on both operands of every matrix
product (the projections, the scores, the weighted sums, the head).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import HIGHEST, _mm, _q
from benchmark.work_sambay import kinds


def layer_kind(config, i):
    return kinds(config)[i]


def _layer_norm(x, ln, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * ln["scale"] + ln["bias"]


def _mamba(w, h, dims, quant):
    """h [S,T,D] -> (out [S,T,D], y [S,T,d_inner])."""
    d_state, d_conv, dt_rank = dims
    s, t, _ = h.shape
    xs, z = jnp.split(_mm(quant, h, w["w_in"]), 2, axis=-1)
    padded = jnp.pad(xs, ((0, 0), (d_conv - 1, 0), (0, 0)))
    xs = sum(padded[:, j:j + t] * w["conv_w"][:, j] for j in range(d_conv))
    xs = jax.nn.silu(xs + w["conv_b"])
    dt_r, b, c = jnp.split(_mm(quant, xs, w["w_x"]),
                           [dt_rank, dt_rank + d_state], axis=-1)
    dt = jax.nn.softplus(_mm(quant, dt_r, w["w_dt"]) + w["b_dt"])
    a = -jnp.exp(w["A_log"])                                   # [di, ds]

    def step(state, inputs):
        dt_t, x_t, b_t, c_t = inputs                  # [S,di] [S,di] [S,ds]
        state = jnp.exp(dt_t[:, :, None] * a) * state \
            + (dt_t * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    state = jnp.zeros((s, xs.shape[-1], d_state), jnp.float32)
    _, y = lax.scan(step, state, tuple(jnp.swapaxes(v, 0, 1)
                                       for v in (dt, xs, b, c)))
    y = jnp.swapaxes(y, 0, 1) + w["D"] * xs
    return _mm(quant, y * jax.nn.silu(z), w["w_out"]), y


def _diff_attention(w, q, k, v, l0, window, eps, quant):
    """q [S,T,H,hd], k and v [S,T,KV,hd] -> [S,T,H*hd]; ``window`` None is
    the whole context."""
    s, t, heads, hd = q.shape
    kv = k.shape[2]
    pairs, groups = heads // 2, kv // 2
    rep = pairs // groups
    at = jnp.arange(t)
    mask = at[None, :] <= at[:, None]
    if window is not None:
        mask &= at[None, :] > at[:, None] - window
    lam = (jnp.exp(jnp.sum(w["lq1"] * w["lk1"]))
           - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + l0)
    # a KV pair at a time, so that the [T,T] scores of all heads never
    # stand side by side
    qg = jnp.moveaxis(q.reshape(s, t, groups, rep, 2, hd), 2, 0)
    kg = jnp.moveaxis(k.reshape(s, t, groups, 2, hd), 2, 0)
    vg = jnp.moveaxis(v.reshape(s, t, groups, 2 * hd), 2, 0)

    def group(args):
        q_g, k_g, v_g = args          # [S,T,rep,2,hd] [S,T,2,hd] [S,T,2hd]
        scores = jnp.einsum("sqrmd,skmd->srmqk", _q(quant, q_g),
                            _q(quant, k_g), precision=HIGHEST) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        a = jnp.einsum("srmqk,ske->sqrme", _q(quant, probs), _q(quant, v_g),
                       precision=HIGHEST)
        diff = a[:, :, :, 0] - lam * a[:, :, :, 1]          # [S,T,rep,2hd]
        norm = diff * lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True) + eps)
        return norm * w["subln"] * (1.0 - l0)

    out = lax.map(group, (qg, kg, vg))                  # [g,S,T,rep,2hd]
    return jnp.moveaxis(out, 0, 2).reshape(s, t, heads * hd)


@functools.partial(jax.jit, static_argnames=("kind", "dims", "quant"))
def _layer(x, layer, memory, kv, kind, dims, quant):
    """(x, memory, kv) after one layer; ``memory`` and ``kv`` pass through
    the layers that do not make them."""
    heads, kv_heads, hd, eps, window, l0, mamba_dims = dims
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layer)
    m = w["mixer"]
    s, t, _ = x.shape
    h = _layer_norm(x, w["ln_mix"], eps)
    if kind in ("mamba", "memory"):
        out, y = _mamba(m, h, mamba_dims, quant)
        if kind == "memory":
            memory = y
    elif kind == "gmu":
        out = _mm(quant, jax.nn.silu(_mm(quant, h, m["w_in"])) * memory,
                  m["w_out"])
    else:
        if kind == "cross":
            q = _mm(quant, h, m["wq"]) + m["bq"]
            k, v = kv
        else:
            qkv = _mm(quant, h, m["wqkv"]) + m["bqkv"]
            q, k, v = jnp.split(
                qkv, [heads * hd, (heads + kv_heads) * hd], axis=-1)
            k = k.reshape(s, t, kv_heads, hd)
            v = v.reshape(s, t, kv_heads, hd)
            if kind == "full":
                kv = (k, v)
        mixed = _diff_attention(m, q.reshape(s, t, heads, hd), k, v, l0,
                                window if kind == "window" else None, eps,
                                quant)
        out = _mm(quant, mixed, m["wo"]) + m["bo"]
    x = x + out
    h = _layer_norm(x, w["ln_mlp"], eps)
    gate, up = jnp.split(_mm(quant, h, w["mlp"]["w1"]), 2, axis=-1)
    return x + _mm(quant, up * jax.nn.silu(gate), w["mlp"]["w2"]), memory, kv


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, embed, eps, quant):
    h = _layer_norm(x, jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), ln_f), eps)
    return _mm(quant, h, embed.astype(jnp.float32).T)


def hidden_states(config, tokens, at, ends, layer_of, quants=(None,),
                  block_rows=1):
    """The residual stream before the last norm, float32 [S, N, D], at the
    positions ``at`` [S, N] of int32 ``tokens`` [S, T]: one array for each
    entry of ``quants``.  ``layer_of(i)`` gives layer i's weights."""
    mamba = config["assumed"]["mamba"]
    mamba_dims = (mamba["d_state"], mamba["d_conv"], mamba["dt_rank"])
    blocks = [slice(i, i + block_rows)
              for i in range(0, tokens.shape[0], block_rows)]
    embedded = [jnp.take(ends["embed"], tokens[b], axis=0).astype(jnp.float32)
                for b in blocks]
    # per quant, per block of rows: (x, memory, the full layer's k and v)
    carried = [[(x, None, None) for x in embedded] for _ in quants]
    for i in range(config["num_hidden_layers"]):
        layer, kind = layer_of(i), layer_kind(config, i)
        dims = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"], config["layer_norm_eps"],
                config["sliding_window"], 0.8 - 0.6 * math.exp(-0.3 * i),
                mamba_dims)
        carried = [[_layer(x, layer, memory, kv, kind, dims, quant)
                    for x, memory, kv in rows]
                   for rows, quant in zip(carried, quants)]
        del layer
    out = []
    for rows in carried:
        x = jnp.concatenate([x for x, _, _ in rows], axis=0)
        out.append(jnp.take_along_axis(x, jnp.asarray(at)[:, :, None], axis=1))
    return out


def logits_at(config, hidden, ends, quant=None):
    """float32 logits [S, N, V] of ``hidden`` [S, N, D]: for a block of
    positions; at the cell's sizes the whole is 3.3 GB."""
    return _head(hidden, ends["ln_f"], ends["embed"],
                 config["layer_norm_eps"], quant)


def token_gaps(config, hidden, served, ends, control=None,
               block_positions=128):
    """By how much the reference's logit of a token lies below the
    reference's best, [S, N]: of ``served`` [S, N], or, with ``control``
    (hidden states and their quant hook), of the tokens that the control
    puts first.  A block of positions at a time."""
    gaps = []
    for lo in range(0, hidden.shape[1], block_positions):
        block = slice(lo, lo + block_positions)
        ref = logits_at(config, hidden[:, block], ends)
        if control is None:
            chosen = jnp.asarray(served)[:, block]
        else:
            chosen = jnp.argmax(logits_at(
                config, control[0][:, block], ends, control[1]), axis=-1)
        picked = jnp.take_along_axis(ref, chosen[:, :, None], axis=-1)[..., 0]
        gaps.append(jnp.max(ref, axis=-1) - picked)
    return jnp.concatenate(gaps, axis=1)
