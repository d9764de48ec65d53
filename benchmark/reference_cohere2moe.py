"""Plain reference of the ``cohere2_moe`` decoder (Command A+), given the
SAME share of the deployment as the program (``configs/command-a-plus-05-
2026-ep8-d4.json``: the held experts under a router over all of them, the
held rows of the vocabulary): float32 ``jax.numpy`` at ``HIGHEST``
precision, written from the layer equations of ISSUE 33.  No cache, no
chunks, no kernels, no sorting of rows: every attention is a dense masked
softmax over the whole sequence, taken a block of query positions at a
time so that 8,960 positions x 128 heads fit, and every held expert is
evaluated on every position and weighed by a gate that is zero where the
router did not pick it.  It takes the weights as data and imports nothing
of the program.  A layer is on the device at a time, and the logits exist
for a block of positions at a time only.

Layer ``i``: ``n = LayerNorm(x)`` (mean subtracted, a scale, no bias);
``x' = x + Attn(n) + Ffn(n)``: the parallel block.

- ``Attn``: ``q = n Wq`` as H heads of hd, ``k = n Wk``, ``v = n Wv`` as KV
  heads of hd.  ``layer_types[i]`` ``sliding_attention``: rotary on q and k,
  interleaved pairs ``(2j, 2j+1)`` turned by ``pos * theta^(-2j / hd)``, and
  position t sees keys ``t-window+1 .. t``.  ``full_attention``: no rotary,
  keys ``0 .. t``.  Scores ``q k^T / sqrt(hd)``, softmax, query head h reads
  KV head ``h // (H / KV)``; out ``concat(heads) Wo``.
- ``Ffn``: ``s = sigmoid(n Wr)`` over ALL the router's experts; the
  ``num_experts_per_tok`` largest; ``w_k = s_k / sum of those``; routed part
  ``sum over the picks that are HELD of w_k E_k(n)``, ``E(n) = Wd(silu(Wg n)
  * Wu n)``; shared part the mean of the ``num_shared_experts`` experts of
  the same form; ``Ffn = routed + shared``.
- Head: ``LayerNorm(x) E^T * logit_scale``.

``quant`` is ``reference.fp8``'s hook, on both operands of every matrix
product (the projections, the router, the experts, the scores, the weighted
sums, the head).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import HIGHEST, _mm, _q

QUERY_BLOCK = 128  # query positions whose scores stand at once


def _layer_norm(x, scale, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale


def _rope_gptj(x, theta):
    """x [S,T,heads,hd]: pair j of a head is dimensions (2j, 2j+1)."""
    s, t, heads, hd = x.shape
    freqs = theta ** (-2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs    # [T,hd/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    pairs = x.reshape(s, t, heads, hd // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(s, t, heads, hd)


def _attention(q, k, v, window, quant):
    """q [S,T,H,hd], k and v [S,T,KV,hd] -> [S,T,H*hd]; ``window`` None is
    the whole context.  A block of query positions at a time; query head h
    is row ``h % (H / KV)`` of KV head ``h // (H / KV)``."""
    s, t, heads, hd = q.shape
    kv = k.shape[2]
    q = q.reshape(s, t, kv, heads // kv, hd)
    block = min(QUERY_BLOCK, t)
    keys_at = jnp.arange(t)

    def rows(lo):
        q_b = lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        at = lo + jnp.arange(block)
        mask = keys_at[None, :] <= at[:, None]
        if window is not None:
            mask &= keys_at[None, :] > at[:, None] - window
        scores = jnp.einsum("sqgrd,skgd->sgrqk", _q(quant, q_b), _q(quant, k),
                            precision=HIGHEST) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("sgrqk,skgd->sqgrd", _q(quant, probs), _q(quant, v),
                          precision=HIGHEST)

    # the last block may reach back over the one before it: same rows twice
    starts = [min(lo, t - block) for lo in range(0, t, block)]
    out = lax.map(rows, jnp.asarray(starts))        # [n,S,block,KV,rep,hd]
    mixed = jnp.zeros(q.shape, jnp.float32)
    for j, lo in enumerate(starts):
        mixed = lax.dynamic_update_slice_in_dim(mixed, out[j], lo, axis=1)
    return mixed.reshape(s, t, heads * hd)


def _expert(n, gate, up, down, quant):
    return _mm(quant, jax.nn.silu(_mm(quant, n, gate)) * _mm(quant, n, up),
               down)


def gates(n, router, top_k, quant=None):
    """[.., experts]: the normalised weight of each of the router's experts
    for each position, zero where it is not among the ``top_k`` largest."""
    scores = jax.nn.sigmoid(_mm(quant, n, router))
    top, picks = lax.top_k(scores, top_k)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(picks, scores.shape[-1], dtype=scores.dtype)
    return jnp.einsum("...k,...ke->...e", weights, chosen, precision=HIGHEST)


def ffn(n, w, held, top_k, n_shared, quant=None):
    """``routed + shared`` of ``n`` [..., D] under the layer's ``ffn`` tree
    ``w`` (as stored): every held expert on every position, weighed by its
    gate; the shared experts one after another, averaged.  An expert at a
    time, its matrices made float32 when its turn comes: sixteen experts'
    float32 matrices and hidden rows side by side would not fit beside
    8,960 positions."""
    f32 = jnp.float32
    ff = w["w_down"].shape[1]
    gate = gates(n, w["router"].astype(f32), top_k, quant)
    held = jnp.asarray(held, jnp.int32)

    def routed(j, out):
        gu = w["w_gate_up"][j].astype(f32)
        mine = jnp.take(gate, held[j], axis=-1)[..., None]
        return out + mine * _expert(n, gu[:, :ff], gu[:, ff:],
                                    w["w_down"][j].astype(f32), quant)

    # [D, 2 n_shared F]: the gates expert after expert, then the ups
    sgu = w["shared_gate_up"].reshape(n.shape[-1], 2, n_shared, ff)
    sdn = w["shared_down"].reshape(n_shared, ff, n.shape[-1])

    def shared(j, out):
        return out + _expert(n, sgu[:, 0, j].astype(f32),
                             sgu[:, 1, j].astype(f32), sdn[j].astype(f32),
                             quant)

    zeros = jnp.zeros_like(n)
    return (lax.fori_loop(0, held.shape[0], routed, zeros)
            + lax.fori_loop(0, n_shared, shared, zeros) / n_shared)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, layer, dims, quant):
    heads, kv, hd, eps, theta, window, held, top_k, n_shared = dims
    f32 = jnp.float32
    s, t, _ = x.shape
    n = _layer_norm(x, layer["ln"].astype(f32), eps)
    q, k, v = jnp.split(_mm(quant, n, layer["wqkv"].astype(f32)),
                        [heads * hd, (heads + kv) * hd], axis=-1)
    q = q.reshape(s, t, heads, hd)
    k = k.reshape(s, t, kv, hd)
    v = v.reshape(s, t, kv, hd)
    if window is not None:
        q, k = _rope_gptj(q, theta), _rope_gptj(k, theta)
    mixed = _attention(q, k, v, window, quant)
    return (x + _mm(quant, mixed, layer["wo"].astype(f32))
            + ffn(n, layer["ffn"], held, top_k, n_shared, quant))


def layer_dims(config, i):
    """What layer ``i`` is, from the configuration's own keys."""
    sliding = config["layer_types"][i] == "sliding_attention"
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["layer_norm_eps"],
            float(config["rope_theta"]),
            config["sliding_window"] if sliding else None,
            tuple(config["deployment"]["experts_held"]),
            config["num_experts_per_tok"], config["num_shared_experts"])


@functools.partial(jax.jit, static_argnames=("eps", "scale", "quant"))
def _head(x, ln_f, embed, eps, scale, quant):
    h = _layer_norm(x, ln_f.astype(jnp.float32), eps)
    return _mm(quant, h, embed.astype(jnp.float32).T) * scale


def hidden_states(config, tokens, at, ends, layer_of, quants=(None,),
                  block_rows=1):
    """The residual stream before the last norm, float32 [S, N, D], at the
    positions ``at`` [S, N] of int32 ``tokens`` [S, T]: one array for each
    entry of ``quants``.  ``layer_of(i)`` gives layer i's weights."""
    blocks = [slice(i, i + block_rows)
              for i in range(0, tokens.shape[0], block_rows)]
    embedded = [jnp.take(ends["embed"], tokens[b], axis=0).astype(jnp.float32)
                for b in blocks]
    carried = [list(embedded) for _ in quants]
    for i in range(config["num_hidden_layers"]):
        layer, dims = layer_of(i), layer_dims(config, i)
        carried = [[_layer(x, layer, dims, quant) for x in rows]
                   for rows, quant in zip(carried, quants)]
        del layer
    out = []
    for rows in carried:
        x = jnp.concatenate(rows, axis=0)
        out.append(jnp.take_along_axis(x, jnp.asarray(at)[:, :, None], axis=1))
    return out


def logits_at(config, hidden, ends, quant=None):
    """float32 logits [S, N, V] of ``hidden`` [S, N, D], over the held rows
    of the vocabulary: for a block of positions."""
    return _head(hidden, ends["ln_f"], ends["embed"],
                 config["layer_norm_eps"], float(config["logit_scale"]), quant)


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
