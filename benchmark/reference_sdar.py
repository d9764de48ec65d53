"""Plain reference of the ``sdar_moe`` decoder (SDAR-30B-A3B-Chat,
``configs/sdar-30b-a3b-chat-d7.json``) and of generation by diffusion over
blocks: float32 ``jax.numpy`` at ``HIGHEST`` precision, written from the
layer equations of ISSUE 39.  No cache, no chunks, no kernels, no sorting of
rows.  It takes the weights as data and imports nothing of the program.  A
layer is on the device at a time, a stream at a time.

Layer ``i``: ``x = x + Attn(RMSNorm(x))``; ``x = x + Ffn(RMSNorm(x))``.

- ``Attn``: ``q = h Wq`` as H heads of hd, ``k = h Wk``, ``v = h Wv`` as KV
  heads of hd, no bias; ``q`` and ``k`` RMS-normed over a head's hd
  dimensions under one scale vector each, BEFORE the rotary embedding (all
  hd dimensions, halves pairing, ``pos * theta^(-j / (hd/2))``); scores ``q
  k^T / sqrt(hd)``, float32 softmax over the keys a row may see (below);
  query head h reads KV head ``h // (H / KV)``; out ``concat(heads) Wo``.
- ``Ffn``: ``p = softmax(h Wr)`` over ALL the experts; the
  ``num_experts_per_tok`` largest; ``w_k = p_k / sum of those``; ``sum_k w_k
  E_k(h)``, ``E(h) = Wd(silu(Wg h) * Wu h)``; no shared expert.  Every
  expert is evaluated on every row and weighed by a gate that is zero where
  the router did not pick it.
- Head: ``RMSNorm(x) W_head`` (untied).  Logits at a position predict the
  token AT that position.

WHAT A ROW SEES is block diffusion's own training form: the rows are the
CLEAN sequence followed by NOISY copies of its generated blocks (a block of
``B`` positions in the state it had at one denoising pass: ``[MASK]`` where
not yet fixed).  A clean row at position ``i`` sees the clean rows ``j`` with
``j // B <= i // B``: the block-causal forward, which is also what a commit
pass computes of a finished block.  A noisy row of copy ``c`` sees the
clean rows of the blocks BEFORE its own and the rows of its own block in
copy ``c``: what a denoising pass computes from the stored keys and values
of the earlier blocks and the block itself.  One dense masked softmax over
all rows, a block of query rows at a time.

Departures: none from the equations; the four noisy copies of every
generated block pass the layers together with the clean sequence instead of
one pass at a time (the same sums); rows are padded to whole query blocks
with rows that see themselves alone.

``quant`` is ``reference.fp8``'s hook, on both operands of every matrix
product (the projections, the router, the experts, the scores, the weighted
sums, the head).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import HIGHEST, _mm, _q

QUERY_BLOCK = 128  # query rows whose scores stand at once
CLEAN, PAD = -1, -2  # a row's copy: the clean sequence, padding


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotary embedding of x [S,R,heads,hd] at positions ``pos`` [S,R], the
    halves convention: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = pos.astype(jnp.float32)[..., None] * freqs       # [S,R,half]
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sees(pos, copy, block, lo=0, size=None):
    """[S,size,R]: whether each of the rows ``lo .. lo + size - 1`` (all of
    them by default) sees row j (the module's top), from the rows' positions
    and copies [S,R]."""
    size = pos.shape[1] if size is None else size
    mine = lambda a: lax.dynamic_slice_in_dim(a, lo, size, axis=1)[:, :, None]
    blk = pos // block
    earlier = (copy[:, None, :] == CLEAN) & (blk[:, None, :] < mine(blk))
    own = (copy[:, None, :] == mine(copy)) & (blk[:, None, :] == mine(blk))
    alone = (lo + jnp.arange(size))[:, None] == jnp.arange(pos.shape[1])
    real = copy != PAD
    return ((earlier | own) & real[:, None, :] & mine(real)) | alone[None]


def _attention(q, k, v, pos, copy, block, quant):
    """q [S,R,H,hd], k and v [S,R,KV,hd] -> [S,R,H*hd]; a block of query
    rows at a time (R is whole query blocks); query head h is row ``h % (H
    / KV)`` of KV head ``h // (H / KV)``."""
    s, r, heads, hd = q.shape
    kv = k.shape[2]
    q = q.reshape(s, r, kv, heads // kv, hd)
    size = min(QUERY_BLOCK, r)

    def rows(lo):
        q_b = lax.dynamic_slice_in_dim(q, lo, size, axis=1)
        mask = sees(pos, copy, block, lo, size)                  # [S,q,R]
        scores = jnp.einsum("sqgrd,skgd->sgrqk", _q(quant, q_b), _q(quant, k),
                            precision=HIGHEST) * hd ** -0.5
        probs = jax.nn.softmax(
            jnp.where(mask[:, None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("sgrqk,skgd->sqgrd", _q(quant, probs), _q(quant, v),
                          precision=HIGHEST)

    out = lax.map(rows, jnp.arange(0, r, size))     # [n,S,size,KV,rep,hd]
    return jnp.moveaxis(out, 0, 1).reshape(s, r, heads * hd)


def gates(h, router, top_k, quant=None):
    """[.., experts]: the normalised weight of each expert for each row,
    zero where it is not among the ``top_k`` most probable."""
    probs = jax.nn.softmax(_mm(quant, h, router), axis=-1)
    top, picks = lax.top_k(probs, top_k)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(picks, probs.shape[-1], dtype=probs.dtype)
    return jnp.einsum("...k,...ke->...e", weights, chosen, precision=HIGHEST)


def ffn(h, w, top_k, quant=None):
    """The routed sum of ``h`` [..., D] under the layer's ``ffn`` tree ``w``
    (as stored): every expert on every row, weighed by its gate, an expert
    at a time, its matrices made float32 when its turn comes."""
    f32 = jnp.float32
    ff = w["w_down"].shape[1]
    gate = gates(h, w["router"].astype(f32), top_k, quant)

    def routed(j, out):
        gu = w["w_gate_up"][j].astype(f32)
        hidden = jax.nn.silu(_mm(quant, h, gu[:, :ff])) * _mm(
            quant, h, gu[:, ff:])
        mine = lax.dynamic_index_in_dim(gate, j, axis=-1)     # [..,1]
        return out + mine * _mm(quant, hidden, w["w_down"][j].astype(f32))

    return lax.fori_loop(0, w["w_down"].shape[0], routed, jnp.zeros_like(h))


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, pos, copy, layer, dims, quant):
    heads, kv, hd, eps, theta, top_k, block = dims
    f32 = jnp.float32
    s, r, _ = x.shape
    h = _rms_norm(x, layer["ln_attn"].astype(f32), eps)
    q, k, v = jnp.split(_mm(quant, h, layer["wqkv"].astype(f32)),
                        [heads * hd, (heads + kv) * hd], axis=-1)
    q = _rms_norm(q.reshape(s, r, heads, hd), layer["q_norm"].astype(f32), eps)
    k = _rms_norm(k.reshape(s, r, kv, hd), layer["k_norm"].astype(f32), eps)
    v = v.reshape(s, r, kv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    mixed = _attention(q, k, v, pos, copy, block, quant)
    x = x + _mm(quant, mixed, layer["wo"].astype(f32))
    h = _rms_norm(x, layer["ln_mlp"].astype(f32), eps)
    return x + ffn(h, layer["ffn"], top_k, quant)


def layer_dims(config):
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["rms_norm_eps"],
            float(config["rope_theta"]), config["num_experts_per_tok"],
            config["assumed"]["block_length"])


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, lm_head, eps, quant):
    h = _rms_norm(x, ln_f.astype(jnp.float32), eps)
    return _mm(quant, h, lm_head.astype(jnp.float32))


def logits_at(config, hidden, ends, quant=None):
    """float32 logits [.., V] of ``hidden`` [.., D]."""
    return _head(hidden, ends["ln_f"], ends["lm_head"],
                 config["rms_norm_eps"], quant)


def noisy_copies(config, tokens, fixed_at, first):
    """The noisy copies of a stream's generated region, from what the
    program recorded.  ``tokens`` [N] are the region's final ids (positions
    ``first .. first + N - 1``, whole blocks), ``fixed_at`` [N] for each of
    them how many of its block's positions were unmasked when it was fixed
    (``-1``: known from the prompt).  Copy ``c`` of a block is the block
    with exactly the positions of ``fixed_at < c`` unmasked: its state at
    the pass that ran with ``c`` of its positions unmasked, if one did.
    Returns (ids [B,N] with the mask id where masked, masked [B,N])."""
    b = config["assumed"]["block_length"]
    masked = np.asarray(fixed_at)[None, :] >= np.arange(b)[:, None]
    ids = np.where(masked, config["assumed"]["mask_token_id"],
                   np.asarray(tokens)[None, :])
    return ids.astype(np.int32), masked


def hidden_states(config, tokens, noisy, first, ends, layer_of,
                  quants=(None,)):
    """The residual stream before the last norm, float32, of the clean
    sequences ``tokens`` [S,T] (T whole blocks) and of the noisy copies
    ``noisy`` [S,C,N] of their positions ``first`` [S] ``..`` (ids, the mask
    id where masked): ``(clean [S,T,D], noisy [S,C,N,D])`` for each entry of
    ``quants``.  ``layer_of(i)`` gives layer i's weights; a stream passes a
    layer at a time."""
    tokens, noisy = np.asarray(tokens), np.asarray(noisy)
    s, t = tokens.shape
    _, c, n = noisy.shape
    rows = t + c * n
    padded = -(-rows // QUERY_BLOCK) * QUERY_BLOCK
    ids = np.zeros((s, padded), np.int32)
    pos = np.zeros((s, padded), np.int32)
    copy = np.full((s, padded), PAD, np.int32)
    ids[:, :t], pos[:, :t], copy[:, :t] = tokens, np.arange(t), CLEAN
    for j in range(c):
        span = slice(t + j * n, t + (j + 1) * n)
        ids[:, span], copy[:, span] = noisy[:, j], j
        pos[:, span] = np.asarray(first)[:, None] + np.arange(n)
    dims = layer_dims(config)
    embedded = [jnp.take(ends["embed"], ids[i:i + 1], axis=0).astype(
        jnp.float32) for i in range(s)]
    carried = [list(embedded) for _ in quants]
    for i in range(config["num_hidden_layers"]):
        layer = layer_of(i)
        carried = [[_layer(x, pos[j:j + 1], copy[j:j + 1], layer, dims, quant)
                    for j, x in enumerate(streams)]
                   for streams, quant in zip(carried, quants)]
        del layer
    out = []
    for streams in carried:
        x = jnp.concatenate(streams, axis=0)
        out.append((x[:, :t], x[:, t:rows].reshape(s, c, n, -1)))
    return out


def pass_gaps(config, hidden, served, fixed_at, ends, control=None):
    """What the cell's ``correct`` compares, for one stream: ``hidden``
    [C,N,D] of its noisy copies, ``served`` [N] the region's final ids,
    ``fixed_at`` [N] (``-1``: not compared: known, or past the stream's
    end).  Returns (token gaps, place gaps), 1-d arrays:

    - a served token's gap: how far the reference's logit of it lies under
      the reference's best, at its position in the copy of the pass that
      fixed it;
    - a pass's gap: how far the reference's confidence (its largest softmax
      probability) at the positions the pass fixed lies under the
      reference's best over the positions still masked, summed over the
      positions a pass fixes and divided by their number.

    With ``control`` (hidden states [C,N,D] and their quant hook) the
    control stands in the program's place on the same states: the tokens it
    puts first where the program fixed one, and the positions it would fix
    by its own confidences."""
    b = config["assumed"]["block_length"]
    served, fixed_at = np.asarray(served), np.asarray(fixed_at)
    n = len(served)
    conf = np.zeros((b, n))        # the reference's confidence
    gap = np.zeros((b, n))         # of the chosen token under its best
    picks = np.zeros((b, n))       # the chooser's confidence
    for c in range(b):
        ref = logits_at(config, hidden[c], ends)                  # [N,V]
        top = jnp.max(ref, axis=-1)
        conf[c] = np.asarray(jnp.exp(top - jax.nn.logsumexp(ref, axis=-1)))
        if control is None:
            chosen = jnp.asarray(served)
            picks[c] = conf[c]
        else:
            other = logits_at(config, control[0][c], ends, control[1])
            chosen = jnp.argmax(other, axis=-1)
            picks[c] = np.asarray(jnp.exp(
                jnp.max(other, axis=-1) - jax.nn.logsumexp(other, axis=-1)))
        gap[c] = np.asarray(top - jnp.take_along_axis(
            ref, chosen[:, None], axis=-1)[:, 0])
    token_gaps, place_gaps = [], []
    for lo in range(0, n, b):
        at = fixed_at[lo:lo + b]
        for c in sorted(set(at[at >= 0].tolist())):
            fixed = np.flatnonzero(at == c)
            masked = np.flatnonzero(at >= c)
            token_gaps += gap[c, lo + fixed].tolist()
            mine = conf[c, lo + masked]
            if control is None:
                theirs = conf[c, lo + fixed]
            else:   # the positions the control would fix: stable, as the rule
                order = np.argsort(-picks[c, lo + masked], kind="stable")
                theirs = mine[order[:len(fixed)]]
            best = np.sort(mine)[::-1][:len(fixed)]
            place_gaps.append(float(best.sum() - theirs.sum()) / len(fixed))
    return np.asarray(token_gaps), np.asarray(place_gaps)
