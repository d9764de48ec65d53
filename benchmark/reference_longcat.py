"""Plain reference of the ``longcat_flash`` decoder (the language model of
LongCat-Flash-Omni), given the SAME share of the deployment as the program
(``configs/longcat-flash-omni-ep32-d4.json``: the held experts under a
router over all the routed experts and zero slots, the held rows of the
vocabulary): float32 ``jax.numpy`` at ``HIGHEST`` precision, written from
the equations of the published ``LongcatFlashDecoderLayer`` (below).
Multi-head latent attention in its EXPANDED form only (``reference_axk1``'s
dense masked softmax, a block of query positions at a time); no cache, no
chunks, no kernels, no absorbed queries, no sorting of rows: every held
expert is evaluated on every position and weighed by a gate that is zero
where the router did not pick it, and the zero slots add their gates times
the input.  It takes the weights as data and imports nothing of the
program.  A double layer is on the device at a time, and the logits exist
for a block of positions at a time only.

Double layer ``l``, every norm an RMSNorm (float32, a scale, no bias):

- ``x = x + Mla_0(RMSNorm(x))``; ``h2 = RMSNorm(x)``; ``m = Moe(h2)``;
  ``x = x + Ffn_0(h2)``; ``x = x + Mla_1(RMSNorm(x))``; ``x = x +
  Ffn_1(RMSNorm(x)) + m``.
- ``Mla``: ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` as H heads of
  ``[q_nope | q_pe]``, both parts times ``(hidden / q_lora_rank)^0.5``
  (``mla_scale_q_lora``); ``[c_kv | k_pe] = h W_kva``; ``c_kv =
  RMSNorm(c_kv) * (hidden / kv_lora_rank)^0.5`` (``mla_scale_kv_lora``,
  the latent only); ``k_nope = c_kv W_uk^T``, ``v = c_kv W_uv`` a head;
  plain rotary at ``rope_theta`` on ``q_pe`` and the one shared ``k_pe``,
  dimension j paired with ``j + rope / 2``; ``score = (q_nope . k_nope +
  q_pe . k_pe) / sqrt(nope + rope)``, causal; ``attn = concat(heads) W_o``.
- ``Ffn``: ``W_d(silu(W_g h) * W_u h)`` at ``ffn_hidden_size``, a slice of
  the width at a time.
- ``Moe``: ``p = softmax(h2 W_r)`` over the routed experts and the
  ``zero_expert_num`` slots after them; the ``moe_topk`` largest of ``p +
  b`` (``e_score_correction_bias``) are the picks, ``w_k =
  routed_scaling_factor * p_k`` their weights, not renormalised; ``Moe =
  sum over the picks that are HELD experts of w_k E_k(h2) + (sum over the
  zero picks of w_k) h2``, ``E`` of ``Ffn``'s form at
  ``expert_ffn_hidden_size``.
- Head: ``RMSNorm(x) W_head``.

Departures from the published code, each the program's as well: the
router's product is in float32 (the published code casts both operands to
float32 before it; the program multiplies bf16 operands, exact in float32,
and accumulates in float32); the program folds the two LoRA scales into
the float32 stage of their norms before the cast to bf16, where the
published code multiplies the bf16 outputs, and this reference multiplies
where the published code does.

``quant`` is ``reference.fp8``'s hook, on both operands of every matrix
product (the projections, the rebuilt keys and values, the router, the
experts, the scores, the weighted sums, the head).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import HIGHEST, _mm, _q, _rms_norm
from benchmark.reference_axk1 import _attention, _expert, _rope, dense_ffn


def inv_freq(config):
    """Plain rotary's ``rope / 2`` inverse frequencies: ``theta^(-2j /
    rope)``."""
    rope, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    return np.array([theta ** (-2.0 * j / rope) for j in range(rope // 2)])


def lora_scales(config):
    """(the query's, the latent's): ``(hidden / rank)^0.5`` where the
    configuration's ``mla_scale_*_lora`` is true, else 1."""
    d = config["hidden_size"]
    return tuple((d / config[rank]) ** 0.5 if config[flag] else 1.0
                 for flag, rank in (("mla_scale_q_lora", "q_lora_rank"),
                                    ("mla_scale_kv_lora", "kv_lora_rank")))


def gates(n, router, bias, top_k, scale, quant=None):
    """[.., slots]: the weight of each of the router's slots for each
    position: ``scale * p`` where the slot is among the ``top_k`` largest of
    ``p + bias``, zero elsewhere."""
    p = jax.nn.softmax(_mm(quant, n, router), axis=-1)
    _, picks = lax.top_k(p + bias, top_k)
    chosen = jnp.sum(jax.nn.one_hot(picks, p.shape[-1], dtype=p.dtype),
                     axis=-2)
    return scale * p * chosen


def moe(n, w, held, n_routed, top_k, scale, quant=None):
    """The shortcut branch of ``n`` [..., D] under a layer's ``moe`` tree
    ``w`` (as stored): every held expert on every position, weighed by its
    gate, an expert at a time; the zero slots' gates times ``n``."""
    f32 = jnp.float32
    ff = w["w_down"].shape[1]
    gate = gates(n, w["router"].astype(f32), w["bias"].astype(f32), top_k,
                 scale, quant)
    held = jnp.asarray(held, jnp.int32)

    def routed(j, out):
        gu = w["w_gate_up"][j].astype(f32)
        mine = jnp.take(gate, held[j], axis=-1)[..., None]
        return out + mine * _expert(n, gu[:, :ff], gu[:, ff:],
                                    w["w_down"][j].astype(f32), quant)

    zero = jnp.sum(gate[..., n_routed:], axis=-1, keepdims=True)
    return lax.fori_loop(0, held.shape[0], routed, jnp.zeros_like(n)) \
        + zero * n


def mla(h, a, freqs, dims, quant=None):
    """One attention sublayer of ``h`` [S,T,D] (normed) under its tree
    ``a``, expanded: [S,T,D]."""
    heads, nope, rope, lora, eps, q_scale, kv_scale = dims
    f32 = jnp.float32
    s, t, _ = h.shape
    c_q = _rms_norm(_mm(quant, h, a["w_qa"].astype(f32)),
                    a["ln_q"].astype(f32), eps)
    # w_qb's columns: every head's q_nope, then every head's q_pe
    q = _mm(quant, c_q, a["w_qb"].astype(f32))
    q_nope = q[..., :heads * nope].reshape(s, t, heads, nope) * q_scale
    q_pe = q[..., heads * nope:].reshape(s, t, heads, rope) * q_scale
    kv = _mm(quant, h, a["w_kva"].astype(f32))
    c_kv = _rms_norm(kv[..., :lora], a["ln_kv"].astype(f32), eps) * kv_scale
    k_nope = jnp.einsum("stc,hdc->sthd", _q(quant, c_kv),
                        _q(quant, a["w_uk"].astype(f32)), precision=HIGHEST)
    v = jnp.einsum("stc,hcd->sthd", _q(quant, c_kv),
                   _q(quant, a["w_uv"].astype(f32)), precision=HIGHEST)
    q_pe = _rope(q_pe, freqs, 1.0)
    k_pe = _rope(kv[:, :, None, lora:], freqs, 1.0)[:, :, 0]
    mixed = _attention(q_nope, q_pe, k_nope, k_pe, v,
                       (nope + rope) ** -0.5, quant)
    return _mm(quant, mixed, a["w_o"].astype(f32))


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, layer, freqs, dims, quant):
    attn_dims, held, n_routed, top_k, routed_scale = dims
    eps = attn_dims[4]
    f32 = jnp.float32
    (a0, a1), (f0, f1) = layer["attn"], layer["mlp"]
    x = x + mla(_rms_norm(x, a0["ln"].astype(f32), eps), a0, freqs,
                attn_dims, quant)
    h2 = _rms_norm(x, f0["ln"].astype(f32), eps)
    m = moe(h2, layer["moe"], held, n_routed, top_k, routed_scale, quant)
    x = x + dense_ffn(h2, f0, quant)
    x = x + mla(_rms_norm(x, a1["ln"].astype(f32), eps), a1, freqs,
                attn_dims, quant)
    h4 = _rms_norm(x, f1["ln"].astype(f32), eps)
    return x + dense_ffn(h4, f1, quant) + m


def layer_dims(config):
    """What a double layer is, from the configuration's own keys (every one
    is alike)."""
    attn = (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["kv_lora_rank"],
            config["rms_norm_eps"]) + lora_scales(config)
    share = config["deployment"]
    return (attn, tuple(share["experts_held"]), share["router_experts"],
            config["moe_topk"], float(config["routed_scaling_factor"]))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, eps, quant):
    h = _rms_norm(x, ln_f.astype(jnp.float32), eps)
    return _mm(quant, h, head.astype(jnp.float32).T)


def hidden_states(config, tokens, at, ends, layer_of, quants=(None,),
                  block_rows=1):
    """The residual stream before the last norm, float32 [S, N, D], at the
    positions ``at`` [S, N] of int32 ``tokens`` [S, T]: one array for each
    entry of ``quants``.  ``layer_of(i)`` gives double layer i's
    weights."""
    freqs = jnp.asarray(inv_freq(config), jnp.float32)
    dims = layer_dims(config)
    blocks = [slice(i, i + block_rows)
              for i in range(0, tokens.shape[0], block_rows)]
    embedded = [jnp.take(ends["embed"], tokens[b], axis=0).astype(jnp.float32)
                for b in blocks]
    carried = [list(embedded) for _ in quants]
    for i in range(config["num_layers"]):
        layer = layer_of(i)
        carried = [[_layer(x, layer, freqs, dims, quant) for x in rows]
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
    return _head(hidden, ends["ln_f"], ends["head"], config["rms_norm_eps"],
                 quant)


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
