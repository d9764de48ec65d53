"""Weights of a SambaY configuration from ``--seed``, made on the device a
layer at a time, one compiled program a layer kind.  The served model and
the plain reference are handed arrays made by the same program, as with
``weights.lm_layer``.  The tree is the one that ``configs/phi-4-mini-flash-
reasoning.json`` describes under ``assumed``: [in, out] matrices, bf16;
float32 for ``A_log``, ``D``, ``b_dt`` and the lambda vectors."""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference_sambay import layer_kind
from benchmark.weights import _norm_scale, _normal, seed_key

BF16 = jnp.bfloat16


def _dims(config):
    mamba = config["assumed"]["mamba"]
    return (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["vocab_size"],
            mamba["expand"] * config["hidden_size"], mamba["d_state"],
            mamba["d_conv"], mamba["dt_rank"])


def _bias(key, n):
    """A bias: small, and not zero, so that a side that drops it shows."""
    return (0.1 * jax.random.normal(key, (n,))).astype(BF16)


def _norm(key, d):
    k = jax.random.split(key)
    return {"scale": _norm_scale(k[0], d), "bias": _bias(k[1], d)}


def _mixer(key, kind, dims):
    d, _, heads, kv, hd, _, di, ds, d_conv, dt_rank = dims
    k = jax.random.split(key, 12)
    if kind in ("mamba", "memory"):
        step = jnp.exp(jax.random.uniform(
            k[0], (di,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "w_in": _normal(k[1], (d, 2 * di), d, BF16),
            "conv_w": _normal(k[2], (di, d_conv), d_conv, BF16),
            "conv_b": _bias(k[3], di),
            "w_x": _normal(k[4], (di, dt_rank + 2 * ds), di, BF16),
            "w_dt": _normal(k[5], (dt_rank, di), dt_rank, BF16),
            # softplus(b_dt) = step: the family's initialisation
            "b_dt": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jnp.broadcast_to(
                jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))),
            "D": jnp.ones((di,), jnp.float32),
            "w_out": _normal(k[6], (di, d), di, BF16),
        }
    if kind == "gmu":
        return {"w_in": _normal(k[0], (d, di), d, BF16),
                "w_out": _normal(k[1], (di, d), di, BF16)}
    q_out, kv_out = heads * hd, kv * hd
    out = {
        "wo": _normal(k[0], (q_out, d), q_out, BF16),
        "bo": _bias(k[1], d),
        "subln": _norm_scale(k[2], 2 * hd),
        **{name: 0.1 * jax.random.normal(k[3 + j], (hd,), jnp.float32)
           for j, name in enumerate(("lq1", "lk1", "lq2", "lk2"))},
    }
    if kind == "cross":
        out["wq"] = _normal(k[7], (d, q_out), d, BF16)
        out["bq"] = _bias(k[8], q_out)
    else:
        out["wqkv"] = _normal(k[7], (d, q_out + 2 * kv_out), d, BF16)
        out["bqkv"] = _bias(k[8], q_out + 2 * kv_out)
    return out


@functools.partial(jax.jit, static_argnames=("kind", "dims"))
def _sambay_layer(key, kind, dims):
    d, ff = dims[:2]
    k = jax.random.split(key, 5)
    return {
        "ln_mix": _norm(k[0], d),
        "mixer": _mixer(k[1], kind, dims),
        "ln_mlp": _norm(k[2], d),
        "mlp": {"w1": _normal(k[3], (d, 2 * ff), d, BF16),
                "w2": _normal(k[4], (ff, d), ff, BF16)},
    }


@functools.partial(jax.jit, static_argnames=("dims",))
def _sambay_ends(key, dims):
    d, vocab = dims[0], dims[5]
    k = jax.random.split(key)
    return {"embed": _normal(k[0], (vocab, d), d, BF16),
            "ln_f": _norm(k[1], d)}


def sambay_layer(config, seed, index):
    """Layer ``index``'s weights; every layer of a kind from one program."""
    return _sambay_layer(seed_key(seed, 2000 + index),
                         layer_kind(config, index), _dims(config))


def sambay_ends(config, seed):
    """The embedding, which is also the head, and the last norm."""
    return _sambay_ends(seed_key(seed, 1999), _dims(config))


def sambay_params(config, seed):
    """The served model's tree."""
    ends = sambay_ends(config, seed)
    return {"embed": ends["embed"], "ln_f": ends["ln_f"],
            "layers": [sambay_layer(config, seed, i)
                       for i in range(config["num_hidden_layers"])]}
