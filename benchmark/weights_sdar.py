"""Weights of an ``sdar_moe`` configuration from ``--seed``, made on the
device a layer at a time by one compiled program.  The served model and the
plain reference are handed arrays made by the same program, as with
``weights.lm_layer``.  The tree is the one that ``configs/sdar-30b-a3b-chat-
d7.json`` describes under ``assumed``: [in, out] matrices, bf16; a layer
holds every expert of its router (``num_experts``), no shared one; the head
is untied.

Fan-in scaled normals, but for the experts' down projections at
``DOWN_GAIN`` times that, as ``weights_cohere2moe`` has them and for its
reason (PERF.md section 6, PR 33, measured again for this family in PR 39):
with every matrix at its fan-in scale a lane's stream collapses to one
direction within a few layers, decoding repeats one token and the router's
picks never change; with the feed-forward's part at four times the rest a
stream's tokens vary and the picks spread, for every seed alike."""

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import _norm_scale, _normal, seed_key

BF16 = jnp.bfloat16
DOWN_GAIN = 4.0  # of the experts' down projections over the fan-in scale


def _dims(config):
    return (config["hidden_size"], config["moe_intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["vocab_size"], config["num_experts"])


@functools.partial(jax.jit, static_argnames=("dims", "down_gain"))
def _sdar_layer(key, dims, down_gain):
    d, ff, heads, kv, hd, _, n_experts = dims
    k = jax.random.split(key, 9)
    return {
        "ln_attn": _norm_scale(k[0], d),
        "wqkv": _normal(k[1], (d, (heads + 2 * kv) * hd), d, BF16),
        "q_norm": _norm_scale(k[2], hd),
        "k_norm": _norm_scale(k[3], hd),
        "wo": _normal(k[4], (heads * hd, d), heads * hd, BF16),
        "ln_mlp": _norm_scale(k[5], d),
        "ffn": {
            "router": _normal(k[6], (d, n_experts), d, BF16),
            "w_gate_up": _normal(k[7], (n_experts, d, 2 * ff), d, BF16),
            "w_down": _normal(k[8], (n_experts, ff, d), ff / down_gain ** 2,
                              BF16),
        },
    }


@functools.partial(jax.jit, static_argnames=("dims",))
def _sdar_ends(key, dims):
    d, vocab = dims[0], dims[5]
    k = jax.random.split(key, 3)
    return {"embed": _normal(k[0], (vocab, d), d, BF16),
            "ln_f": _norm_scale(k[1], d),
            "lm_head": _normal(k[2], (d, vocab), d, BF16)}


def sdar_layer(config, seed, index, down_gain=DOWN_GAIN):
    """Layer ``index``'s weights; every layer from one program."""
    return _sdar_layer(seed_key(seed, 3000 + index), _dims(config),
                       float(down_gain))


def sdar_ends(config, seed):
    """The embedding, the last norm and the untied head."""
    return _sdar_ends(seed_key(seed, 2999), _dims(config))


def sdar_params(config, seed, down_gain=DOWN_GAIN):
    """The served model's tree."""
    ends = sdar_ends(config, seed)
    return {**ends, "layers": [sdar_layer(config, seed, i, down_gain)
                               for i in range(config["num_hidden_layers"])]}
