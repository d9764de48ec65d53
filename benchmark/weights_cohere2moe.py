"""Weights of a ``cohere2_moe`` configuration from ``--seed``, made on the
device a layer at a time by one compiled program.  The served model and the
plain reference are handed arrays made by the same program, as with
``weights.lm_layer``.  The tree is the one that ``configs/command-a-plus-05-
2026-ep8-d4.json`` describes under ``assumed``: [in, out] matrices, bf16; a
layer holds the experts this chip holds (``num_experts`` of them) under a
router over all of the deployment's (``deployment.router_experts``).

Fan-in scaled normals, but for the experts' down projections at
``DOWN_GAIN`` times that.  With every matrix at its fan-in scale, attention
passes on what a lane's keys share and averages away what tells them apart:
within four layers a lane's stream is one direction, greedy decoding repeats
one token (3% of a stream's tokens distinct), the lane's picks among the
experts never change, and how many held experts a tick hits, and with it the
tick's time, follows from the seed (PERF.md section 6, PR 33).  With the
feed-forward's part at four times the rest the stream stays a token's own:
a stream's tokens vary and the router's picks spread evenly, as a trained
model's do, for every seed alike."""

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import _norm_scale, _normal, seed_key

BF16 = jnp.bfloat16
DOWN_GAIN = 4.0  # of the experts' down projections over the fan-in scale


def _dims(config):
    return (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["vocab_size"],
            config["deployment"]["router_experts"], config["num_experts"],
            config["num_shared_experts"])


@functools.partial(jax.jit, static_argnames=("dims",))
def _cohere2moe_layer(key, dims):
    d, ff, heads, kv, hd, _, routed, held, n_shared = dims
    k = jax.random.split(key, 8)
    return {
        "ln": _norm_scale(k[0], d),
        "wqkv": _normal(k[1], (d, (heads + 2 * kv) * hd), d, BF16),
        "wo": _normal(k[2], (heads * hd, d), heads * hd, BF16),
        "ffn": {
            "router": _normal(k[3], (d, routed), d, BF16),
            "w_gate_up": _normal(k[4], (held, d, 2 * ff), d, BF16),
            "w_down": _normal(k[5], (held, ff, d), ff / DOWN_GAIN ** 2, BF16),
            "shared_gate_up": _normal(k[6], (d, 2 * n_shared * ff), d, BF16),
            "shared_down": _normal(k[7], (n_shared * ff, d),
                                   ff / DOWN_GAIN ** 2, BF16),
        },
    }


@functools.partial(jax.jit, static_argnames=("dims",))
def _cohere2moe_ends(key, dims):
    d, vocab = dims[0], dims[5]
    k = jax.random.split(key)
    return {"embed": _normal(k[0], (vocab, d), d, BF16),
            "ln_f": _norm_scale(k[1], d)}


def cohere2moe_layer(config, seed, index):
    """Layer ``index``'s weights; every layer from one program."""
    return _cohere2moe_layer(seed_key(seed, 2000 + index), _dims(config))


def cohere2moe_ends(config, seed):
    """The held rows of the embedding, which are also the head, and the
    last norm."""
    return _cohere2moe_ends(seed_key(seed, 1999), _dims(config))


def cohere2moe_params(config, seed):
    """The served model's tree."""
    ends = cohere2moe_ends(config, seed)
    return {"embed": ends["embed"], "ln_f": ends["ln_f"],
            "layers": [cohere2moe_layer(config, seed, i)
                       for i in range(config["num_hidden_layers"])]}
