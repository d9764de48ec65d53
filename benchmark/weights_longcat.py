"""Weights of a ``longcat_flash`` configuration from ``--seed``, made on the
device a double layer at a time by one compiled program.  The served model
and the plain reference are handed arrays made by the same program, as with
``weights.lm_layer``.  The tree is the one that ``configs/longcat-flash-omni-
ep32-d4.json`` describes under ``assumed``: [in, out] matrices, bf16; a
double layer holds two attention sublayers in ``weights_axk1``'s layout,
two dense feed-forwards, and the mixture of experts: a router over all of
the deployment's routed experts and zero slots
(``deployment.router_slots``), its selection bias, and the experts this chip
holds (``n_routed_experts`` of them).

Fan-in scaled normals, but for the feed-forward's down projections (dense
and routed) at ``DOWN_GAIN`` times that, as ``weights_axk1`` has them and
for its reason: with every matrix at its fan-in scale a stream collapses to
one direction within a few layers and greedy decoding repeats one token.
The selection bias (``e_score_correction_bias``, float32) is a normal over
the slots at one over their number, the mean of the router's probabilities
and about their spread under these weights: near the twelfth pick of a
token it reorders the slots, so it changes picks."""

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import _norm_scale, _normal, seed_key

BF16 = jnp.bfloat16
DOWN_GAIN = 4.0  # of the down projections over the fan-in scale


def _dims(config):
    share = config["deployment"]
    return (config["hidden_size"], config["num_attention_heads"],
            config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["ffn_hidden_size"],
            config["expert_ffn_hidden_size"],
            share["router_experts"] + config["zero_expert_num"],
            config["n_routed_experts"], config["vocab_size"])


def _attention(k, dims):
    d, heads, q_rank, lora, nope, rope, vd = dims[:7]
    return {
        "ln": _norm_scale(k[0], d),
        "w_qa": _normal(k[1], (d, q_rank), d, BF16),
        "ln_q": _norm_scale(k[2], q_rank),
        "w_qb": _normal(k[3], (q_rank, heads * (nope + rope)), q_rank, BF16),
        "w_kva": _normal(k[4], (d, lora + rope), d, BF16),
        "ln_kv": _norm_scale(k[5], lora),
        "w_uk": _normal(k[6], (heads, nope, lora), lora, BF16),
        "w_uv": _normal(k[7], (heads, lora, vd), lora, BF16),
        "w_o": _normal(k[8], (heads * vd, d), heads * vd, BF16),
    }


def _mlp(k, d, ff):
    return {"ln": _norm_scale(k[0], d),
            "w_gate_up": _normal(k[1], (d, 2 * ff), d, BF16),
            "w_down": _normal(k[2], (ff, d), ff / DOWN_GAIN ** 2, BF16)}


@functools.partial(jax.jit, static_argnames=("dims",))
def _longcat_layer(key, dims):
    d, ff_dense, ff, slots, held = dims[0], dims[7], dims[8], dims[9], dims[10]
    k = jax.random.split(key, 30)
    return {
        "attn": [_attention(k[0:9], dims), _attention(k[9:18], dims)],
        "mlp": [_mlp(k[18:21], d, ff_dense), _mlp(k[21:24], d, ff_dense)],
        "moe": {
            "router": _normal(k[24], (d, slots), d, BF16),
            "bias": jax.random.normal(k[25], (slots,), jnp.float32) / slots,
            "w_gate_up": _normal(k[26], (held, d, 2 * ff), d, BF16),
            "w_down": _normal(k[27], (held, ff, d), ff / DOWN_GAIN ** 2,
                              BF16),
        },
    }


@functools.partial(jax.jit, static_argnames=("dims",))
def _longcat_ends(key, dims):
    d, vocab = dims[0], dims[-1]
    k = jax.random.split(key, 3)
    return {"embed": _normal(k[0], (vocab, d), d, BF16),
            "ln_f": _norm_scale(k[1], d),
            "head": _normal(k[2], (vocab, d), d, BF16)}


def longcat_layer(config, seed, index):
    """Double layer ``index``'s weights."""
    return _longcat_layer(seed_key(seed, 4000 + index), _dims(config))


def longcat_ends(config, seed):
    """The held rows of the embedding and of the (untied) head, and the
    last norm."""
    return _longcat_ends(seed_key(seed, 3999), _dims(config))


def longcat_params(config, seed):
    """The served model's tree."""
    return dict(longcat_ends(config, seed),
                layers=[longcat_layer(config, seed, i)
                        for i in range(config["num_layers"])])
