"""Weights and input rows from ``--seed``, made on the device by the
benchmark.  The program is handed these arrays and the plain reference is
handed the same ones: neither side makes its own."""

import functools

import jax
import jax.numpy as jnp


def seed_key(seed, stream):
    """A key for ``seed`` (any whole number up to 2**63) and a stream id."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), stream)


def _normal(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) * float(fan_in ** -0.5)


def _norm_scale(key, d):
    """A norm's scale: about 1, so that a reference that drops it shows."""
    return (1.0 + 0.1 * jax.random.normal(key, (d,))).astype(jnp.bfloat16)


# -- ResNet-50 ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("stages", "shape"))
def _resnet50(key, stages, shape):
    in_ch, stem, expansion, classes = shape
    keys = iter(jax.random.split(key, 512))
    bf16 = jnp.bfloat16

    def he(shp, fan_in):
        return jax.random.normal(next(keys), shp, bf16) * float(
            (2.0 / fan_in) ** 0.5)

    def scale(ch, lo, hi):
        return jax.random.uniform(next(keys), (ch, 1, 1), jnp.float32,
                                  lo, hi).astype(bf16)

    params = {"stem": he((stem, in_ch, 7, 7), in_ch * 49),
              "stem_scale": scale(stem, 0.5, 1.0), "stages": []}
    prev = stem
    for mid, blocks, first_stride in stages:
        out, stage = mid * expansion, []
        for b in range(blocks):
            stride = first_stride if b == 0 else 1
            block = {
                "w1": he((mid, prev, 1, 1), prev), "s1": scale(mid, 0.5, 1.0),
                "w2": he((mid, mid, 3, 3), mid * 9), "s2": scale(mid, 0.5, 1.0),
                "w3": he((out, mid, 1, 1), mid), "s3": scale(out, 0.2, 0.4),
            }
            if prev != out or stride != 1:
                block["proj"] = he((out, prev, 1, 1), prev)
            stage.append(block)
            prev = out
        params["stages"].append(stage)
    params["head_w"] = he((prev, classes), prev)
    params["head_b"] = (0.1 * jax.random.normal(next(keys), (classes,))
                        ).astype(bf16)
    return params


def resnet50_params(config, seed):
    """The whole tree in one jitted call, bf16, in the layout that
    ``configs/resnet50-224.json`` describes: OIHW convolutions, a [C,1,1]
    scale after each, the head as [features, classes]."""
    return _resnet50(
        seed_key(seed, 1), tuple(tuple(s) for s in config["stages"]),
        (config["in_channels"], config["stem_channels"], config["expansion"],
         config["num_classes"]),
    )


@functools.partial(jax.jit, static_argnames=("shape",))
def _rows(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def rows(seed, index, shape):
    """Standard-normal float32 rows number ``index`` of this seed."""
    return _rows(seed_key(seed, 1000 + index), tuple(shape))


# -- the decoder ---------------------------------------------------------------

def _dims(config):
    return (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["vocab_size"])


@functools.partial(jax.jit, static_argnames=("dims",))
def _lm_layer(key, dims):
    d, ff, heads, kv, hd, _ = dims
    k = jax.random.split(key, 9)
    bf16 = jnp.bfloat16

    return {
        "attn": {
            "wq": _normal(k[0], (d, heads * hd), d, bf16),
            "wk": _normal(k[1], (d, kv * hd), d, bf16),
            "wv": _normal(k[2], (d, kv * hd), d, bf16),
            "wo": _normal(k[3], (heads * hd, d), heads * hd, bf16),
        },
        "ln_attn": _norm_scale(k[4], d),
        "ln_mlp": _norm_scale(k[5], d),
        "mlp": {
            "w_gate": _normal(k[6], (d, ff), d, bf16),
            "w_up": _normal(k[7], (d, ff), d, bf16),
            "w_down": _normal(k[8], (ff, d), ff, bf16),
        },
    }


@functools.partial(jax.jit, static_argnames=("dims",))
def _lm_ends(key, dims):
    d, _, _, _, _, vocab = dims
    k = jax.random.split(key, 3)
    return {
        "embed": _normal(k[0], (vocab, d), d, jnp.bfloat16),
        "ln_f": _norm_scale(k[1], d),
        "lm_head": _normal(k[2], (d, vocab), d, jnp.bfloat16),
    }


def lm_layer(config, seed, index):
    """One layer's weights, bf16.  One compiled program makes every layer, for
    the served model and for the reference alike, so both see the same bits
    without either holding the other's arrays."""
    return _lm_layer(seed_key(seed, 2000 + index), _dims(config))


def lm_ends(config, seed):
    """The embedding, the last norm and the untied head."""
    return _lm_ends(seed_key(seed, 1999), _dims(config))


def lm_params(config, seed):
    """The served model's tree: [in, out] matrices, as the config's family
    multiplies them (x @ w)."""
    ends = lm_ends(config, seed)
    return {
        "embed": ends["embed"],
        "layers": [lm_layer(config, seed, i)
                   for i in range(config["num_hidden_layers"])],
        "ln_f": ends["ln_f"],
        "lm_head": ends["lm_head"],
    }
