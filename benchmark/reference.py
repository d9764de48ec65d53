"""Plain references: float32 ``jax.numpy`` forwards of ResNet-50 and of the
decoder, written from the published descriptions.  They take the weights as
data, import nothing of the program, have no kernels, cache or batching, and
run in blocks (of rows, or a layer at a time) so that they fit beside
nothing else on the chip.

``quant`` is the hook of the low-precision control: applied to both operands
of every matrix product or convolution.  ``None`` is the reference itself;
``fp8`` is the nearest precision below the bf16 that the configurations
state, with one scale to a tensor, as an fp8 deployment would run it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def fp8(x):
    """Round to float8 e4m3 under one scale for the tensor, and back."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _q(quant, x):
    return x if quant is None else quant(x)


# -- ResNet-50 v1.5 ------------------------------------------------------------

def _same(size, k, stride):
    """SAME padding as the configuration's file states it: the output is
    ceil(size / stride) and an odd total pad puts the extra at the end."""
    total = max((-(-size // stride) - 1) * stride + k - size, 0)
    return (total // 2, total - total // 2)


def _conv(x, w, stride, quant):
    k = w.shape[-1]
    pads = [_same(x.shape[2], k, stride), _same(x.shape[3], k, stride)]
    return lax.conv_general_dilated(
        _q(quant, x), _q(quant, w), (stride, stride), pads,
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST,
    )


def _max_pool(x):
    pads = [(0, 0), (0, 0), _same(x.shape[2], 3, 2), _same(x.shape[3], 3, 2)]
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                             pads)


@functools.partial(jax.jit, static_argnames=("strides", "quant"))
def _resnet50(params, x, strides, quant):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    h = jax.nn.relu(_conv(x, p["stem"], 2, quant) * p["stem_scale"])
    h = _max_pool(h)
    for stage, first_stride in zip(p["stages"], strides):
        for b, block in enumerate(stage):
            stride = first_stride if b == 0 else 1
            y = jax.nn.relu(_conv(h, block["w1"], 1, quant) * block["s1"])
            y = jax.nn.relu(_conv(y, block["w2"], stride, quant) * block["s2"])
            y = _conv(y, block["w3"], 1, quant) * block["s3"]
            skip = (_conv(h, block["proj"], stride, quant)
                    if "proj" in block else h)
            h = jax.nn.relu(y + skip)
    pooled = jnp.mean(h, axis=(2, 3))
    return jnp.matmul(_q(quant, pooled), _q(quant, p["head_w"]),
                      precision=HIGHEST) + p["head_b"]


def resnet50_scores(config, params, rows, quant=None):
    """float32 scores [n, classes] of float32 rows [n, C, H, W]; call it with
    a block of rows at a time."""
    strides = tuple(s for _, _, s in config["stages"])
    return _resnet50(params, rows, strides, quant)


# -- the decoder ---------------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding over [S, T, heads, hd], the halves convention of the
    family's published code: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)."""
    t, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(quant, a, b):
    return jnp.matmul(_q(quant, a), _q(quant, b), precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _decoder_layer(x, layer, dims, quant):
    heads, kv, hd, eps, theta = dims
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layer)
    s, t, _ = x.shape
    h = _rms_norm(x, w["ln_attn"], eps)
    q = _rope(_mm(quant, h, w["attn"]["wq"]).reshape(s, t, heads, hd), theta)
    k = _rope(_mm(quant, h, w["attn"]["wk"]).reshape(s, t, kv, hd), theta)
    v = _mm(quant, h, w["attn"]["wv"]).reshape(s, t, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    scores = jnp.einsum("sqhd,skhd->shqk", _q(quant, q), _q(quant, k),
                        precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    mixed = jnp.einsum("shqk,skhd->sqhd", _q(quant, probs), _q(quant, v),
                       precision=HIGHEST)
    x = x + _mm(quant, mixed.reshape(s, t, heads * hd), w["attn"]["wo"])
    h = _rms_norm(x, w["ln_mlp"], eps)
    gate = jax.nn.silu(_mm(quant, h, w["mlp"]["w_gate"]))
    up = _mm(quant, h, w["mlp"]["w_up"])
    return x + _mm(quant, gate * up, w["mlp"]["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _decoder_head(x, ln_f, lm_head, eps, quant):
    h = _rms_norm(x, ln_f.astype(jnp.float32), eps)
    return _mm(quant, h, lm_head.astype(jnp.float32))


def decoder_logits(config, tokens, at, ends, layer_of, quants=(None,),
                   block_rows=4):
    """float32 logits [S, N, vocab] at the positions ``at`` [S, N] of int32
    ``tokens`` [S, T], one array for each entry of ``quants``.  ``ends``
    holds the embedding, the last norm and the head; ``layer_of(i)`` gives
    layer i's weights, so that one layer is on the device at a time, and the
    rows pass it ``block_rows`` at a time."""
    dims = (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["rms_norm_eps"], config["rope_theta"])
    blocks = [slice(i, i + block_rows)
              for i in range(0, tokens.shape[0], block_rows)]
    embedded = [jnp.take(ends["embed"], tokens[b], axis=0).astype(jnp.float32)
                for b in blocks]
    xs = [list(embedded) for _ in quants]
    for i in range(config["num_hidden_layers"]):
        layer = layer_of(i)
        xs = [[_decoder_layer(x, layer, dims, quant) for x in rows]
              for rows, quant in zip(xs, quants)]
        del layer
    out = []
    for rows, quant in zip(xs, quants):
        x = jnp.concatenate(rows, axis=0)
        wanted = jnp.take_along_axis(x, jnp.asarray(at)[:, :, None], axis=1)
        out.append(_decoder_head(wanted, ends["ln_f"], ends["lm_head"],
                                 config["rms_norm_eps"], quant))
    return out
