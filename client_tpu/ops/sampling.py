"""One lane's token choice on the device, shared by every LM program a
model family hands ``serve/lm.LmEngine``: ``select_token`` for a decode
tick or a prefill chunk, ``accept_lane`` for a speculative verify tick."""

import jax
import jax.numpy as jnp
from jax import lax

# static cap for the per-lane top-k filter (per-lane k is dynamic below it)
TOPK_CAP = 64


def select_token(logits, key, temperature, top_k):
    """Argmax when temperature == 0, else temperature softmax sampling over
    the top-k filtered logits (top_k <= 0 = unfiltered)."""
    greedy = jnp.argmax(logits)
    kmax = min(TOPK_CAP, logits.shape[-1])
    vals = lax.top_k(logits, kmax)[0]
    thresh = vals[jnp.clip(top_k - 1, 0, kmax - 1)]
    keep = (top_k <= 0) | (logits >= thresh)
    filtered = jnp.where(keep, logits, -jnp.inf)
    sampled = jax.random.categorical(
        key, filtered / jnp.maximum(temperature, 1e-6)
    )
    return jnp.where(temperature > 0.0, sampled, greedy).astype(jnp.int32)


def accept_lane(logits, props, count, temp, top_k, keys, *, width):
    """One lane's speculative acceptance rule on device.

    ``logits`` [w, V] are the target model's scores at positions
    ``length .. length + w - 1`` (position j scores the token FOLLOWING
    ``seq[j]``), ``props`` [w - 1] the drafted tokens (``props[j]`` is
    the proposal for what position j generates), ``count`` how many are
    real, ``keys`` [w + 1, 2] this lane's per-position RNG subkeys.

    Greedy lanes (temperature 0) accept a draft iff it equals the
    argmax — the accepted prefix + the argmax correction reconstructs
    plain greedy decode byte-exactly.  Temperature lanes run rejection
    sampling for a point-mass proposal: accept draft ``x`` with
    probability ``p(x)`` under the lane's filtered/tempered target
    distribution (the exact `select_token` distribution), and on
    rejection sample the correction from the residual (``p`` with
    ``x``'s mass removed, renormalized) — the delivered tokens are an
    exact draw from the target distribution.  When every draft is
    accepted the correction is a free "bonus" sample from the last
    position's full distribution.

    Returns (n_accepted, correction_token).
    """
    w = width
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)  # [w]
    kmax = min(TOPK_CAP, vocab)
    vals = lax.top_k(logits, kmax)[0]
    thresh = vals[:, jnp.clip(top_k - 1, 0, kmax - 1)]
    keep = (top_k <= 0) | (logits >= thresh[:, None])
    scaled = jnp.where(keep, logits, -jnp.inf) / jnp.maximum(temp, 1e-6)
    probs = jax.nn.softmax(scaled, axis=-1)  # [w, V] target distribution
    j = jnp.arange(w - 1)
    p_draft = probs[j, props]
    u = jax.vmap(jax.random.uniform)(keys[:w - 1])
    accept = jnp.where(temp > 0.0, u < p_draft, props == greedy[:w - 1])
    # longest accepted prefix of the REAL drafts (cumprod stops at the
    # first rejection; padding past ``count`` never counts)
    chain = jnp.cumprod(
        jnp.where(j < count, accept, False).astype(jnp.int32)
    )
    n_acc = jnp.sum(chain).astype(jnp.int32)
    rejected = n_acc < count
    rej_tok = props[jnp.minimum(n_acc, w - 2)]
    corr_scaled = jnp.where(
        rejected & (jnp.arange(vocab) == rej_tok), -jnp.inf,
        scaled[n_acc],
    )
    sampled = jax.random.categorical(keys[w - 1], corr_scaled)
    corr = jnp.where(temp > 0.0, sampled, greedy[n_acc])
    return n_acc, corr.astype(jnp.int32)
