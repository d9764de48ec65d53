"""One lane's token choice on the device, shared by every LM program the
engine jits (``serve/lm/engine.py`` and the model families' own ticks)."""

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
