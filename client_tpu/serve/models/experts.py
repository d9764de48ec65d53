"""A mixture-of-experts feed-forward layer as one chip of an expert-parallel
deployment runs it: the layer is TOLD which of the routed experts it holds.

The router keeps its published width: every token is scored over ALL the
experts (float32: a sigmoid of each logit, or where a family's configuration
asks for it a softmax over them), its ``top_k`` largest are its picks, and
their weights are normalised over the picks.  Of the (token, pick) pairs, only
those that fall on a HELD expert are computed here; what the absent experts
would add is another chip's part of the sum and is left out (on one chip
the layer runs without its exchange, and nothing stands in for the absent
chips).  Nothing is dropped under imbalance: the sorted buffer has the
static worst-case shape of every pair, and the grouped product
(``client_tpu.ops.grouped_matmul``) does work for the rows that are there,
reading no matrix of a held expert that no pair hit.  The shared experts,
which every chip computes alike, are one dense product over the stacked
ones, averaged (one shared expert's mean is the expert: a family that adds
its single shared expert, ``axk1``, gets that from the same code).

``Ffn(h) = scale * sum over held picks of w_k E_k(h) + (1 / n_shared) sum_j
S_j(h)`` with ``E(h) = W_down(silu(W_gate h) * W_up h)``; ``scale`` is a
family's routed scaling factor, and without one nothing is multiplied.

Three options serve a router of the ``longcat_flash`` kind, and without them
nothing is traced that was not before: a selection ``bias`` over the router's
slots, added to the scores to choose the picks while the weights stay the
scores; ``normalize=False``, the picks' weights left as scored; and ``n_zero``
zero-compute slots after the routed experts, whose picks return their input:
a zero pick never enters the sorted buffer (it is masked as a pair of an
absent expert is), and every chip adds ``scale * w * h`` for its own rows in
float32, as it would a shared expert.

The layer returns, beside its output, three int32 counts that only the
device knows: how many held experts had a row, how many pairs fell on held
experts, and the busiest held expert's rows; with zero slots a fourth, the
zero picks of real rows.
"""

import jax
import jax.numpy as jnp
from jax import lax

from client_tpu.ops.grouped_matmul import grouped_matmul


def init_params(key, d_model, d_ff, n_experts, n_held, n_shared, dtype):
    """The layer's tree: ``router`` [D, n_experts] over ALL experts;
    ``w_gate_up`` [held, D, 2F] (an expert's gate columns, then its up
    columns) and ``w_down`` [held, F, D] for the held experts, in the order
    the layer is told them; ``shared_gate_up`` [D, 2 * n_shared * F] (every
    shared expert's gate columns, expert after expert, then every up) and
    ``shared_down`` [n_shared * F, D]."""
    k = jax.random.split(key, 5)

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, dtype) * float(fan_in ** -0.5)

    return {
        "router": dense(k[0], (d_model, n_experts), d_model),
        "w_gate_up": dense(k[1], (n_held, d_model, 2 * d_ff), d_model),
        "w_down": dense(k[2], (n_held, d_ff, d_model), d_ff),
        "shared_gate_up": dense(k[3], (d_model, 2 * n_shared * d_ff), d_model),
        "shared_down": dense(k[4], (n_shared * d_ff, d_model), d_ff),
    }


def route(h, router, top_k, score="sigmoid", bias=None, normalize=True):
    """(picks [T, top_k] int32 over all the router's slots, weights [T,
    top_k] float32): float32 scores, the ``top_k`` largest, normalised over
    the picks.  ``score`` is the family's: ``"sigmoid"`` of each logit, or
    ``"softmax"`` over all the slots' (the probabilities, so that the
    picks' weights are ``p_k`` over the sum of the picked ``p``).  A
    ``bias`` [slots] chooses the picks as the largest of ``scores + bias``
    and leaves their weights the scores; ``normalize=False`` leaves the
    weights unnormalised.  The logits' product accumulates in float32 from
    operands as stored (bf16 products are exact in float32)."""
    logits = jnp.matmul(h, router, preferred_element_type=jnp.float32)
    scores = {"sigmoid": jax.nn.sigmoid,
              "softmax": jax.nn.softmax}[score](logits)
    if bias is None:
        top, picks = lax.top_k(scores, top_k)
    else:
        _, picks = lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, picks, axis=-1)
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return picks.astype(jnp.int32), top


def _swiglu(gate_up):
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(gate) * up


ROW_TILE = 16  # the sorted buffer's rows come in whole sublane tiles (bf16)


def routed(h, layer, held, top_k, real, scale=None, score="sigmoid",
           bias=None, normalize=True, n_zero=0):
    """The held experts' part of the routed sum for ``h`` [T, D]: float32
    [T, D], and the counts (experts hit, rows, busiest expert's rows; with
    ``n_zero`` the zero picks of real rows).  ``real`` [T] masks rows that
    are no token (a tick's idle lanes, a chunk's padding): they route
    nowhere.  ``scale``, where a family has a routed scaling factor,
    multiplies the picks' weights; ``score``, ``bias`` and ``normalize`` are
    ``route``'s.  ``n_zero`` slots follow the router's routed experts
    (``layer["router"]``'s last columns): a pick of one adds its weight
    times the row.

    The pairs are sorted by held expert (absent ones last) into a buffer of
    all T x top_k pairs; ``group_sizes`` says how many rows each held
    expert has; the two grouped products touch those rows alone.  Each
    token then takes its own pairs' results back through the inverse of the
    sort, a gather, and adds them in float32 under its weights: the same
    sum a scatter-add by token would give, without its serial adds."""
    t = h.shape[0]
    n_slots = layer["router"].shape[-1]
    n_held = len(held)
    picks, weights = route(h, layer["router"], top_k, score, bias, normalize)
    if scale is not None:
        weights = weights * scale
    # a pick's place among the held experts; n_held: held elsewhere, or a
    # zero slot, which no expert computes
    local = jnp.full((n_slots,), n_held, jnp.int32).at[
        jnp.asarray(held, jnp.int32)].set(jnp.arange(n_held, dtype=jnp.int32))
    group = jnp.where(real[:, None], local[picks], n_held).reshape(-1)
    pairs = t * top_k
    group = jnp.pad(group, (0, -pairs % ROW_TILE), constant_values=n_held)
    order = jnp.argsort(group, stable=True)
    group_sizes = jnp.bincount(group, length=n_held + 1)[:n_held].astype(
        jnp.int32)
    rows = jnp.take(h, order // top_k, axis=0, mode="clip")  # by expert
    act = _swiglu(grouped_matmul(rows, layer["w_gate_up"], group_sizes))
    out = grouped_matmul(act.astype(h.dtype), layer["w_down"], group_sizes)
    # where each pair's row went: the rows of absent pairs were never
    # written, so they are masked, not multiplied by zero
    back = jnp.argsort(order)[:pairs].reshape(t, top_k)
    mine = jnp.where((group[:pairs] < n_held).reshape(t, top_k, 1),
                     jnp.take(out, back, axis=0).astype(jnp.float32), 0.0)
    counts = jnp.stack([jnp.sum(group_sizes > 0), jnp.sum(group_sizes),
                        jnp.max(group_sizes)]).astype(jnp.int32)
    out = jnp.sum(mine * weights[:, :, None], axis=1)
    if not n_zero:
        return out, counts
    zero = (picks >= n_slots - n_zero) & real[:, None]
    w_zero = jnp.sum(jnp.where(zero, weights, 0.0), axis=1, keepdims=True)
    return (out + w_zero * h.astype(jnp.float32),
            jnp.concatenate([counts, jnp.sum(zero, dtype=jnp.int32)[None]]))


def shared(h, layer, n_shared):
    """The mean of the shared experts for ``h`` [T, D], float32: one
    gate-and-up product over the stacked experts, one down product over
    their stacked hidden rows (which sums the experts), over their number."""
    act = _swiglu(h @ layer["shared_gate_up"])
    return jnp.matmul(act, layer["shared_down"],
                      preferred_element_type=jnp.float32) / n_shared


def ffn(h, layer, held, top_k, n_shared, real, scale=None):
    """``routed + shared`` for ``h`` [T, D]: (float32 [T, D], counts)."""
    out, counts = routed(h, layer, held, top_k, real, scale)
    return out + shared(h, layer, n_shared), counts
