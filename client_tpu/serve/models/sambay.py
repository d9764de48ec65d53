"""SambaY decoder-hybrid-decoder LM (arXiv:2507.06607; the family of
``Phi-4-mini-flash-reasoning``, ``model_type`` ``phi4flash``) as a second
family that ``serve/lm.LmEngine`` serves.

Layer ``i`` of ``n_layers`` is ``x += mixer(LN(x)); x += MLP(LN(x))`` with
LayerNorm (scale and bias) and a SwiGLU MLP of one fused gate-and-up
matrix.  The mixer's kind follows from ``n_layers`` and ``mb_per_layer``
(:meth:`SambaYConfig.layer_kind`):

========  ================================================  ==================
kind      mixer                                             per-lane state
========  ================================================  ==================
mamba     Mamba-1 selective scan                            conv tail, SSM
window    differential attention over the last ``window``   a ring of K and V
          keys
memory    a Mamba-1 layer that also hands on its scan       conv tail, SSM
          output ``m`` (before the ``z`` gate)
full      differential attention over the whole context     paged K/V (the
                                                            model's only pool)
gmu       gated memory unit ``W_out(silu(W_in h) * m)``     none
cross     differential cross attention: own ``W_q``/``W_o``  none (reads the
          over the ``full`` layer's keys and values         full layer's pool)
========  ================================================  ==================

There is no rotary embedding: the Mamba layers carry position.  The head is
the embedding, multiplied as it is stored.

The family's step is written once, over a cache view, for both shapes the
engine runs: ``decode_step`` at (n, 1) and ``prefill_step`` at (1, C).  A
lane's fixed state (``SambaYConfig.state_spec``) is allocated by ``kv.KvBlockPool``
beside the paged pool; the programs ``sambay_decode_tick`` and
``sambay_prefill_chunk`` are what ``SambaYPrograms`` at the bottom jits for
the engine, under those names, so that a device trace tells them apart.

Masking is what keeps the state right: a prefill bucket's padding past
``prompt_len`` and a decode tick's idle lanes leave every state as it was
(``dt = 0`` in the scan, the conv tail taken at the last real position,
ring writes dropped, paged writes sent to the trash block).
"""

import dataclasses
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from client_tpu.ops.paged_decode import (
    STEP_BLOCKS, paged_decode_attention, reads_in_place, steps_read,
    tick_steps)
from client_tpu.ops.quant import matmul as _mm
from client_tpu.ops.sampling import select_token
from client_tpu.serve.prof import annotation

MAMBA, WINDOW, MEMORY, FULL, GMU, CROSS = (
    "mamba", "window", "memory", "full", "gmu", "cross")

TRASH_BLOCK = 0  # kv.KvBlockPool.TRASH: where masked paged writes land


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int = 200064
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    d_ff: int = 10240
    max_seq: int = 4096
    window: int = 512
    mb_per_layer: int = 2
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # the recurrent (SSM) state's own type: float32 as the family's code
    # keeps it, whatever the activations are
    state_dtype: str = "float32"

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError("differential attention pairs its heads: "
                             "n_kv_heads even, n_heads a multiple of it")
        if self.n_layers % 2 or self.n_layers // 2 + 2 > self.n_layers:
            raise ValueError("n_layers: an even number, 4 or more")

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def layer_kind(self, i):
        """The self-decoder is the first half: Mamba where ``i`` is a
        multiple of ``mb_per_layer``, window attention elsewhere.  The
        cross-decoder opens with the memory layer and the one full
        attention layer, then alternates GMU and cross attention."""
        half = self.n_layers // 2
        mamba_like = i % self.mb_per_layer == 0
        if i < half:
            return MAMBA if mamba_like else WINDOW
        if i == half:
            return MEMORY
        if i == half + 1:
            return FULL
        return GMU if mamba_like else CROSS

    @property
    def kinds(self):
        return tuple(self.layer_kind(i) for i in range(self.n_layers))

    def lambda_init(self, i):
        """Differential attention's ``l0`` at layer ``i``."""
        return 0.8 - 0.6 * math.exp(-0.3 * i)

    # -- what a lane owns (serve/lm/kv.py reads this) -------------------------

    @property
    def kv_row(self):
        """One position's keys (or values) as the caches hold them: a KV
        pair to a row, ``[n_kv_heads / 2, 2 * head_dim]``.  For the values
        that is differential attention's own unit (``[v_2g | v_2g+1]``);
        for the keys it puts both maps' keys of a pair side by side, and
        at head_dim 64 it makes the minor dimension 128 wide, a whole
        vector register, where [20, 64] would be padded to it.  The
        caches keep the pair OUTSIDE the positions (a block is [pairs,
        block_size, 2 hd], a ring [pairs, window, 2 hd]): the order in
        which attention's products, batched over lane and pair, read them,
        so that nothing is transposed on the way (stored position-major,
        the v5e's compiler copied every ring and the whole pool into this
        order in every tick: PERF.md section 6, PR 29)."""
        return (self.n_kv_heads // 2, 2 * self.head_dim)

    @property
    def state_spec(self):
        """(paged layers, {pool: a block's shape with None where the
        block's positions go}, {name: [(per-lane shape, dtype) a layer]}).  One
        paged K/V pool for the whole model;
        a ring of ``window`` positions a window layer; a conv tail and an
        SSM state a Mamba layer.  The SSM state is kept [d_state, d_inner]:
        d_inner on the lanes of the vector unit."""
        kinds = self.kinds
        n_win = kinds.count(WINDOW)
        n_rec = kinds.count(MAMBA) + kinds.count(MEMORY)
        pairs, wide = self.kv_row
        ring = ((pairs, self.window, wide), self.jdtype)
        block = (pairs, None, wide)
        return 1, {"k": block, "v": block}, {
            "ring_k": [ring] * n_win,
            "ring_v": [ring] * n_win,
            "conv": [((self.d_conv - 1, self.d_inner), self.jdtype)] * n_rec,
            "ssm": [((self.d_state, self.d_inner),
                     jnp.dtype(self.state_dtype))] * n_rec,
        }

    @property
    def family(self):
        """The family's programs, as ``serve/lm.LmEngine`` and
        ``language._LmRunner`` ask every configuration for them."""
        return SambaYPrograms


# -- parameters -----------------------------------------------------------------

def init_params(key, cfg):
    """A params tree with [in, out] matrices (``x @ w``), the family's own
    initialisation for the scan (``A_log = log(1..d_state)``, ``D = 1``,
    ``b_dt`` the inverse softplus of a log-uniform step in 1e-3..1e-1),
    lambda vectors normal(0, 0.1), norms at 1.  ``benchmark/
    weights_sambay.py`` makes the same tree from a seed, a layer a call."""
    dt = cfg.jdtype
    d, di, ds, hd = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.head_dim
    q_out, kv_out = cfg.n_heads * hd, cfg.n_kv_heads * hd
    keys = iter(jax.random.split(key, 16 * cfg.n_layers + 2))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, dt) * float(fan_in ** -0.5)

    def norm():
        return {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)}

    def lambdas():
        return {name: 0.1 * jax.random.normal(next(keys), (hd,), jnp.float32)
                for name in ("lq1", "lk1", "lq2", "lk2")}

    def mixer(kind):
        if kind in (MAMBA, MEMORY):
            step = jnp.exp(jax.random.uniform(
                next(keys), (di,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            return {
                "w_in": dense((d, 2 * di), d),
                "conv_w": dense((di, cfg.d_conv), cfg.d_conv),
                "conv_b": jnp.zeros((di,), dt),
                "w_x": dense((di, cfg.dt_rank + 2 * ds), di),
                "w_dt": dense((cfg.dt_rank, di), cfg.dt_rank),
                "b_dt": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))),
                "D": jnp.ones((di,), jnp.float32),
                "w_out": dense((di, d), di),
            }
        if kind == GMU:
            return {"w_in": dense((d, di), d), "w_out": dense((di, d), di)}
        out = {"wo": dense((q_out, d), q_out), "bo": jnp.zeros((d,), dt),
               "subln": jnp.ones((2 * hd,), dt), **lambdas()}
        if kind == CROSS:
            out["wq"] = dense((d, q_out), d)
            out["bq"] = jnp.zeros((q_out,), dt)
        else:
            out["wqkv"] = dense((d, q_out + 2 * kv_out), d)
            out["bqkv"] = jnp.zeros((q_out + 2 * kv_out,), dt)
        return out

    layers = [{
        "ln_mix": norm(), "mixer": mixer(kind), "ln_mlp": norm(),
        "mlp": {"w1": dense((d, 2 * cfg.d_ff), d),
                "w2": dense((cfg.d_ff, d), cfg.d_ff)},
    } for kind in cfg.kinds]
    return {"embed": dense((cfg.vocab_size, d), d), "layers": layers,
            "ln_f": norm()}


def lm_flops_per_token(cfg, context=0):
    """Model FLOPs a generated token costs, 2 a weight element of every
    matrix it meets, the tied head among them; ``context`` adds the
    attention term of the window, full and cross layers (a differential
    head's weighted sum is twice as wide as its scores: 6 x head_dim a key
    a head)."""
    d, di, ff, hd = cfg.d_model, cfg.d_inner, cfg.d_ff, cfg.head_dim
    q_out, kv_out = cfg.n_heads * hd, cfg.n_kv_heads * hd
    per_kind = {
        MAMBA: 3 * d * di + di * (cfg.dt_rank + 2 * cfg.d_state)
        + cfg.dt_rank * di,
        WINDOW: d * (q_out + 2 * kv_out) + q_out * d,
        GMU: 2 * d * di,
        CROSS: 2 * d * q_out,
    }
    per_kind[MEMORY], per_kind[FULL] = per_kind[MAMBA], per_kind[WINDOW]
    kinds = cfg.kinds
    weights = sum(per_kind[k] for k in kinds) + len(kinds) * 3 * d * ff \
        + d * cfg.vocab_size
    keys = (kinds.count(WINDOW) * min(int(context), cfg.window)
            + (1 + kinds.count(CROSS)) * int(context))
    return 2 * weights + 6 * cfg.n_heads * hd * keys


# -- the layer's parts ------------------------------------------------------------

def _layer_norm(x, ln, cfg):
    """LayerNorm of the float32 residual stream ``x``, in the activations'
    type: what the matrix products read."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + cfg.norm_eps)).astype(cfg.jdtype) \
        * ln["scale"] + ln["bias"]


def _mlp(layer, x, cfg):
    h = _layer_norm(x, layer["ln_mlp"], cfg)
    gate, up = jnp.split(_mm(h, layer["mlp"]["w1"]), 2, axis=-1)
    return x + _mm(up * jax.nn.silu(gate), layer["mlp"]["w2"]).astype(x.dtype)


def _pair_queries(q, cfg):
    """``q`` [B,T,H,hd] as a KV pair's rows meet them, [B,T,KV/2,r,2,2hd]
    with ``r = H / KV``: query heads ``2p+m`` go with KV heads ``2g+m``,
    ``g = p // r``, and a row of keys holds both maps' keys
    (``SambaYConfig.kv_row``), so map ``m``'s query is laid into its half
    of a 2hd-wide row of zeros, scaled by ``hd ** -0.5``: the product over
    the whole row is ``q_(2p+m) . k_(2g+m)`` exactly, for twice the
    multiplications of a matrix unit that a decode tick leaves idle, and
    the keys are never split or transposed."""
    b, t = q.shape[:2]
    hd = cfg.head_dim
    qg = q.reshape(b, t, cfg.n_kv_heads // 2, cfg.n_heads // cfg.n_kv_heads,
                   2, hd) * jnp.asarray(hd ** -0.5, q.dtype)
    zeros = jnp.zeros_like(qg[..., 0, :])
    return jnp.stack([jnp.concatenate([qg[..., 0, :], zeros], axis=-1),
                      jnp.concatenate([zeros, qg[..., 1, :]], axis=-1)],
                     axis=-2)


def _diff_out(a, mixer, l0, cfg):
    """The two maps' weighted sums ``a`` [B,T,KV/2,r,2,2hd] float32 (both
    maps of a pair weigh the pair's two value heads side by side) to the
    pair's output ``RMSNorm(a_0 - lambda a_1) * (1 - l0)``: float32
    [B,T,H*hd]."""
    lam = (jnp.exp(jnp.sum(mixer["lq1"] * mixer["lk1"]))
           - jnp.exp(jnp.sum(mixer["lq2"] * mixer["lk2"])) + l0)
    diff = a[..., 0, :] - lam * a[..., 1, :]          # [B,T,g,r,2hd] float32
    var = jnp.mean(diff * diff, axis=-1, keepdims=True)
    out = diff * lax.rsqrt(var + cfg.norm_eps) \
        * mixer["subln"].astype(jnp.float32) * (1.0 - l0)
    return out.reshape(a.shape[:2] + (cfg.n_heads * cfg.head_dim,))


def diff_attention(q, kk, vv, valid, mixer, l0, cfg):
    """Differential attention of ``q`` [B,T,H,hd] over keys and values
    [B,KV/2,S,2hd] (``SambaYConfig.kv_row``) that the caller has laid out
    (a gather through a block table, a lane's ring, a ring beside a
    chunk's own keys), masked by ``valid`` [B,T,S].

    As ``transformer.paged_attention`` does, the queries of a KV pair
    (:func:`_pair_queries`) are contracted against the pair's rows as they
    are stored: ONE ``dot_general`` with batch dimensions (lane, KV pair),
    float32 accumulation, no repeat and no copy.  Returns [B,T,H*hd]."""
    s = jnp.einsum("btgrme,bgse->bgrmts", _pair_queries(q, cfg), kk,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bgrmts,bgse->btgrme", p.astype(vv.dtype), vv,
                   preferred_element_type=jnp.float32)
    return _diff_out(a, mixer, l0, cfg).astype(q.dtype)


SCAN_UNROLL = 8  # scan steps a loop iteration of a prefill chunk


def selective_scan(dt, xs, bm, cm, a_t, s0):
    """``s_t = exp(dt_t A) s_(t-1) + (dt_t xs_t) B_t``, ``y_t = s_t . C_t``
    over the T axis of ``dt``, ``xs`` [B,T,di] and ``bm``, ``cm`` [B,T,ds],
    all float32, with ``a_t`` = A transposed [ds,di] and the carried state
    ``s0`` [B,ds,di].  A position with ``dt = 0`` leaves the state as it
    was.  Returns (y [B,T,di], the state after the last position)."""
    if dt.shape[1] == 1:
        s = jnp.exp(dt[:, 0, None, :] * a_t) * s0 \
            + (dt[:, 0] * xs[:, 0])[:, None, :] * bm[:, 0, :, None]
        return jnp.sum(s * cm[:, 0, :, None], axis=1)[:, None], s

    def step(s, inputs):
        dt_t, x_t, b_t, c_t = inputs
        s = jnp.exp(dt_t[:, None, :] * a_t) * s \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    # time first for the scan; SCAN_UNROLL steps a loop iteration
    s, y = lax.scan(step, s0, tuple(jnp.swapaxes(v, 0, 1)
                                    for v in (dt, xs, bm, cm)),
                    unroll=SCAN_UNROLL)
    return jnp.swapaxes(y, 0, 1), s


def _mamba(mixer, h, conv, ssm, n_real, cfg):
    """The Mamba-1 mixer over ``h`` [B,T,D] from a lane's carried state:
    ``conv`` [B,d_conv-1,di] (the inputs before the chunk) and ``ssm``
    [B,ds,di].  ``n_real`` [B] is how many of the T positions are real.
    Returns (output [B,T,D], the scan's ``y`` [B,T,di] before the gate,
    conv, ssm)."""
    t = h.shape[1]
    di, ds, k = cfg.d_inner, cfg.d_state, cfg.d_conv
    xs, z = jnp.split(_mm(h, mixer["w_in"]), 2, axis=-1)
    seen = jnp.concatenate([conv, xs], axis=1)            # [B,T+k-1,di]
    xs = sum(seen[:, j:j + t] * mixer["conv_w"][:, j] for j in range(k))
    xs = jax.nn.silu(xs + mixer["conv_b"])
    # the tail after the last real position: rows n_real .. n_real+k-2
    conv = jax.vmap(lambda rows, n: lax.dynamic_slice_in_dim(
        rows, n, k - 1, axis=0))(seen, n_real)
    dbc = _mm(xs, mixer["w_x"]).astype(jnp.float32)
    dt_r, bm, cm = jnp.split(dbc, [cfg.dt_rank, cfg.dt_rank + ds], axis=-1)
    dt = jax.nn.softplus(
        jnp.matmul(dt_r, mixer["w_dt"].astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
        + mixer["b_dt"].astype(jnp.float32))
    real = jnp.arange(t)[None, :] < n_real[:, None]
    dt = jnp.where(real[:, :, None], dt, 0.0)
    xs32 = xs.astype(jnp.float32)
    a_t = -jnp.exp(mixer["A_log"].astype(jnp.float32)).T
    y, s = selective_scan(dt, xs32, bm, cm, a_t, ssm.astype(jnp.float32))
    y = (y + mixer["D"].astype(jnp.float32) * xs32).astype(h.dtype)
    out = _mm(y * jax.nn.silu(z), mixer["w_out"])
    return out, y, conv, s.astype(ssm.dtype)


def _split_qkv(mixer, h, cfg):
    b, t = h.shape[:2]
    hd, q_out, kv_out = (cfg.head_dim, cfg.n_heads * cfg.head_dim,
                         cfg.n_kv_heads * cfg.head_dim)
    qkv = _mm(h, mixer["wqkv"]) + mixer["bqkv"]
    q, k, v = jnp.split(qkv, [q_out, q_out + kv_out], axis=-1)
    return (q.reshape(b, t, cfg.n_heads, hd),
            k.reshape((b, t) + cfg.kv_row), v.reshape((b, t) + cfg.kv_row))


def _attn_out(mixer, mixed):
    return _mm(mixed, mixer["wo"]) + mixer["bo"]


# -- the step, over a cache view -------------------------------------------------

def _layers(params, x, cache, cfg, view):
    """Every layer over the embedded ``x`` [B,T,D].  The residual stream
    is float32 (a bf16 one rounds 64 additions onto a growing sum: the
    largest single term of the served logits' distance from the float32
    reference); the matrix products read and write the activations' type.
    ``cache`` is (pool_k, pool_v, state); ``view`` says how this call reads and writes it (a decode tick
    over n lanes, or one lane's prefill chunk): see :class:`_DecodeView`
    and :class:`_PrefillView`."""
    pool_k, pool_v, state = cache
    state = {name: list(arrays) for name, arrays in state.items()}
    x = x.astype(jnp.float32)
    win = rec = 0
    memory = None
    for i, (kind, layer) in enumerate(zip(cfg.kinds, params["layers"])):
        mixer = layer["mixer"]
        h = _layer_norm(x, layer["ln_mix"], cfg)
        l0 = cfg.lambda_init(i)
        if kind in (MAMBA, MEMORY):
            conv, ssm = view.read(state["conv"][rec]), \
                view.read(state["ssm"][rec])
            out, y, conv, ssm = _mamba(mixer, h, conv, ssm, view.n_real, cfg)
            state["conv"][rec] = view.write(state["conv"][rec], conv)
            state["ssm"][rec] = view.write(state["ssm"][rec], ssm)
            rec += 1
            if kind == MEMORY:
                memory = y
        elif kind == WINDOW:
            q, k, v = _split_qkv(mixer, h, cfg)
            seen, state["ring_k"][win], state["ring_v"][win] = view.window(
                state["ring_k"][win], state["ring_v"][win], k, v)
            out = _attn_out(mixer, diff_attention(q, *seen, mixer, l0, cfg))
            win += 1
        elif kind == FULL:
            q, k, v = _split_qkv(mixer, h, cfg)
            pool_k = [view.paged_write(pool_k[0], k)]
            pool_v = [view.paged_write(pool_v[0], v)]
            out = _attn_out(mixer, view.attend(q, pool_k[0], pool_v[0],
                                               mixer, l0))
        elif kind == GMU:
            out = _mm(jax.nn.silu(_mm(h, mixer["w_in"])) * memory,
                      mixer["w_out"])
        else:  # CROSS
            b, t = h.shape[:2]
            q = (_mm(h, mixer["wq"]) + mixer["bq"]).reshape(
                b, t, cfg.n_heads, cfg.head_dim)
            out = _attn_out(mixer, view.attend(q, pool_k[0], pool_v[0],
                                               mixer, l0))
        x = x + out.astype(x.dtype)
        x = _mlp(layer, x, cfg)
    return _layer_norm(x, params["ln_f"], cfg), (pool_k, pool_v, state)


def _head(params, x):
    """Logits of ``x`` [B,D] through the tied head: the embedding [V,D]
    contracted as it is stored."""
    return jnp.einsum("bd,vd->bv", x, params["embed"],
                      preferred_element_type=jnp.float32)


def _write_rows(cache, first, at, rows):
    """``cache[first[i], :, at[i]] = rows[i]`` for a cache [.., pairs,
    positions, 2hd] and ``rows`` [N, pairs, 2hd]; an ``at`` past the
    positions drops the row.  Spelled as a scatter of N x pairs rows of
    2hd, the cache's minor dimension: one whose window is [pairs, 2hd]
    makes the compiler lay the cache out position-major for it, and
    transpose it back for attention's products, in every tick."""
    pairs = jnp.arange(cache.shape[1])
    return cache.at[first[:, None], pairs[None, :], at[:, None]].set(
        rows, mode="drop")


def _gather_blocks(pool, tables):
    """A pool [n_blocks+1, pairs, block, 2hd] through ``tables`` [B, width]:
    each lane's logical cache [B, pairs, width * block, 2hd]."""
    b, width = tables.shape
    _, pairs, block, wide = pool.shape
    return jnp.swapaxes(pool[tables], 1, 2).reshape(
        b, pairs, width * block, wide)


def _attend_gathered(view, q, pool_k, pool_v, mixer, l0):
    """The paged layer through ``view.paged_keys``: every lane's whole
    logical cache, gathered at the full layer and kept on the view for the
    cross layers (nothing writes the pool in between)."""
    if view.gathered is None:
        view.gathered = view.paged_keys(pool_k, pool_v)
    return diff_attention(q, *view.gathered, mixer, l0, view.cfg)


class _DecodeView:
    """n lanes, one position each, at ``pos`` [n]; ``live`` [n] masks the
    lanes that are not in the tick (idle, mid-prefill, at their budget):
    their state stays as it was."""

    def __init__(self, cfg, n, tables, pos, live, block_size):
        self.cfg, self.n, self.tables, self.pos, self.live = (
            cfg, n, tables, pos, live)
        self.block_size = block_size
        self.gathered = None
        self.n_real = live.astype(jnp.int32)
        self.lane = jnp.arange(n)

    def read(self, array):
        return array[:self.n]

    def write(self, array, new):
        if array.shape[0] == self.n:
            return new
        return lax.dynamic_update_slice_in_dim(array, new, 0, axis=0)

    def window(self, ring_k, ring_v, k, v):
        """((keys, values, valid), ring_k, ring_v): the rings with this
        tick's position written.  Position p lives in slot p % window, so
        slots 0..pos are this lane's own until the ring is full."""
        w = self.cfg.window
        slot = jnp.where(self.live, self.pos % w, w)  # w: dropped
        ring_k = _write_rows(ring_k, self.lane, slot, k[:, 0])
        ring_v = _write_rows(ring_v, self.lane, slot, v[:, 0])
        valid = jnp.arange(w)[None, :] <= self.pos[:, None]
        return (ring_k[:self.n], ring_v[:self.n], valid[:, None, :]), \
            ring_k, ring_v

    def paged_write(self, pool, new):
        blk = self.tables[self.lane, self.pos // self.block_size]
        blk = jnp.where(self.live, blk, TRASH_BLOCK)
        return _write_rows(pool, blk, self.pos % self.block_size, new[:, 0])

    def paged_keys(self, pool_k, pool_v):
        s_len = self.tables.shape[-1] * self.block_size
        valid = jnp.arange(s_len)[None, :] <= self.pos[:, None]
        return (_gather_blocks(pool_k, self.tables),
                _gather_blocks(pool_v, self.tables), valid[:, None, :])

    def attend(self, q, pool_k, pool_v, mixer, l0):
        """Differential attention of ``q`` [n,1,H,hd] over the lanes' paged
        caches, this tick's row written.  Where the kernel can take the
        pool's blocks as they lie (``paged_decode.reads_in_place``: whole
        tiles on the chip, anything interpreted) it reads each lane's
        blocks in place up to that lane's own length, and a lane that is
        not in the tick reads nothing; otherwise the gather of every
        lane's whole table."""
        if not reads_in_place(pool_k):
            return _attend_gathered(self, q, pool_k, pool_v, mixer, l0)
        qg = _pair_queries(q, self.cfg)[:, 0]            # [n,g,r,2,2hd]
        a = paged_decode_attention(
            qg.reshape(qg.shape[:2] + (-1, qg.shape[-1])), pool_k, pool_v,
            self.tables, jnp.where(self.live, self.pos + 1, 0))
        return _diff_out(a.reshape(qg.shape)[:, None], mixer, l0,
                         self.cfg).astype(q.dtype)


class _PrefillView:
    """One lane (``slot``), C positions from ``start``; those at or past
    ``prompt_len`` are bucket padding.  ``fresh`` is the prompt's first
    chunk: the lane's state starts from zero there."""

    def __init__(self, cfg, width, table, slot, start, prompt_len, fresh,
                 block_size):
        self.cfg, self.table, self.slot, self.start = cfg, table, slot, start
        self.fresh, self.block_size = fresh, block_size
        self.gathered = None
        self.pos = start + jnp.arange(width)
        self.real = self.pos < prompt_len
        self.end = jnp.minimum(prompt_len, start + width)  # real positions
        self.n_real = (self.end - start)[None]

    def read(self, array):
        lane = lax.dynamic_index_in_dim(array, self.slot, 0, keepdims=True)
        return jnp.where(self.fresh, jnp.zeros_like(lane), lane)

    def write(self, array, new):
        return lax.dynamic_update_slice_in_dim(array, new, self.slot, axis=0)

    def window(self, ring_k, ring_v, k, v):
        """((keys, values, valid), ring_k, ring_v): the lane's ring beside
        the chunk's own keys, then the rings with the chunk's last
        ``window`` real positions written, each to its slot.  The ring
        holds positions < start: slot j the latest of them that is j
        modulo the window.  Query t sees t - window + 1 .. t."""
        w = self.cfg.window
        slots = jnp.arange(w)
        held = self.start - 1 - (self.start - 1 - slots) % w
        held = jnp.where(self.fresh, -1, held)
        keys_at = jnp.concatenate([held, self.pos])
        t = self.pos[:, None]
        valid = (keys_at[None, :] >= 0) & (keys_at[None, :] <= t) \
            & (keys_at[None, :] > t - w)
        seen = (jnp.concatenate([self.read(ring_k), jnp.swapaxes(k, 1, 2)],
                                axis=2),
                jnp.concatenate([self.read(ring_v), jnp.swapaxes(v, 1, 2)],
                                axis=2), valid[None])
        keep = self.real & (self.pos >= self.end - w)
        slot = jnp.where(keep, self.pos % w, w)  # w: dropped
        lane = jnp.broadcast_to(self.slot, slot.shape)
        return (seen, _write_rows(ring_k, lane, slot, k[0]),
                _write_rows(ring_v, lane, slot, v[0]))

    def paged_write(self, pool, new):
        blk = jnp.where(self.real, self.table[self.pos // self.block_size],
                        TRASH_BLOCK)
        return _write_rows(pool, blk, self.pos % self.block_size, new[0])

    def paged_keys(self, pool_k, pool_v):
        s_len = self.table.shape[-1] * self.block_size
        valid = jnp.arange(s_len)[None, :] <= self.pos[:, None]
        return (_gather_blocks(pool_k, self.table[None]),
                _gather_blocks(pool_v, self.table[None]), valid[None])

    def attend(self, q, pool_k, pool_v, mixer, l0):
        """A chunk is FLOPs bound at its hundreds of query rows: the
        gather of the one lane's table."""
        return _attend_gathered(self, q, pool_k, pool_v, mixer, l0)


def decode_step(params, tokens, pool_k, pool_v, state, tables, lens, live,
                cfg, block_size):
    """One token a lane for the ``n`` lanes of ``tokens`` [n], each at
    position ``lens`` [n]: float32 logits [n,V] and the cache after."""
    view = _DecodeView(cfg, tokens.shape[0], tables, lens, live, block_size)
    x = jnp.take(params["embed"], tokens, axis=0)[:, None, :]
    x, cache = _layers(params, x, (pool_k, pool_v, state), cfg, view)
    return _head(params, x[:, 0]), cache


def prefill_step(params, chunk, pool_k, pool_v, state, table, slot, start,
                 prompt_len, fresh, cfg, block_size):
    """``chunk`` [1,C] of lane ``slot``'s prompt at positions ``start`` ..:
    float32 logits [V] at the prompt's last position (meaningful in the
    chunk that holds it) and the cache after."""
    c = chunk.shape[1]
    view = _PrefillView(cfg, c, table, slot, start, prompt_len, fresh,
                        block_size)
    x = jnp.take(params["embed"], chunk, axis=0)
    x, cache = _layers(params, x, (pool_k, pool_v, state), cfg, view)
    last = jnp.clip(prompt_len - 1 - start, 0, c - 1)
    xsel = lax.dynamic_index_in_dim(x[0], last, 0, keepdims=True)
    return _head(params, xsel)[0], cache


# -- the two programs, and the family as the engine asks for it ----------------

def sambay_decode_tick(params, tokens_full, pool_k, pool_v, state, tables,
                       lens, live, temps, topks, keys_full, *, cfg, n,
                       block_size):
    """One batched decode step over the first ``n`` lanes, with the token
    choice on the device as ``transformer.paged_decode_tick`` makes it."""
    logits, (pool_k, pool_v, state) = decode_step(
        params, tokens_full[:n], pool_k, pool_v, state, tables, lens, live,
        cfg, block_size)
    pairs = jax.vmap(lambda key: jax.random.split(key, 2))(keys_full[:n])
    nxt = jax.vmap(select_token)(logits, pairs[:, 0], temps, topks)
    return (tokens_full.at[:n].set(nxt), pool_k, pool_v, state,
            keys_full.at[:n].set(pairs[:, 1]))


def sambay_prefill_chunk(params, chunk, pool_k, pool_v, state, table, slot,
                         start, prompt_len, fresh, key, temperature, top_k,
                         *, cfg, block_size):
    """One prefill chunk of lane ``slot``; the returned token is the first
    generated one where the chunk holds the prompt's last position."""
    logits, (pool_k, pool_v, state) = prefill_step(
        params, chunk, pool_k, pool_v, state, table, slot, start,
        prompt_len, fresh, cfg, block_size)
    k_sample, k_carry = jax.random.split(key)
    tok = select_token(logits, k_sample, temperature, top_k)
    return tok, pool_k, pool_v, state, k_carry


class SambaYPrograms:
    """This family behind the interface of ``transformer.DecoderPrograms``,
    handed out as ``cfg.family``: the same two shapes over one paged layer
    and the lanes' fixed state, which is donated with the pools.  The
    programs are jitted under their own names."""

    recurrent = (
        "lanes carry recurrent state (Mamba layers, window rings) that "
        "K/V blocks alone do not rebuild"
    )
    init_params = staticmethod(init_params)
    generate = None         # no contiguous cache: the engine alone serves it
    quantize_params = None  # no int8 weights
    serving_params = None   # served as published

    def __init__(self, cfg, block_size):
        self.cfg, self.block_size = cfg, block_size
        # CPU (the test platform) has no donation support
        self.donate = (2, 3, 4) if jax.default_backend() != "cpu" else ()
        self.flops_per_token = lm_flops_per_token(cfg)
        self.window = cfg.window
        # what ``_DecodeView.attend`` will find of the pool's blocks
        self._in_place = reads_in_place(jax.ShapeDtypeStruct(
            (block_size, cfg.kv_row[1]), cfg.jdtype))
        self._static = dict(cfg=cfg, block_size=block_size)
        self.prefill_jit = jax.jit(
            sambay_prefill_chunk,
            static_argnames=("cfg", "block_size"), donate_argnums=self.donate,
        )
        self._tick_jit = jax.jit(
            sambay_decode_tick,
            static_argnames=("cfg", "n", "block_size"),
            donate_argnums=self.donate,
        )

    def attended_positions(self, max_pos, table_width):
        """A chunk's read: the whole table, whatever the lane holds (the
        gather of ``_PrefillView``).  A decode tick's is :meth:`_tick_reads`."""
        return table_width * self.block_size

    def _tick_reads(self, lengths, table_width):
        """The cache positions of each lane that a decode tick's attention
        reads, for lanes at ``lengths`` (an array) before the tick's
        write: each lane's own length, this tick's row with it, rounded up
        to the kernel's step (its trip count, ``paged_decode.steps_read``);
        the whole table a lane where the tick gathers instead."""
        if not self._in_place:
            return [table_width * self.block_size] * len(lengths)
        return (steps_read(lengths + 1, self.block_size)
                * (STEP_BLOCKS * self.block_size)).tolist()

    def tick_fields(self, kind, lengths, **_):
        """For a decode tick's ``tick_trace()`` entry where the tick reads
        in place: ``kv_steps``, the steps the kernel took over the entry's
        lanes and the layers that read the full cache, and
        ``kv_steps_full``, those on its straight-line path
        (``paged_decode.tick_steps``)."""
        if kind != "decode" or not self._in_place:
            return {}
        kinds = self.cfg.kinds
        return tick_steps(np.asarray(lengths) + 1, self.block_size,
                          kinds.count("full") + kinds.count("cross"))

    def prefill(self, params, kv, chunk, table, slot, start, prompt_len,
                fresh, key, temperature, top_k):
        with annotation("lm.sambay_prefill_chunk"):
            tok, kv.pools["k"], kv.pools["v"], kv.lane_state, key = (
                self.prefill_jit(
                    params, chunk, kv.pools["k"], kv.pools["v"],
                    kv.lane_state, table, jnp.int32(slot), start,
                    prompt_len, jnp.bool_(fresh), key, temperature, top_k,
                    **self._static,
                )
            )
        return tok, key

    def make_tick(self, n):
        return functools.partial(self._tick_jit, n=n, **self._static)

    def tick(self, fn, params, kv, tokens, tables, lens, live, temps, topks,
             keys):
        with annotation("lm.sambay_decode_tick"):
            tokens, kv.pools["k"], kv.pools["v"], kv.lane_state, keys = fn(
                params, tokens, kv.pools["k"], kv.pools["v"], kv.lane_state,
                tables, lens, live, temps, topks, keys,
            )
        return tokens, keys
