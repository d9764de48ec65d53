"""The ``cohere2_moe`` decoder (Command A+, ``model_type`` ``cohere2_moe``)
as a third family that ``serve/lm.LmEngine`` serves: one chip's share of an
expert-parallel deployment.

Layer ``i`` is a PARALLEL block over one norm: ``n = LayerNorm(x)`` (mean
subtracted, a scale and no bias), ``x + Attn(n) + Ffn(n)``.

- ``Attn``: ``n_heads`` query heads of ``head_dim`` over ``n_kv_heads`` KV
  heads (the head size is the configuration's own, not ``d_model /
  n_heads``), no bias, no q/k norm.  Layers come in periods of
  ``full_every``: the last of a period attends the whole context and has NO
  position embedding; the others attend the last ``window`` keys (itself
  among them) under a rotary embedding over all of a head's dimensions,
  interleaved pairs ``(2i, 2i + 1)`` at angle ``pos * theta^(-2i / hd)``.
- ``Ffn``: ``serve/models/experts.py``: sigmoid routing over all
  ``n_experts``, the ``top_k`` picks' weights normalised, only the pairs
  that fall on ``experts_held`` computed, plus the mean of the shared
  experts.
- Head: ``LayerNorm(x) E^T * logit_scale`` over the ``vocab_size`` rows of
  the embedding that are held here (a sliced vocabulary is a smaller one).

Every layer pages its K/V, all in one block shape ``[n_kv_heads, block,
head_dim]`` on one block table a lane: a lane IS its blocks (prefix
adoption, host swap and fleet export stay on).  A window layer keeps its
blocks behind the window too; it only stops reading them.

The step is written once (``_layers``) over a cache view, as
``sambay._layers`` is: ``_DecodeView`` at (n, 1) reads each lane's blocks
in place through ``ops/paged_decode`` (a window layer from the lane's first
visible position), ``_PrefillView`` at (1, C) reads the table in groups of
columns under a running softmax (a window layer from the first group its
chunk can see).  The programs ``cohere2moe_decode_tick`` and
``cohere2moe_prefill_chunk`` are jitted under those names so that a device
trace tells them apart, and return, beside what the other families'
programs return, the expert layers' counts (``COUNTERS``).
"""

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from client_tpu.ops.paged_decode import (
    STEP_BLOCKS, paged_decode_attention, reads_in_place, steps_read,
    tick_steps)
from client_tpu.ops.sampling import select_token
from client_tpu.serve.models import experts
from client_tpu.serve.models.sambay import TRASH_BLOCK, _write_rows
from client_tpu.serve.prof import annotation

# table columns a step of the grouped read gathers and contracts at once:
# the kernel's step, so that both reads of a lane cover the same positions
GROUP_BLOCKS = STEP_BLOCKS

# What the expert layers count on the device, summed over the layers, in the
# order the programs return it: (tick_trace() field, Prometheus series or
# None, "counter" | "gauge", help).  The engine copies the vector to the host
# with the tokens and knows none of the names.
COUNTERS = (
    ("experts_held", None, None, None),
    ("experts_hit", "ctpu_lm_experts_hit_total", "counter",
     "Held experts with at least one row, summed over expert layers and "
     "dispatches"),
    ("expert_rows", "ctpu_lm_expert_rows_total", "counter",
     "(token, pick) pairs that fell on held experts, summed over expert "
     "layers and dispatches"),
    ("expert_rows_max", "ctpu_lm_expert_rows_max", "gauge",
     "Rows of the busiest held expert in the last dispatch, summed over "
     "expert layers"),
)


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 32768          # the rows of the embedding held here
    d_model: int = 4096
    n_layers: int = 4
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 4096                 # an expert's width
    n_experts: int = 128             # the router's width: all the experts
    top_k: int = 8
    experts_held: tuple = tuple(range(16))  # which of them live here
    n_shared: int = 4
    window: int = 4096
    full_every: int = 4              # layer i is full where i % this is last
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_seq: int = 8960
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("n_heads a multiple of n_kv_heads, head_dim even")
        held = tuple(int(e) for e in self.experts_held)
        if not held or len(set(held)) != len(held) or not all(
                0 <= e < self.n_experts for e in held):
            raise ValueError("experts_held: distinct ids under n_experts")
        object.__setattr__(self, "experts_held", held)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def is_full(self, i):
        return i % self.full_every == self.full_every - 1

    @property
    def state_spec(self):
        """(paged layers, {pool: a block's shape with None where the
        block's positions go}, no per-lane state): every layer pages its
        keys and values, heads outside a block's positions, the layout
        ``ops/paged_decode`` reads in place."""
        block = (self.n_kv_heads, None, self.head_dim)
        return self.n_layers, {"k": block, "v": block}, {}

    @property
    def family(self):
        return Cohere2MoePrograms


# -- parameters -----------------------------------------------------------------

def init_params(key, cfg):
    """[in, out] matrices (``x @ w``): ``wqkv`` holds the query, key and
    value columns side by side; a layer's ``ffn`` is ``experts.init_params``'
    tree.  ``benchmark/weights_cohere2moe.py`` makes the same tree from a
    seed, a layer a call."""
    dt = cfg.jdtype
    d, hd = cfg.d_model, cfg.head_dim
    q_out, kv_out = cfg.n_heads * hd, cfg.n_kv_heads * hd
    keys = iter(jax.random.split(key, 3 * cfg.n_layers + 1))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, dt) * float(fan_in ** -0.5)

    layers = [{
        "ln": jnp.ones((d,), dt),
        "wqkv": dense((d, q_out + 2 * kv_out), d),
        "wo": dense((q_out, d), q_out),
        "ffn": experts.init_params(
            next(keys), d, cfg.d_ff, cfg.n_experts, len(cfg.experts_held),
            cfg.n_shared, dt),
    } for _ in range(cfg.n_layers)]
    return {"embed": dense((cfg.vocab_size, d), d), "layers": layers,
            "ln_f": jnp.ones((d,), dt)}


def lm_flops_per_token(cfg, context=0):
    """Model FLOPs a generated token costs HERE, 2 a weight element it
    meets: attention, router, shared experts, the share of its ``top_k``
    picks that the held experts get under even routing, the sliced head;
    ``context`` adds attention over the keys a window and a full layer
    see."""
    d, ff, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    q_out, kv_out = cfg.n_heads * hd, cfg.n_kv_heads * hd
    picks_here = cfg.top_k * len(cfg.experts_held) / cfg.n_experts
    layer = (d * (q_out + 2 * kv_out) + q_out * d + d * cfg.n_experts
             + 3 * d * ff * (cfg.n_shared + picks_here))
    full = sum(cfg.is_full(i) for i in range(cfg.n_layers))
    keys = (full * int(context)
            + (cfg.n_layers - full) * min(int(context), cfg.window))
    return int(2 * (cfg.n_layers * layer + d * cfg.vocab_size)
               + 4 * q_out * keys)


# -- the layer's parts ------------------------------------------------------------

def _layer_norm(x, scale, cfg):
    """LayerNorm without bias of the float32 residual stream ``x``, in the
    activations' type: what the matrix products read."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + cfg.norm_eps)).astype(cfg.jdtype) \
        * scale


def _rope_pairs(x, pos, cfg):
    """The interleaved (GPT-J) rotary embedding of ``x`` [B,T,heads,hd] at
    positions ``pos`` [B,T]: lanes ``(2i, 2i+1)`` turn by ``pos *
    theta^(-2i / hd)``.  A pair's partner is one lane to the side, so the
    turn is two rolls and a select: nothing is split or strided."""
    hd = cfg.head_dim
    lane = jnp.arange(hd)
    freq = cfg.rope_theta ** (-(lane - lane % 2).astype(jnp.float32) / hd)
    angle = pos.astype(jnp.float32)[:, :, None, None] * freq
    x32 = x.astype(jnp.float32)
    partner = jnp.where(lane % 2 == 0, -jnp.roll(x32, -1, axis=-1),
                        jnp.roll(x32, 1, axis=-1))
    return (x32 * jnp.cos(angle) + partner * jnp.sin(angle)).astype(x.dtype)


def _gather_group(pool, tables, g, block_size):
    """Columns ``g * GROUP_BLOCKS ..`` of ``tables`` [B, width] through a
    pool [blocks, kv, block, hd]: [B, kv, GROUP_BLOCKS * block, hd]."""
    cols = lax.dynamic_slice_in_dim(tables, g * GROUP_BLOCKS, GROUP_BLOCKS,
                                    axis=1)
    b = tables.shape[0]
    _, kv, _, hd = pool.shape
    return jnp.swapaxes(pool[cols], 1, 2).reshape(
        b, kv, GROUP_BLOCKS * block_size, hd)


def attend_groups(q, pool_k, pool_v, tables, pos, lo, cfg, block_size):
    """Attention of ``q`` [B,T,H,hd] (roped where its layer ropes) over a
    paged cache, position ``pos`` [B,T] seeing keys ``lo .. pos`` ([B,T]
    each): the table is read a group of ``GROUP_BLOCKS`` columns at a time,
    from the group that holds the smallest ``lo`` to the one that holds the
    largest ``pos``, under a running maximum, sum and weighted sum in
    float32 (``transformer.paged_attention``'s scheme with a lower bound),
    so that a chunk's scores exist for one group only.  The query heads of a
    KV head meet its gathered keys in one product batched over (lane, KV
    head), operands as stored, float32 accumulated.  Returns [B,T,H*hd]
    float32."""
    b, t = q.shape[:2]
    kv, rep, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    span = GROUP_BLOCKS * block_size
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % GROUP_BLOCKS)))
    qg = q.reshape(b, t, kv, rep, hd) * jnp.asarray(hd ** -0.5, q.dtype)

    def step(g, carry):
        m, l, acc = carry
        kk = _gather_group(pool_k, tables, g, block_size)
        vv = _gather_group(pool_v, tables, g, block_size)
        at = g * span + jnp.arange(span)
        seen = (at >= lo[:, :, None]) & (at <= pos[:, :, None])   # [B,T,S]
        s = jnp.einsum("btgrd,bgsd->bgrts", qg, kk,
                       preferred_element_type=jnp.float32)
        s = jnp.where(seen[:, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        old = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * old + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * old + jnp.einsum(
            "bgrts,bgsd->bgrtd", p.astype(vv.dtype), vv,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    rows = (b, kv, rep, t)
    m, l, acc = lax.fori_loop(
        jnp.min(lo) // span, jnp.max(pos) // span + 1, step,
        (jnp.full(rows + (1,), -1e30, jnp.float32),
         jnp.zeros(rows + (1,), jnp.float32),
         jnp.zeros(rows + (hd,), jnp.float32)))
    # [B,kv,rep,T,hd] -> [B,T,H*hd]; a row that saw nothing (none does) 0
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, cfg.n_heads * hd)


# -- the step, over a cache view -------------------------------------------------

def _layers(params, x, pool_k, pool_v, cfg, view):
    """Every layer over the embedded ``x`` [B,T,D]: the final norm's output,
    the pools after, and the expert layers' counts summed.  The residual
    stream is float32; the matrix products read and write the activations'
    type."""
    pool_k, pool_v = list(pool_k), list(pool_v)
    hd = cfg.head_dim
    q_out, kv_out = cfg.n_heads * hd, cfg.n_kv_heads * hd
    b, t = x.shape[:2]
    x = x.astype(jnp.float32)
    counts = jnp.zeros((3,), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        h = _layer_norm(x, layer["ln"], cfg)
        q, k, v = jnp.split(h @ layer["wqkv"], [q_out, q_out + kv_out],
                            axis=-1)
        q = q.reshape(b, t, cfg.n_heads, hd)
        k = k.reshape(b, t, cfg.n_kv_heads, hd)
        v = v.reshape(b, t, cfg.n_kv_heads, hd)
        full = cfg.is_full(i)
        if not full:
            q, k = (_rope_pairs(a, view.pos, cfg) for a in (q, k))
        pool_k[i] = view.paged_write(pool_k[i], k)
        pool_v[i] = view.paged_write(pool_v[i], v)
        mixed = view.attend(q, pool_k[i], pool_v[i],
                            None if full else cfg.window)
        out, hit = experts.ffn(
            h.reshape(b * t, -1), layer["ffn"], cfg.experts_held, cfg.top_k,
            cfg.n_shared, view.real.reshape(-1))
        x = x + jnp.matmul(mixed.astype(h.dtype), layer["wo"],
                           preferred_element_type=jnp.float32) \
            + out.reshape(b, t, -1)
        counts = counts + hit
    return _layer_norm(x, params["ln_f"], cfg), pool_k, pool_v, counts


def _head(params, x, cfg):
    """Logits of ``x`` [B,D] through the tied head: the held rows of the
    embedding [V,D] contracted as they are stored, times ``logit_scale``."""
    return jnp.einsum("bd,vd->bv", x, params["embed"],
                      preferred_element_type=jnp.float32) * cfg.logit_scale


def _counters(cfg, counts):
    held = jnp.int32(cfg.n_layers * len(cfg.experts_held))
    return jnp.concatenate([held[None], counts])


class _DecodeView:
    """n lanes, one position each, at ``pos`` [n]; ``live`` [n] masks the
    lanes that are not in the tick: they write to the trash block, read
    nothing and route nowhere."""

    def __init__(self, cfg, tables, lens, live, block_size):
        self.cfg, self.tables, self.block_size = cfg, tables, block_size
        self.lens, self.live = lens, live
        self.pos = lens[:, None]                 # [n,1]
        self.real = live[:, None]
        self.lane = jnp.arange(lens.shape[0])

    def paged_write(self, pool, new):
        blk = self.tables[self.lane, self.lens // self.block_size]
        blk = jnp.where(self.live, blk, TRASH_BLOCK)
        return _write_rows(pool, blk, self.lens % self.block_size, new[:, 0])

    def attend(self, q, pool_k, pool_v, window):
        """``q`` [n,1,H,hd] over the lanes' caches, this tick's row written.
        Where the kernel can take the pool's blocks as they lie
        (``paged_decode.reads_in_place``) it reads each lane's blocks in
        place up to that lane's own length, a window layer from the step
        that holds the lane's first visible position; otherwise the grouped
        read, from the same places."""
        cfg = self.cfg
        length = jnp.where(self.live, self.lens + 1, 0)
        first = None if window is None else jnp.maximum(length - window, 0)
        if not reads_in_place(pool_k):
            lo = jnp.zeros_like(self.pos) if first is None else first[:, None]
            return attend_groups(q, pool_k, pool_v, self.tables, self.pos, lo,
                                 cfg, self.block_size)
        n = q.shape[0]
        qg = q[:, 0].reshape(n, cfg.n_kv_heads, -1, cfg.head_dim) \
            * jnp.asarray(cfg.head_dim ** -0.5, q.dtype)
        out = paged_decode_attention(qg, pool_k, pool_v, self.tables, length,
                                     first)
        return out.reshape(n, 1, -1)


class _PrefillView:
    """One lane, C positions from ``start``; those at or past
    ``prompt_len`` are bucket padding: written to the trash block, routed
    nowhere."""

    def __init__(self, cfg, width, table, start, prompt_len, block_size):
        self.cfg, self.table, self.block_size = cfg, table, block_size
        self.pos = (start + jnp.arange(width))[None]      # [1,C]
        self.real = self.pos < prompt_len

    def paged_write(self, pool, new):
        blk = jnp.where(self.real[0],
                        self.table[self.pos[0] // self.block_size],
                        TRASH_BLOCK)
        return _write_rows(pool, blk, self.pos[0] % self.block_size, new[0])

    def attend(self, q, pool_k, pool_v, window):
        lo = jnp.zeros_like(self.pos) if window is None else jnp.maximum(
            self.pos - window + 1, 0)
        return attend_groups(q, pool_k, pool_v, self.table[None], self.pos,
                             lo, self.cfg, self.block_size)


def decode_step(params, tokens, pool_k, pool_v, tables, lens, live, cfg,
                block_size):
    """One token a lane for the lanes of ``tokens`` [n], each at position
    ``lens`` [n]: float32 logits [n,V], the pools after, the counts."""
    view = _DecodeView(cfg, tables, lens, live, block_size)
    x = jnp.take(params["embed"], tokens, axis=0)[:, None, :]
    x, pool_k, pool_v, counts = _layers(params, x, pool_k, pool_v, cfg, view)
    return _head(params, x[:, 0], cfg), pool_k, pool_v, counts


def prefill_step(params, chunk, pool_k, pool_v, table, start, prompt_len,
                 cfg, block_size):
    """``chunk`` [1,C] of a prompt at positions ``start`` ..: float32 logits
    [V] at the prompt's last position (meaningful in the chunk that holds
    it), the pools after, the counts."""
    c = chunk.shape[1]
    view = _PrefillView(cfg, c, table, start, prompt_len, block_size)
    x = jnp.take(params["embed"], chunk, axis=0)
    x, pool_k, pool_v, counts = _layers(params, x, pool_k, pool_v, cfg, view)
    last = jnp.clip(prompt_len - 1 - start, 0, c - 1)
    xsel = lax.dynamic_index_in_dim(x[0], last, 0, keepdims=True)
    return _head(params, xsel, cfg)[0], pool_k, pool_v, counts


# -- the two programs, and the family as the engine asks for it ----------------

def cohere2moe_decode_tick(params, tokens_full, pool_k, pool_v, tables, lens,
                           live, temps, topks, keys_full, *, cfg, n,
                           block_size):
    """One batched decode step over the first ``n`` lanes, with the token
    choice on the device as ``transformer.paged_decode_tick`` makes it."""
    logits, pool_k, pool_v, counts = decode_step(
        params, tokens_full[:n], pool_k, pool_v, tables, lens, live, cfg,
        block_size)
    pairs = jax.vmap(lambda key: jax.random.split(key, 2))(keys_full[:n])
    nxt = jax.vmap(select_token)(logits, pairs[:, 0], temps, topks)
    return (tokens_full.at[:n].set(nxt), pool_k, pool_v,
            keys_full.at[:n].set(pairs[:, 1]), _counters(cfg, counts))


def cohere2moe_prefill_chunk(params, chunk, pool_k, pool_v, table, start,
                             prompt_len, key, temperature, top_k, *, cfg,
                             block_size):
    """One prefill chunk of a lane; the returned token is the first
    generated one where the chunk holds the prompt's last position."""
    logits, pool_k, pool_v, counts = prefill_step(
        params, chunk, pool_k, pool_v, table, start, prompt_len, cfg,
        block_size)
    k_sample, k_carry = jax.random.split(key)
    tok = select_token(logits, k_sample, temperature, top_k)
    return tok, pool_k, pool_v, k_carry, _counters(cfg, counts)


class Cohere2MoePrograms:
    """This family behind the interface of ``transformer.DecoderPrograms``,
    handed out as ``cfg.family``.  A lane is its blocks (``recurrent`` is
    empty); there is no verify program yet, which ``no_verify`` says.  Its
    programs return one value more than the other families': the expert
    layers' counts, named by ``counters``, which the engine writes into the
    tick's ``tick_trace()`` entry; ``tick_fields`` adds what the host can
    count of the K/V positions a dispatch may see and does read."""

    recurrent = ""
    no_verify = (
        "the family has no verify program: a speculative step over a "
        "window layer's first position and the expert layers' counts is "
        "not written yet"
    )
    counters = COUNTERS
    init_params = staticmethod(init_params)
    generate = None         # no contiguous cache: the engine alone serves it
    quantize_params = None  # no int8 weights
    serving_params = None   # served as published

    def __init__(self, cfg, block_size):
        self.cfg, self.block_size = cfg, block_size
        # CPU (the test platform) has no donation support
        self.donate = (2, 3) if jax.default_backend() != "cpu" else ()
        self.flops_per_token = lm_flops_per_token(cfg)
        self.window = cfg.window
        # positions a step of either read covers (the kernel's in place, or
        # the grouped one where a tick cannot take the blocks as they lie)
        self._span = GROUP_BLOCKS * block_size
        self._full = sum(cfg.is_full(i) for i in range(cfg.n_layers))
        self._static = dict(cfg=cfg, block_size=block_size)
        self.prefill_jit = jax.jit(
            cohere2moe_prefill_chunk,
            static_argnames=("cfg", "block_size"), donate_argnums=self.donate,
        )
        self._tick_jit = jax.jit(
            cohere2moe_decode_tick,
            static_argnames=("cfg", "n", "block_size"),
            donate_argnums=self.donate,
        )

    def attended_positions(self, max_pos, table_width):
        """Positions the full layer reads for a chunk whose largest query
        position is ``max_pos``: whole groups of columns up to it."""
        return (max_pos // self._span + 1) * self._span

    def _reads(self, lengths, window=None):
        """Positions a layer's decode read covers for lanes that attend
        ``lengths`` positions each (an array): whole steps from the one
        that holds the first visible position (``paged_decode.steps_read``,
        the kernel's trip count)."""
        span = self._span
        first = np.maximum(lengths - window, 0) // span * span if window \
            else 0
        return steps_read(lengths, self.block_size) * span - first

    def _tick_reads(self, lengths, table_width):
        """The cache positions of each lane that a decode tick's FULL layer
        reads, for lanes at ``lengths`` before the tick's write."""
        return self._reads(np.asarray(lengths) + 1).tolist()

    def tick_fields(self, kind, lengths, start=None, width=None, **_):
        """What the host can count for a ``tick_trace()`` entry, over the
        entry's lanes and every layer: ``kv_positions_live``, the positions
        attention may see (a decode lane of length ``len``: ``len + 1`` on
        a full layer, ``min(len + 1, window)`` on a window layer; a chunk:
        the union over its rows), and ``kv_positions_read``, what the
        program's trip counts read of them; on a decode tick ``kv_steps``,
        the steps the decode kernel took, and ``kv_steps_full``, those on
        its straight-line path (``paged_decode.tick_steps``)."""
        lengths = np.asarray(lengths, np.int64)
        w, full = self.cfg.window, self._full
        windowed = self.cfg.n_layers - full
        if kind == "prefill_chunk":
            span = self._span
            end = int(lengths[0])                      # start + real tokens
            behind = max(start - w + 1, 0)             # a window layer's lo
            groups = (start + width - 1) // span + 1
            live = full * end + windowed * (end - behind)
            read = span * (full * groups + windowed * (groups - behind // span))
            return {"kv_positions_live": int(live),
                    "kv_positions_read": int(read)}
        seen = lengths + 1
        live = full * seen.sum() + windowed * np.minimum(seen, w).sum()
        read = (full * self._reads(seen).sum()
                + windowed * self._reads(seen, w).sum())
        return {"kv_positions_live": int(live), "kv_positions_read": int(read),
                **tick_steps(        # a layer after the other
                    np.tile(seen, full + windowed), self.block_size,
                    starts=np.concatenate(
                        [0 * seen] * full
                        + [np.maximum(seen - w, 0)] * windowed))}

    def prefill(self, params, kv, chunk, table, slot, start, prompt_len,
                fresh, key, temperature, top_k):
        with annotation("lm.cohere2moe_prefill_chunk"):
            tok, kv.pools["k"], kv.pools["v"], key, counts = self.prefill_jit(
                params, chunk, kv.pools["k"], kv.pools["v"], table, start,
                prompt_len, key, temperature, top_k, **self._static,
            )
        return tok, key, counts

    def make_tick(self, n):
        return functools.partial(self._tick_jit, n=n, **self._static)

    def tick(self, fn, params, kv, tokens, tables, lens, live, temps, topks,
             keys):
        with annotation("lm.cohere2moe_decode_tick"):
            tokens, kv.pools["k"], kv.pools["v"], keys, counts = fn(
                params, tokens, kv.pools["k"], kv.pools["v"], tables, lens,
                live, temps, topks, keys,
            )
        return tokens, keys, counts
