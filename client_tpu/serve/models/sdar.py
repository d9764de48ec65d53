"""The ``sdar_moe`` decoder (SDAR-30B-A3B-Chat, ``model_type`` ``sdar_moe``)
as a family that ``serve/lm.LmEngine`` serves: generation by DIFFUSION OVER
BLOCKS.  A lane's step holds a block of ``block_length`` positions and
yields no token, or a whole block.

Layer ``i`` is a pre-norm decoder layer: ``x + Attn(RMSNorm(x))``, then ``x +
Ffn(RMSNorm(x))``.

- ``Attn``: ``n_heads`` query heads of ``head_dim`` over ``n_kv_heads`` KV
  heads, no bias; an RMSNorm over a head's dimensions on queries and on keys
  (one scale vector each) BEFORE the rotary embedding (all of a head's
  dimensions, halves pairing: ``transformer._rope``).  Attention is
  BLOCK-CAUSAL: position ``i`` sees every position ``j`` with ``j // B <= i
  // B``, so the positions of a block see one another.
- ``Ffn``: ``serve/models/experts.py`` with the softmax score: the router's
  probabilities over all ``n_experts``, the ``top_k`` largest, normalised;
  no shared expert.  The layer is told which experts it holds (here: all).
- Head: ``RMSNorm(x) W_head``, untied.  Logits at position ``i`` predict
  token ``i`` itself: a ``[MASK]`` at ``i`` is denoised in place.

A lane's state beside its blocks is its current block, ``[3, B]`` int32 in
the engine's lane array: the block's tokens, which of its positions are
still masked (the program's own bit, never a comparison with the mask id),
and for each position how many of the block's positions were unmasked when
it was fixed (``-1``: known from the prompt, or not fixed yet): the order of
the denoising, which the host reads with the tokens.

ONE program serves both kinds of pass (``sdar_block_tick``).  Every pass
embeds the block (``[MASK]`` where masked), writes the rows' keys and values
at ``length .. length + B - 1`` (rewritten in place pass after pass) and
reads ``0 .. length + B - 1`` in place through ``ops/paged_decode``: inside
a block attention is bidirectional, so the B positions of a lane see the
SAME keys and go to the kernel as ``B x n_heads / n_kv_heads`` query rows a
KV head under one lane length, no mask of their own.  A lane with a masked
position is in a DENOISING pass: at every masked position the token of the
largest logit (or the lane's sampled one) with its softmax probability as
confidence, and the ``block_length / denoising_steps`` masked positions of
highest confidence are fixed (ties: the lowest position): the published
``low_confidence_static`` rule.  A lane with none is in its COMMIT pass: the
rows just written are the finished block's, for good, and the lane's state
becomes the next block, all masked.  The device tells the two apart by the
mask it holds; the host knows every lane's phase from the static schedule
alone (``SdarPrograms.advance``), so ticks are dispatched ahead as for
every other family, and advances the lane's length on the commit.

The prefill chunk (``sdar_prefill_chunk``) attends block-causally through
``transformer.paged_attention`` with a row's sight at its block's last
position, stores the prompt's whole blocks (positions under ``prompt_len //
B * B``) and yields no token: it returns the lane's first block, the
prompt's last ``prompt_len % B`` tokens known and the rest masked.

Both programs are jitted under their own names so that a device trace tells
them apart, and return the expert layers' counts as ``cohere2moe``'s do.
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
from client_tpu.serve.lm.policy import attention_width_index, attention_widths
from client_tpu.serve.models import experts
from client_tpu.serve.models.cohere2moe import COUNTERS
from client_tpu.serve.models.sambay import TRASH_BLOCK, _write_rows
from client_tpu.serve.models.transformer import (
    _rope, _write_blocks, paged_attention)
from client_tpu.serve.prof import annotation

# the rows of a lane's state in the engine's lane array
TOKENS, MASKED, FIXED_AT = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 7
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 768                  # an expert's width
    n_experts: int = 128             # the router's width: all the experts
    top_k: int = 8
    experts_held: tuple = tuple(range(128))  # which of them live here
    block_length: int = 4            # positions a diffusion block holds
    denoising_steps: int = 4         # passes that unmask a whole block
    mask_id: int = 151669            # the id whose embedding a mask reads
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    max_seq: int = 2560
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("n_heads a multiple of n_kv_heads, head_dim even")
        if self.block_length % self.denoising_steps:
            raise ValueError("denoising_steps divides block_length")
        if not 0 <= self.mask_id < self.vocab_size:
            raise ValueError("mask_id is a row of the embedding")
        held = tuple(int(e) for e in self.experts_held)
        if not held or len(set(held)) != len(held) or not all(
                0 <= e < self.n_experts for e in held):
            raise ValueError("experts_held: distinct ids under n_experts")
        object.__setattr__(self, "experts_held", held)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def fixed_a_pass(self):
        """Masked positions a denoising pass fixes."""
        return self.block_length // self.denoising_steps

    @property
    def state_spec(self):
        """(paged layers, {pool: a block's shape with None where the
        block's positions go}, no per-lane state): every layer pages its
        keys and values, heads outside a block's positions, the layout
        ``ops/paged_decode`` reads in place.  A lane's current diffusion
        block rides in the engine's lane array (``lane_state``)."""
        block = (self.n_kv_heads, None, self.head_dim)
        return self.n_layers, {"k": block, "v": block}, {}

    @property
    def family(self):
        return SdarPrograms


# -- parameters ---------------------------------------------------------------

def init_params(key, cfg):
    """[in, out] matrices (``x @ w``): ``wqkv`` holds the query, key and
    value columns side by side; ``q_norm`` and ``k_norm`` scale a head's
    dimensions; a layer's ``ffn`` is the routed part of
    ``experts.init_params``' tree.  ``benchmark/weights_sdar.py`` makes the
    same tree from a seed, a layer a call."""
    dt = cfg.jdtype
    d, hd = cfg.d_model, cfg.head_dim
    q_out, kv_out = cfg.n_heads * hd, cfg.n_kv_heads * hd
    held = len(cfg.experts_held)
    keys = iter(jax.random.split(key, 5 * cfg.n_layers + 2))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, dt) * float(fan_in ** -0.5)

    layers = [{
        "ln_attn": jnp.ones((d,), dt),
        "wqkv": dense((d, q_out + 2 * kv_out), d),
        "q_norm": jnp.ones((hd,), dt),
        "k_norm": jnp.ones((hd,), dt),
        "wo": dense((q_out, d), q_out),
        "ln_mlp": jnp.ones((d,), dt),
        "ffn": {
            "router": dense((d, cfg.n_experts), d),
            "w_gate_up": dense((held, d, 2 * cfg.d_ff), d),
            "w_down": dense((held, cfg.d_ff, d), cfg.d_ff),
        },
    } for _ in range(cfg.n_layers)]
    return {"embed": dense((cfg.vocab_size, d), d), "layers": layers,
            "ln_f": jnp.ones((d,), dt),
            "lm_head": dense((d, cfg.vocab_size), d)}


def lm_flops_per_token(cfg, context=0):
    """Model FLOPs a generated token costs HERE, 2 a weight element it
    meets: its position passes the layers ``denoising_steps + 1`` times (the
    denoising passes and the commit), each time through attention, router
    and the share of its ``top_k`` picks that the held experts get under
    even routing; the head once for every pass in which it is still masked,
    half of the denoising passes on average; ``context`` adds attention
    over the keys each pass sees."""
    d, ff, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    q_out, kv_out = cfg.n_heads * hd, cfg.n_kv_heads * hd
    picks_here = cfg.top_k * len(cfg.experts_held) / cfg.n_experts
    layer = (d * (q_out + 2 * kv_out) + q_out * d + d * cfg.n_experts
             + 3 * d * ff * picks_here)
    passes = cfg.denoising_steps + 1
    return int(2 * passes * cfg.n_layers * layer
               + (cfg.denoising_steps + 1) * d * cfg.vocab_size
               + 4 * passes * q_out * cfg.n_layers * int(context))


# -- the layer's parts --------------------------------------------------------

def _rms_norm(x, scale, cfg):
    """RMSNorm of ``x`` over its last dimension, float32 statistics, in the
    activations' type: what the matrix products read."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + cfg.norm_eps)).astype(cfg.jdtype) * scale


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
        h = _rms_norm(x, layer["ln_attn"], cfg)
        q, k, v = jnp.split(h @ layer["wqkv"], [q_out, q_out + kv_out],
                            axis=-1)
        q = _rms_norm(q.reshape(b, t, cfg.n_heads, hd), layer["q_norm"], cfg)
        k = _rms_norm(k.reshape(b, t, cfg.n_kv_heads, hd), layer["k_norm"],
                      cfg)
        v = v.reshape(b, t, cfg.n_kv_heads, hd)
        q, k = (_rope(a, view.pos, cfg.rope_theta) for a in (q, k))
        rows = (b * t, cfg.n_kv_heads, hd)
        pool_k[i] = view.paged_write(pool_k[i], k.reshape(rows))
        pool_v[i] = view.paged_write(pool_v[i], v.reshape(rows))
        mixed = view.attend(q, pool_k[i], pool_v[i])
        x = x + jnp.matmul(mixed.astype(h.dtype), layer["wo"],
                           preferred_element_type=jnp.float32)
        h = _rms_norm(x, layer["ln_mlp"], cfg)
        out, hit = experts.routed(
            h.reshape(b * t, -1), layer["ffn"], cfg.experts_held, cfg.top_k,
            view.real.reshape(-1), score="softmax")
        x = x + out.reshape(b, t, -1)
        counts = counts + hit
    return _rms_norm(x, params["ln_f"], cfg), pool_k, pool_v, counts


def _head(params, x):
    """float32 logits of ``x`` [.., D] through the untied head [D, V]."""
    return jnp.matmul(x, params["lm_head"],
                      preferred_element_type=jnp.float32)


def _counters(cfg, counts):
    held = jnp.int32(cfg.n_layers * len(cfg.experts_held))
    return jnp.concatenate([held[None], counts])


class _BlockView:
    """n lanes, the ``block_length`` positions from ``lens`` [n] each (a
    block's first position: a multiple of the block length, so the rows lie
    in one pool block); ``live`` [n] masks the lanes that are not in the
    tick: they write to the trash block, read nothing and route nowhere."""

    def __init__(self, cfg, tables, lens, live, block_size):
        self.cfg, self.tables, self.block_size = cfg, tables, block_size
        self.lens, self.live = lens, live
        at = jnp.arange(cfg.block_length)
        self.pos = lens[:, None] + at[None]      # [n,B]
        self.real = jnp.broadcast_to(live[:, None], self.pos.shape)
        lane = jnp.arange(lens.shape[0])
        blk = jnp.where(live, tables[lane, lens // block_size], TRASH_BLOCK)
        self._blk = jnp.repeat(blk, cfg.block_length)
        self._at = (self.pos % block_size).reshape(-1)

    def paged_write(self, pool, rows):
        return _write_rows(pool, self._blk, self._at, rows)

    def attend(self, q, pool_k, pool_v):
        """``q`` [n,B,H,hd] over the lanes' caches, this pass's rows
        written: every row of a lane sees ``0 .. lens + B - 1``.  Where the
        kernel can take the pool's blocks as they lie
        (``paged_decode.reads_in_place``) the B positions' query heads of a
        KV head go to it together, ``B x rep`` rows under the lane's one
        length; otherwise ``transformer.paged_attention`` with every row's
        sight at the block's last position."""
        cfg = self.cfg
        n, b = q.shape[:2]
        if not reads_in_place(pool_k):
            sight = jnp.broadcast_to(self.pos[:, -1:], self.pos.shape)
            return paged_attention(q, pool_k, pool_v, self.tables, sight,
                                   cfg, self.block_size).reshape(n, b, -1)
        kv, hd = cfg.n_kv_heads, cfg.head_dim
        qg = q.reshape(n, b, kv, -1, hd).transpose(0, 2, 1, 3, 4) \
            * jnp.asarray(hd ** -0.5, q.dtype)
        out = paged_decode_attention(
            qg.reshape(n, kv, -1, hd), pool_k, pool_v, self.tables,
            jnp.where(self.live, self.lens + b, 0))
        return out.reshape(n, kv, b, -1, hd).transpose(0, 2, 1, 3, 4) \
            .reshape(n, b, -1)


class _PrefillView:
    """One lane, C positions from ``start``; those at or past ``stored``
    (the prompt's whole diffusion blocks) are the first generated block's or
    bucket padding: not stored, routed nowhere.  A chunk of whole pool
    blocks writes whole blocks (``transformer._write_blocks``): the one that
    holds ``stored`` is the lane's own, and what lands in it past
    ``stored`` is rewritten by the block passes before anything reads it."""

    def __init__(self, cfg, width, table, start, stored, block_size):
        self.cfg, self.table, self.block_size = cfg, table, block_size
        pos = start + jnp.arange(width)
        self.pos = pos[None]                              # [1,C]
        self.real = self.pos < stored
        b = cfg.block_length
        self._sight = self.pos // b * b + (b - 1)
        if width % block_size:
            blk = jnp.where(self.real[0], table[pos // block_size],
                            TRASH_BLOCK)
            self.paged_write = lambda pool, rows: _write_rows(
                pool, blk, pos % block_size, rows)
        else:
            first = pos[::block_size]
            blks = jnp.where(first < stored, table[first // block_size],
                             TRASH_BLOCK)
            self.paged_write = lambda pool, rows: _write_blocks(
                pool, rows, blks)

    def attend(self, q, pool_k, pool_v):
        out = paged_attention(q, pool_k, pool_v, self.table[None],
                              self._sight, self.cfg, self.block_size)
        return out.reshape(q.shape[:2] + (-1,))


def block_step(params, tokens, pool_k, pool_v, tables, lens, live, cfg,
               block_size):
    """One pass over the lanes' blocks: ``tokens`` [n,B] (the mask id where
    masked) at positions ``lens .. lens + B - 1``: float32 logits [n,B,V],
    the pools after (the rows' keys and values written), the counts."""
    view = _BlockView(cfg, tables, lens, live, block_size)
    x = jnp.take(params["embed"], tokens, axis=0)
    x, pool_k, pool_v, counts = _layers(params, x, pool_k, pool_v, cfg, view)
    return _head(params, x), pool_k, pool_v, counts


def prefill_step(params, chunk, pool_k, pool_v, table, start, prompt_len,
                 cfg, block_size):
    """``chunk`` [1,C] of a prompt at positions ``start`` ..: the pools
    after (the prompt's whole blocks stored), the counts.  No logits: the
    prompt's positions predict nothing that generation uses."""
    b = cfg.block_length
    view = _PrefillView(cfg, chunk.shape[1], table, start,
                        prompt_len // b * b, block_size)
    x = jnp.take(params["embed"], chunk, axis=0)
    _, pool_k, pool_v, counts = _layers(params, x, pool_k, pool_v, cfg, view)
    return pool_k, pool_v, counts


def first_block(chunk, start, prompt_len, cfg):
    """The state [3,B] of the first generated block of a prompt whose last
    chunk is ``chunk`` [1,C] from ``start``: the prompt's last ``prompt_len
    % B`` tokens known, the rest masked."""
    b = cfg.block_length
    at = prompt_len // b * b + jnp.arange(b)
    known = at < prompt_len
    tokens = jnp.where(
        known, chunk[0, jnp.clip(at - start, 0, chunk.shape[1] - 1)], 0)
    return jnp.stack([tokens, (~known).astype(jnp.int32),
                      jnp.full((b,), -1, jnp.int32)]).astype(jnp.int32)


def denoise(state, chosen, confidence, live, cfg):
    """The lanes' states [n,3,B] after a pass that chose ``chosen`` [n,B]
    with ``confidence`` [n,B] at every position.  A lane with a masked
    position fixes its ``fixed_a_pass`` masked positions of highest
    confidence (ties: the lowest position) to the chosen tokens; a lane with
    none has committed its block and starts the next, all masked; a lane
    that is not ``live`` keeps its state."""
    tokens, masked, fixed_at = (state[:, TOKENS], state[:, MASKED] > 0,
                                state[:, FIXED_AT])
    b = cfg.block_length
    unmasked = b - jnp.sum(masked, axis=1, keepdims=True)
    # a masked position's rank among the masked by confidence: the stable
    # sort puts the lower position first among equals
    order = jnp.argsort(jnp.where(masked, -confidence, jnp.inf), axis=1,
                        stable=True)
    rank = jnp.argsort(order, axis=1)
    fix = masked & (rank < cfg.fixed_a_pass)
    denoised = jnp.stack([
        jnp.where(fix, chosen, tokens), (masked & ~fix).astype(jnp.int32),
        jnp.where(fix, unmasked, fixed_at)], axis=1)
    fresh = jnp.stack([jnp.zeros_like(tokens), jnp.ones_like(tokens),
                       jnp.full_like(tokens, -1)], axis=1)
    committed = ~jnp.any(masked, axis=1)
    out = jnp.where(committed[:, None, None], fresh, denoised)
    return jnp.where(live[:, None, None], out, state).astype(jnp.int32)


def choose(logits, keys, temps, topks):
    """(token [n,B], its softmax probability [n,B]) at every position of
    ``logits`` [n,B,V]: the largest logit's, or for a lane with a
    temperature ``ops/sampling.select_token``'s draw (whose top-k filter
    over the vocabulary does not run while every lane is greedy)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sample = jax.vmap(jax.vmap(select_token, in_axes=(0, 0, None, None)))
    chosen = lax.cond(
        jnp.any(temps > 0.0),
        lambda: sample(logits, keys, temps, topks), lambda: greedy)
    picked = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return chosen, jnp.exp(picked - jax.nn.logsumexp(logits, axis=-1))


# -- the two programs, and the family as the engine asks for it ---------------

def sdar_block_tick(params, state_full, pool_k, pool_v, tables, lens, live,
                    temps, topks, keys_full, *, cfg, n, block_size):
    """One pass over the blocks of the first ``n`` lanes, denoising or
    commit by the mask each lane's state holds (the module's top)."""
    state = state_full[:n]
    tokens = jnp.where(state[:, MASKED] > 0, cfg.mask_id, state[:, TOKENS])
    logits, pool_k, pool_v, counts = block_step(
        params, tokens, pool_k, pool_v, tables, lens, live, cfg, block_size)
    b = cfg.block_length
    keys = jax.vmap(lambda key: jax.random.split(key, b + 1))(keys_full[:n])
    chosen, confidence = choose(logits, keys[:, :b], temps, topks)
    return (state_full.at[:n].set(denoise(state, chosen, confidence, live,
                                          cfg)),
            pool_k, pool_v, keys_full.at[:n].set(keys[:, b]),
            _counters(cfg, counts))


def sdar_prefill_chunk(params, chunk, pool_k, pool_v, table, start,
                       prompt_len, key, temperature, top_k, *, cfg,
                       block_size):
    """One prefill chunk of a lane; what it returns in a token's place is
    the lane's first block, meaningful where the chunk holds the prompt's
    last position."""
    pool_k, pool_v, counts = prefill_step(
        params, chunk, pool_k, pool_v, table, start, prompt_len, cfg,
        block_size)
    return (first_block(chunk, start, prompt_len, cfg), pool_k, pool_v, key,
            _counters(cfg, counts))


class SdarPrograms:
    """This family behind the interface of ``transformer.DecoderPrograms``,
    handed out as ``cfg.family``.  A lane is its blocks (``recurrent`` is
    empty) and its current diffusion block, which rides where the other
    families' next token does.  What the engine learns here and from no
    model's name: ``block``, the positions a lane's tick holds (1 wherever
    the attribute is missing); ``lane_state``, the lane array's shape;
    ``advance``, which of a lane's ticks deliver and which advance it; and
    ``delivered``, the tokens and the order of their fixing in a tick's
    readback.  A block pass is no draft: ``no_verify``."""

    recurrent = ""
    no_verify = (
        "the family has no verify program: a pass over a diffusion block "
        "fixes the block's own positions and is no draft of later ones"
    )
    counters = COUNTERS
    window = None           # every layer attends the whole context
    init_params = staticmethod(init_params)
    generate = None         # no contiguous cache: the engine alone serves it
    quantize_params = None  # no int8 weights
    serving_params = None   # served as published

    def __init__(self, cfg, block_size):
        self.cfg, self.block_size = cfg, block_size
        self.block = cfg.block_length
        if block_size % self.block:
            raise ValueError(
                f"block_size {block_size} is no multiple of the diffusion "
                f"block of {self.block}: a pass's rows lie in one pool block")
        # CPU (the test platform) has no donation support
        self.donate = (2, 3) if jax.default_backend() != "cpu" else ()
        self.flops_per_token = lm_flops_per_token(cfg)
        self._span = STEP_BLOCKS * block_size   # positions a kernel step
        self._static = dict(cfg=cfg, block_size=block_size)
        self.prefill_jit = jax.jit(
            sdar_prefill_chunk, static_argnames=("cfg", "block_size"),
            donate_argnums=self.donate)
        self._tick_jit = jax.jit(
            sdar_block_tick, static_argnames=("cfg", "n", "block_size"),
            donate_argnums=self.donate)

    # -- the schedule, on the host --------------------------------------------

    def lane_state(self, slots):
        """The engine's lane array: a block's state a slot."""
        return jnp.zeros((slots, 3, self.block), jnp.int32)

    def stored(self, prompt_len):
        """Positions of a prompt that its prefill stores: whole blocks."""
        return prompt_len // self.block * self.block

    def masks(self, length, prompt_len):
        """Masked positions of the block at ``length`` before its first
        pass: all, but for the prompt's tail in the first block."""
        return self.block - max(min(prompt_len - length, self.block), 0)

    def advance(self, length, masks):
        """The static schedule: for a lane whose block at ``length`` holds
        ``masks`` masked positions, the pass it runs now: ``(kind, length
        after, masks after, delivers)``.  ``delivers``: the pass removes the
        block's last mask, so its readback holds the block's tokens."""
        if not masks:
            return "commit", length + self.block, self.block, False
        left = max(masks - self.cfg.fixed_a_pass, 0)
        return "denoise", length, left, not left

    def delivered(self, state, first):
        """(tokens, fixed_at) of the positions ``first ..`` of a block whose
        state [3,B] a delivering tick read back, in position order."""
        return (state[TOKENS, first:].tolist(),
                state[FIXED_AT, first:].tolist())

    # -- what an entry of tick_trace() counts ---------------------------------

    def attended_positions(self, max_pos, table_width):
        """Positions a chunk's attention reads for a largest query position
        of ``max_pos``: ``transformer.paged_attention``'s own rule over a
        row's sight, its block's last position."""
        sight = max_pos // self.block * self.block + self.block - 1
        widths = attention_widths(table_width)
        index = attention_width_index(sight, table_width, self.block_size)
        return widths[min(index, len(widths) - 1)] * self.block_size

    def _tick_reads(self, lengths, table_width):
        """The cache positions of each lane that a block pass reads in a
        layer, for lanes whose blocks start at ``lengths``: whole steps of
        the kernel over ``length + B`` positions."""
        return (steps_read(np.asarray(lengths) + self.block, self.block_size)
                * self._span).tolist()

    def tick_fields(self, kind, lengths, start=None, width=None, **_):
        """What the host can count for a ``tick_trace()`` entry, over the
        entry's lanes and every layer: ``kv_positions_live``, the positions
        attention may see (a block pass at ``len``: ``len + B``; a chunk:
        every stored position up to its last real one),
        ``kv_positions_read``, what the trip counts read; on a pass
        ``kv_steps`` and ``kv_steps_full`` (``paged_decode.tick_steps``),
        and ``window_tokens``, which the benchmark's reader of a named
        program's ticks adds up for every family: no layer has a window, so
        it is the contexts' sum."""
        lengths = np.asarray(lengths, np.int64)
        layers = self.cfg.n_layers
        if kind == "prefill_chunk":
            read = self.attended_positions(start + width - 1,
                                           -(-self.cfg.max_seq
                                             // self.block_size))
            return {"kv_positions_live": layers * int(lengths[0]),
                    "kv_positions_read": layers * int(read)}
        seen = lengths + self.block
        return {"kv_positions_live": layers * int(seen.sum()),
                "kv_positions_read": layers * sum(
                    self._tick_reads(lengths, None)),
                "window_tokens": int(lengths.sum()),
                **tick_steps(seen, self.block_size, layers)}

    # -- the dispatches -------------------------------------------------------

    def prefill(self, params, kv, chunk, table, slot, start, prompt_len,
                fresh, key, temperature, top_k):
        with annotation("lm.sdar_prefill_chunk"):
            state, kv.pools["k"], kv.pools["v"], key, counts = \
                self.prefill_jit(
                    params, chunk, kv.pools["k"], kv.pools["v"], table,
                    start, prompt_len, key, temperature, top_k,
                    **self._static)
        return state, key, counts

    def make_tick(self, n):
        return functools.partial(self._tick_jit, n=n, **self._static)

    def tick(self, fn, params, kv, state, tables, lens, live, temps, topks,
             keys):
        with annotation("lm.sdar_block_tick"):
            state, kv.pools["k"], kv.pools["v"], keys, counts = fn(
                params, state, kv.pools["k"], kv.pools["v"], tables, lens,
                live, temps, topks, keys)
        return state, keys, counts
