"""Decoder-only transformer LM — the framework's flagship served model.

This is the server-side model behind BASELINE.md config 5 (tokenizer→LLM
streaming inference with decoupled token-by-token responses) and the model
`__graft_entry__.py` exposes to the driver.  Llama-style architecture:
RMSNorm, rotary embeddings, grouped-query attention, SwiGLU MLP, untied LM
head.  Pure functional JAX:

- ``init_params(key, cfg)`` → pytree matching ``client_tpu.parallel.param_specs``
- ``forward(params, tokens, cfg)`` — full-sequence logits (training/prefill);
  ``attn_impl="ring"`` switches the attention to sequence-parallel ring
  attention over the mesh's ``sp`` axis for long-context sharding
- ``prefill`` / ``decode_step`` — KV-cache incremental decoding for the
  streaming serving path (static cache shape so every step hits the same
  compiled program)
- ``make_train_step(cfg, mesh)`` — jitted dp/tp/sp-sharded Adam training step
  (the multi-chip path the driver dry-runs)

TPU-first notes: weights and attention/MLP compute are bfloat16 on the MXU
with float32 softmax/norm/loss accumulations; shapes are static everywhere;
the decode loop is a fixed-shape program with `lax.dynamic_update_slice` cache
writes; sharding is annotation-only (GSPMD inserts the collectives).
"""

import collections
import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from client_tpu.ops.paged_decode import (
    STEP_BLOCKS, paged_decode_attention, reads_in_place, steps_read,
    tick_steps)
from client_tpu.ops.quant import is_quantized, matmul as _mm
from client_tpu.ops.sampling import accept_lane, select_token
from client_tpu.parallel.ring_attention import (
    plain_attention,
    ring_attention_sharded,
)
from client_tpu.serve.lm.kv import KvBlockPool
from client_tpu.serve.lm.policy import attention_width_index, attention_widths
from client_tpu.serve.models.sambay import _write_rows


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1536
    max_seq: int = 1024
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"
    # n_experts > 0 switches the FFN to a top-k-routed mixture of experts
    # (expert-parallel over the mesh's "ep" axis — parallel.param_specs)
    n_experts: int = 0
    top_k: int = 2
    # Switch-style load-balance aux loss coefficient (loss_fn adds it for
    # MoE configs; without it the router collapses onto few experts)
    router_aux_coef: float = 0.01

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def state_spec(self):
        """What a lane owns, as ``serve/lm/kv.py`` asks every configuration:
        (paged layers, {pool: a block's shape with None where the block's
        positions go}, per-lane fixed state).  Identical layers: every one
        paged, keys and values in blocks ``[n_kv_heads, block_size,
        head_dim]`` (heads outside a block's positions: the layout
        ``ops/paged_decode`` reads in place), nothing beside them."""
        block = (self.n_kv_heads, None, self.head_dim)
        return self.n_layers, {"k": block, "v": block}, {}

    @property
    def family(self):
        """The family's programs, as ``serve/lm.LmEngine`` and
        ``language._LmRunner`` ask every configuration for them."""
        return DecoderPrograms


def init_params(key, cfg):
    """Initialize a params pytree (layout documented in parallel.param_specs)."""
    dt = cfg.jdtype
    n_keys = 3 + cfg.n_layers * 8
    keys = iter(jax.random.split(key, n_keys))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, dt) * float(fan_in ** -0.5)

    hd = cfg.head_dim
    layers = []
    for _ in range(cfg.n_layers):
        entry = {
            "attn": {
                "wq": dense((cfg.d_model, cfg.n_heads * hd), cfg.d_model),
                "wk": dense((cfg.d_model, cfg.n_kv_heads * hd), cfg.d_model),
                "wv": dense((cfg.d_model, cfg.n_kv_heads * hd), cfg.d_model),
                "wo": dense((cfg.n_heads * hd, cfg.d_model), cfg.n_heads * hd),
            },
            "ln_attn": jnp.ones((cfg.d_model,), dt),
            "ln_mlp": jnp.ones((cfg.d_model,), dt),
        }
        if cfg.n_experts > 0:
            e = cfg.n_experts
            entry["moe"] = {
                "router": dense((cfg.d_model, e), cfg.d_model),
                "w_gate": dense((e, cfg.d_model, cfg.d_ff), cfg.d_model),
                "w_up": dense((e, cfg.d_model, cfg.d_ff), cfg.d_model),
                "w_down": dense((e, cfg.d_ff, cfg.d_model), cfg.d_ff),
            }
        else:
            entry["mlp"] = {
                "w_gate": dense((cfg.d_model, cfg.d_ff), cfg.d_model),
                "w_up": dense((cfg.d_model, cfg.d_ff), cfg.d_model),
                "w_down": dense((cfg.d_ff, cfg.d_model), cfg.d_ff),
            }
        layers.append(entry)
    return {
        "embed": dense((cfg.vocab_size, cfg.d_model), cfg.d_model),
        "layers": layers,
        "ln_f": jnp.ones((cfg.d_model,), dt),
        "lm_head": dense((cfg.d_model, cfg.vocab_size), cfg.d_model),
    }


def _rms_norm(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _rope(x, positions, theta):
    # x: [B,T,H,D]; positions: [B,T] or [T]
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _repeat_kv(x, n_rep, axis=2):
    """[B,T,n_kv,hd] -> [B,T,n_kv*n_rep,hd] for the full-sequence kernels,
    which take one K/V head a query head.  ``paged_attention`` contracts a
    KV-head group at a time, and calls it (heads on ``axis`` 1, as the paged
    pool has them) for wide prefill chunks only."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=axis)


def _qkv(h, attn, cfg):
    """The attention's q, k and v of ``h`` [B,T,D], each [B,T,heads,hd].
    A layer holds each projection in one of two forms, and this reads
    whichever it finds: under ``wq_t`` (``wk_t``, ``wv_t``) the serving
    layout of ``serving_params``, [out, in], contracted on its dimension 1
    as the compiled product reads it; under ``wq`` the published [in, out]
    or an int8 pair (``ops.quant``)."""
    b, t = h.shape[:2]

    def project(name, heads):
        w = attn.get(name + "_t")
        y = (_mm(h, attn[name]) if w is None else
             lax.dot_general(h, w, (((h.ndim - 1,), (1,)), ((), ()))))
        return y.reshape(b, t, heads, cfg.head_dim)

    return (project("wq", cfg.n_heads), project("wk", cfg.n_kv_heads),
            project("wv", cfg.n_kv_heads))


def _attention_block(layer, x, cfg, positions, mesh, attn_impl):
    """Full-sequence causal self-attention sublayer; returns (x, (k, v)) so
    prefill can capture the per-layer KV blocks for the cache."""
    b, t, _ = x.shape
    hd = cfg.head_dim
    h = _rms_norm(x, layer["ln_attn"])
    q, k, v = _qkv(h, layer["attn"], cfg)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    n_rep = cfg.n_heads // cfg.n_kv_heads

    if attn_impl in ("ring", "ring_flash"):
        # "ring_flash": the same sp-sharded ring schedule with each step's
        # block pair computed by the Pallas flash kernel (O(block) memory
        # per step — the long-context sharded-training configuration)
        attn = ring_attention_sharded(
            q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep), mesh,
            impl="flash" if attn_impl == "ring_flash" else "plain",
        )
    elif attn_impl == "flash":
        # Pallas kernel (client_tpu.ops): no [T,T] score materialization —
        # the long-context single-shard path.  It has no partitioning rule,
        # so sp-sharded activations would be silently gathered: use "ring"
        # (which consumes the mesh) for sequence-parallel runs.
        if mesh is not None:
            raise ValueError(
                "attn_impl='flash' is single-shard; use attn_impl='ring' "
                "with a mesh"
            )
        from client_tpu.ops import flash_attention

        attn = flash_attention(q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep))
    else:
        attn = plain_attention(q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep))

    out = _mm(attn.reshape(b, t, cfg.n_heads * hd), layer["attn"]["wo"])
    return x + out, (k, v)


def _mlp_block(layer, x):
    h = _rms_norm(x, layer["ln_mlp"])
    gate = jax.nn.silu(_mm(h, layer["mlp"]["w_gate"]))
    up = _mm(h, layer["mlp"]["w_up"])
    return x + _mm(gate * up, layer["mlp"]["w_down"])


def _moe_block(layer, x, cfg):
    """Top-k-routed mixture-of-experts FFN, expert-parallel over ``ep``.

    Dense formulation: every expert computes on every token (stacked-weight
    einsums with the expert dim sharded over ep — each device runs its local
    experts on the MXU) and the router's top-k weights zero out unselected
    experts in the combine; the contraction over experts becomes a psum over
    ep inserted by GSPMD.  Compiler-friendly (static shapes, no gather/sort
    dispatch) and exact; capacity-based sparse dispatch is the big-scale
    optimization this trades away.
    """
    moe = layer["moe"]
    h = _rms_norm(x, layer["ln_mlp"])
    logits = (
        h.astype(jnp.float32) @ moe["router"].astype(jnp.float32)
    )  # [B,T,E]
    top_w, top_idx = lax.top_k(logits, cfg.top_k)
    top_w = jax.nn.softmax(top_w, axis=-1)  # renormalize over the selected k
    combine = jnp.sum(
        jax.nn.one_hot(top_idx, cfg.n_experts, dtype=jnp.float32)
        * top_w[..., None],
        axis=-2,
    )  # [B,T,E]
    g = jnp.einsum("btd,edf->ebtf", h, moe["w_gate"])
    u = jnp.einsum("btd,edf->ebtf", h, moe["w_up"])
    expert_out = jnp.einsum(
        "ebtf,efd->ebtd", jax.nn.silu(g) * u, moe["w_down"]
    )  # [E,B,T,D]
    out = jnp.einsum(
        "ebtd,bte->btd",
        expert_out.astype(jnp.float32),
        combine,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    # Switch-transformer load-balance loss: E * Σ_e (token fraction routed
    # to e) * (mean router prob of e); minimized (=1) at uniform routing
    probs = jax.nn.softmax(logits, axis=-1)
    frac = jnp.mean(
        jax.nn.one_hot(top_idx, cfg.n_experts, dtype=jnp.float32), axis=(0, 1, 2)
    )  # per-expert routed fraction over B*T*K; uniform router → 1/E each
    aux = cfg.n_experts * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))
    return x + out, aux


def _ffn_block(layer, x, cfg):
    """FFN (dense or MoE) → (residual output, router aux loss or 0)."""
    if "moe" in layer:
        return _moe_block(layer, x, cfg)
    return _mlp_block(layer, x), jnp.float32(0.0)


def forward(params, tokens, cfg, mesh=None, attn_impl="plain",
            with_aux=False):
    """Full-sequence causal LM: tokens [B,T] int32 → logits [B,T,V] f32.

    With ``with_aux=True`` returns ``(logits, aux)`` where aux is the mean
    per-layer router load-balance loss (0 for dense configs).
    """
    b, t = tokens.shape
    if mesh is not None:
        if is_quantized(params["lm_head"]):
            # the int8 pallas_call has no partitioning rule; GSPMD would
            # silently gather sharded activations into it (same hazard the
            # flash branch guards against)
            raise ValueError(
                "quantized params are single-device serving weights; "
                "dequantize or drop the mesh"
            )
    x = jnp.take(params["embed"], tokens, axis=0)
    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", "sp", None))
        )
    positions = jnp.arange(t)
    aux_total = jnp.float32(0.0)
    for layer in params["layers"]:
        x, _ = _attention_block(layer, x, cfg, positions, mesh, attn_impl)
        x, aux = _ffn_block(layer, x, cfg)
        aux_total = aux_total + aux
    x = _rms_norm(x, params["ln_f"])
    logits = _mm(x, params["lm_head"]).astype(jnp.float32)
    if mesh is not None:
        logits = lax.with_sharding_constraint(
            logits, NamedSharding(mesh, P("dp", "sp", "tp"))
        )
    if with_aux:
        return logits, aux_total / len(params["layers"])
    return logits


def init_cache(cfg, batch):
    """Static-shape KV cache: per layer k/v [B, max_seq, n_kv, head_dim]."""
    shape = (batch, cfg.max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": [jnp.zeros(shape, cfg.jdtype) for _ in range(cfg.n_layers)],
        "v": [jnp.zeros(shape, cfg.jdtype) for _ in range(cfg.n_layers)],
        "len": jnp.zeros((batch,), jnp.int32),
    }


def prefill(params, tokens, cfg, cache):
    """Run the prompt through the model, filling the cache from position 0.

    Returns (last-token logits [B,V], cache).
    """
    b, t = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.arange(t)
    for i, layer in enumerate(params["layers"]):
        x, (k, v) = _attention_block(layer, x, cfg, positions, None, "plain")
        cache["k"][i] = lax.dynamic_update_slice(
            cache["k"][i], k, (0, 0, 0, 0)
        )
        cache["v"][i] = lax.dynamic_update_slice(
            cache["v"][i], v, (0, 0, 0, 0)
        )
        x, _ = _ffn_block(layer, x, cfg)
    x = _rms_norm(x, params["ln_f"])
    logits = _mm(x[:, -1], params["lm_head"]).astype(jnp.float32)
    cache["len"] = jnp.full((b,), t, jnp.int32)
    return logits, cache


def decode_step(params, token, cfg, cache):
    """One incremental decode step: token [B] int32 → (logits [B,V], cache)."""
    b = token.shape[0]
    x = jnp.take(params["embed"], token, axis=0)[:, None, :]  # [B,1,D]
    pos = cache["len"]  # [B]
    for i, layer in enumerate(params["layers"]):
        hd = cfg.head_dim
        h = _rms_norm(x, layer["ln_attn"])
        q, k, v = _qkv(h, layer["attn"], cfg)
        q = _rope(q, pos[:, None], cfg.rope_theta)
        k = _rope(k, pos[:, None], cfg.rope_theta)
        # write this step's k/v at position `pos` (same for all batch rows in
        # the serving path; use per-row dynamic slice via one-hot scatter)
        # overwrite (not add) the slot at `pos` so a reused cache with stale
        # rows beyond the prompt can't corrupt this step's K/V
        slot = (jnp.arange(cfg.max_seq)[None, :] == pos[:, None])[:, :, None, None]
        cache["k"][i] = jnp.where(slot, k, cache["k"][i])
        cache["v"][i] = jnp.where(slot, v, cache["v"][i])
        # attention against the full static-shape cache, length-masked
        n_rep = cfg.n_heads // cfg.n_kv_heads
        kk = _repeat_kv(cache["k"][i], n_rep)
        vv = _repeat_kv(cache["v"][i], n_rep)
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, kk, preferred_element_type=jnp.float32
        ) * (hd ** -0.5)
        valid = jnp.arange(cfg.max_seq)[None, :] <= pos[:, None]
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vv.dtype), vv)
        out = _mm(attn.reshape(b, 1, cfg.n_heads * hd), layer["attn"]["wo"])
        x = x + out.astype(x.dtype)
        x, _ = _ffn_block(layer, x, cfg)
    x = _rms_norm(x, params["ln_f"])
    logits = _mm(x[:, 0], params["lm_head"]).astype(jnp.float32)
    cache["len"] = pos + 1
    return logits, cache


def paged_attention(q, pool_k, pool_v, tables, pos, cfg, block_size):
    """Attention of ``q`` ([B,T,H,hd], already roped) against a PAGED KV
    cache: ``pool_k``/``pool_v`` are one layer's block pools
    ([n_blocks+1, n_kv, block_size, hd], serve/lm/kv.KvBlockPool layout:
    heads outside a block's positions) and ``tables`` ([B, table_width]
    int32) maps each lane's logical
    block index to its physical pool block.  Length-masked at ``pos``
    ([B,T] logical query positions; keys at logical position j attend
    iff j <= pos), so trash-mapped rows are never read.

    This is the serving cache layout of serve/lm: the contiguous
    ``init_cache`` [B, max_seq, ...] layout pins max_seq rows per lane
    forever; the paged layout pools HBM across lanes and a lane holds
    only ceil((prompt+budget)/block_size) blocks.

    Only the table columns that the call's LARGEST position reaches are
    read, in groups of an eighth of the table.  The width is chosen on
    the device, inside the one executable:
    ``serve/lm/policy.attention_widths`` gives the widths (its first is
    the group), ``policy.attention_width_index`` picks from ``pos``, and
    a ``lax.fori_loop`` with that traced trip count runs
    ``_attend_columns`` on one group of columns after another, keeping
    the softmax's running maximum, sum and weighted sum in float32.
    Every key a query may see lies in a group the loop reaches and a
    masked score contributes an exact zero, so the result is the whole
    table's to rounding (PERF.md section 6, PR 30).  A table with one
    width has no loop at all.

    This is the read of every call with more than one query row a lane (a
    prefill chunk, a verify tick) and of a decode tick whose pool
    ``ops/paged_decode`` cannot take as it lies; the decode tick otherwise
    reads in place (``paged_layers``).
    """
    b, t = q.shape[:2]
    table_width = tables.shape[-1]
    widths = attention_widths(table_width)
    if len(widths) == 1:
        _, l, acc = _attend_columns(q, pool_k, pool_v, tables, pos, 0, cfg,
                                    block_size)
        return (acc / l).astype(q.dtype)
    group = widths[0]
    # whole groups, the last one over trash columns that no position reaches
    tables = jnp.pad(tables, ((0, 0), (0, group * len(widths) - table_width)))

    def step(g, carry):
        m, l, acc = carry
        m_g, l_g, acc_g = _attend_columns(
            q, pool_k, pool_v,
            lax.dynamic_slice_in_dim(tables, g * group, group, axis=1),
            pos, g * group * block_size, cfg, block_size)
        m_new = jnp.maximum(m, m_g)
        old, new = jnp.exp(m - m_new), jnp.exp(m_g - m_new)
        return m_new, l * old + l_g * new, acc * old + acc_g * new

    rows = (b, t, cfg.n_heads, 1)
    n_groups = jnp.minimum(
        attention_width_index(jnp.max(pos), table_width, block_size) + 1,
        len(widths))
    m, l, acc = lax.fori_loop(0, n_groups, step, (
        jnp.full(rows, -jnp.inf, jnp.float32),
        jnp.zeros(rows, jnp.float32),
        jnp.zeros(rows[:3] + (cfg.head_dim,), jnp.float32)))
    return (acc / l).astype(q.dtype)


def _attend_columns(q, pool_k, pool_v, tables, pos, first, cfg, block_size):
    """``paged_attention`` over the columns of ``tables`` alone, which hold
    logical positions ``first`` onward (S = ``tables.shape[-1] *
    block_size`` of them a lane): each query row's maximum score ``m``,
    the sum ``l`` of ``exp(score - m)`` and the sum ``acc`` of those
    weights times V, [B,T,H,1], [B,T,H,1] and [B,T,H,hd] in float32.
    ``acc / l`` is the attention over these columns; two sets of columns
    combine as ``paged_attention`` does.

    The queries of a KV-head group are contracted against that group's
    gathered keys and values directly: ``q`` as [B,T,n_kv,n_rep,hd]
    against K [B,n_kv,S,hd] (``pool[tables]``, a lane's blocks side by
    side under each head) is ONE ``dot_general`` with batch dimensions
    (lane, KV head), n_rep x T query rows a group, scores
    [B,n_kv,n_rep,T,S] accumulated in float32 from operands at their
    stored width; mask, scale and softmax stay float32; the weighted sum
    is the mirror contraction over V [B,n_kv,S,hd] with the weights cast
    to V's type.  So the gathered K and V are read once each as stored:
    no copy at n_heads, none in float32 (at Mistral-7B widths and 16
    lanes those were 1 GB written a layer a tick: PERF.md section 6,
    PR 28).  With n_rep 1 (MHA) a group is one head and the same code
    runs.

    A chunk of 2 * hd query rows or more (a prefill chunk, never a
    decode or verify tick) takes the per-head form over ``_repeat_kv``
    instead, within each set of columns.  There the float32 scores [B,H,T,S] outweigh the repeated
    K and V (hd : 2T in bytes), and XLA fuses that form's scores, row
    maximum, exponential and row sum into one pass over them, where the
    grouped form's get a pass more (a 512-wide chunk on the v5e: 29.8
    ms against 33.1, PERF.md section 6, PR 28).  ``_repeat_kv`` is
    otherwise for the full-sequence path (``_attention_block``) and the
    contiguous-cache ``decode_step``: tests/test_paged_attention.py
    holds the decode tick's program to that.
    """
    b, t = q.shape[:2]
    hd = cfg.head_dim
    n_kv = cfg.n_kv_heads
    n_rep = cfg.n_heads // n_kv
    s_len = tables.shape[-1] * block_size
    # [B,W,n_kv,block,hd] -> [B,n_kv,S,hd]
    kk = jnp.swapaxes(pool_k[tables], 1, 2).reshape(b, n_kv, s_len, hd)
    vv = jnp.swapaxes(pool_v[tables], 1, 2).reshape(b, n_kv, s_len, hd)
    valid = first + jnp.arange(s_len)[None, None, :] <= pos[:, :, None]
    if t >= 2 * hd:
        # spelled out, not folded into the grouped einsums as n_rep 1: with
        # their size-1 axes XLA no longer fuses this softmax into one pass
        kk, vv = _repeat_kv(kk, n_rep, axis=1), _repeat_kv(vv, n_rep, axis=1)
        s = jnp.einsum(
            "bqhd,bhkd->bhqk", q, kk, preferred_element_type=jnp.float32
        ) * (hd ** -0.5)
        s = jnp.where(valid[:, None], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        acc = jnp.einsum("bhqk,bhkd->bqhd", p.astype(vv.dtype), vv,
                         preferred_element_type=jnp.float32)
        rows = lambda x: x.transpose(0, 2, 1, 3)  # [B,H,T,1] -> [B,T,H,1]
        return rows(m), rows(jnp.sum(p, axis=-1, keepdims=True)), acc
    qg = q.reshape(b, t, n_kv, n_rep, hd)
    s = jnp.einsum(
        "btgrd,bgsd->bgrts", qg, kk, preferred_element_type=jnp.float32
    ) * (hd ** -0.5)
    s = jnp.where(valid[:, None, None], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    acc = jnp.einsum("bgrts,bgsd->bgrtd", p.astype(vv.dtype), vv,
                     preferred_element_type=jnp.float32)
    # [B,n_kv,n_rep,T,x] -> [B,T,H,x]
    rows = lambda x: x.transpose(0, 3, 1, 2, 4).reshape(
        b, t, cfg.n_heads, x.shape[-1])
    return rows(m), rows(jnp.sum(p, axis=-1, keepdims=True)), rows(acc)


# -- the paged decoder: one layer loop, and the three programs the engine runs --

def _write_blocks(pool, rows, blks):
    """``pool[blks[j]]`` = positions ``j * block ..`` of ``rows`` [N, n_kv,
    hd], N whole blocks, for a layer's pool [n_blocks+1, n_kv, block, hd]:
    each block one contiguous [n_kv, block, hd] copy, where
    ``sambay._write_rows`` (the write of everything that is no whole block:
    N x n_kv rows of hd, the pool's minor dimension) would scatter N x n_kv
    rows (2.2 ms a 512-wide chunk over four layers in the rag cell:
    PERF.md section 5)."""
    n_kv, block, hd = pool.shape[1:]
    return pool.at[blks].set(
        rows.reshape(-1, block, n_kv, hd).swapaxes(1, 2))


@functools.partial(jax.jit, static_argnames="cfg")
def _attend_in_place(q, pool_k, pool_v, tables, lengths, cfg):
    """One query row a lane, ``q`` [n,1,H,hd], over the lane's first
    ``lengths`` [n] positions through ``ops/paged_decode``: the blocks are
    read where they lie, each lane to its own length, a lane of length 0
    not at all.  The query rows of a KV head go in together, scaled in
    their own type; operands reach the matrix unit as stored, scores and
    softmax are float32, as in ``_attend_columns``.  Jitted, so that a
    tick's layers trace and lower the kernel once between them: sixteen
    times over cost the chat cell 1.2 s of every start (PERF.md section 6,
    PR 34); the compiled tick is the same."""
    n, hd = q.shape[0], cfg.head_dim
    qg = q[:, 0].reshape(n, cfg.n_kv_heads, -1, hd) \
        * jnp.asarray(hd ** -0.5, q.dtype)
    out = paged_decode_attention(qg, pool_k, pool_v, tables, lengths)
    return out.reshape(n, 1, cfg.n_heads, hd).astype(q.dtype)


def paged_layers(params, x, pool_k, pool_v, tables, pos, write, cfg,
                 block_size, lengths=None):
    """Every layer over the embedded ``x`` [B,T,D] against the PAGED cache
    (a layer's pool [n_blocks+1, n_kv, block_size, hd]): the one layer loop
    of the three programs below, which differ in the shape of ``x`` and in
    what they do before and after it.  ``pos`` [B,T] are the logical
    positions, ``tables`` [B,W] the lanes' block tables, and ``write(pool,
    rows)`` puts the B*T new K or V rows ([B*T, n_kv, hd], lane-major) where
    their positions live in a layer's pool, padding in the trash block:
    ``sambay._write_rows`` at each row's ``(table[pos // block_size], pos
    % block_size)``, or ``_write_blocks`` for a chunk of whole blocks.
    Returns the final-normed ``x`` and the pools.

    Then attention reads the rows back through the tables: position ``pos``
    attends positions ``<= pos``, all of which this call or an earlier one
    wrote.  One algorithm, two reads, chosen by what the call brings: with
    one query row a lane (the decode tick, which says so by giving each
    lane's ``lengths`` [B], 0 for a lane that is not in the tick) and a
    pool that ``ops/paged_decode`` can take as it lies
    (``reads_in_place``), the kernel reads each lane's blocks in place up
    to its own length; otherwise ``paged_attention`` gathers the table a
    group of columns at a time."""
    b, t = x.shape[:2]
    hd = cfg.head_dim
    pool_k, pool_v = list(pool_k), list(pool_v)
    in_place = lengths is not None and reads_in_place(pool_k[0])
    for i, layer in enumerate(params["layers"]):
        h = _rms_norm(x, layer["ln_attn"])
        q, k, v = _qkv(h, layer["attn"], cfg)
        q = _rope(q, pos, cfg.rope_theta)
        k = _rope(k, pos, cfg.rope_theta)
        rows = (b * t, cfg.n_kv_heads, hd)
        pool_k[i] = write(pool_k[i], k.reshape(rows))
        pool_v[i] = write(pool_v[i], v.reshape(rows))
        if in_place:
            attn = _attend_in_place(
                q, pool_k[i], pool_v[i], tables, lengths, cfg)
        else:
            attn = paged_attention(
                q, pool_k[i], pool_v[i], tables, pos, cfg, block_size)
        out = _mm(
            attn.reshape(b, t, cfg.n_heads * hd), layer["attn"]["wo"]
        )
        x = x + out.astype(x.dtype)
        x, _ = _ffn_block(layer, x, cfg)
    return _rms_norm(x, params["ln_f"]), pool_k, pool_v


def paged_decode_tick(params, tokens_full, pool_k, pool_v, tables, lens,
                      live, temps, topks, keys_full, *, cfg, n, block_size):
    """One batched decode step over the first ``n`` lanes (n is static:
    one executable per configured lane count), each lane's pending token
    at position ``lens``, the next one chosen on the device.  ``live`` [n]
    masks the lanes that are not in the tick (idle, or at their budget):
    they write to the trash block and read nothing."""
    x = jnp.take(params["embed"], tokens_full[:n], axis=0)[:, None, :]
    blk = jnp.where(live, tables[jnp.arange(n), lens // block_size],
                    KvBlockPool.TRASH)  # [n] physical blocks
    x, pool_k, pool_v = paged_layers(
        params, x, pool_k, pool_v, tables, lens[:, None],
        lambda pool, rows: _write_rows(pool, blk, lens % block_size, rows),
        cfg, block_size, lengths=jnp.where(live, lens + 1, 0))
    logits = _mm(x[:, 0], params["lm_head"]).astype(jnp.float32)  # [n,V]
    pairs = jax.vmap(functools.partial(jax.random.split, num=2))(
        keys_full[:n]
    )
    nxt = jax.vmap(select_token)(logits, pairs[:, 0], temps, topks)
    tokens_out = tokens_full.at[:n].set(nxt)
    keys_out = keys_full.at[:n].set(pairs[:, 1])
    return tokens_out, pool_k, pool_v, keys_out


def paged_verify_tick(params, tokens_full, pool_k, pool_v, tables, lens,
                      temps, topks, keys_full, props, counts, *, cfg, n,
                      width, block_size):
    """One speculative verify step over the first ``n`` lanes: embed the
    pending input token plus up to ``width - 1`` drafted tokens per lane
    and score all of them in ONE multi-position pass (``paged_decode_tick``
    generalized from T = 1 to T = width).

    K/V for every drafted position scatters into the lane's own block
    reservation as it is computed (position ``lens + j`` attends only
    positions ``<= lens + j``, all of which this tick or history wrote),
    so accepted positions need no second write.  Positions past the
    lane's draft count write to the trash block (the prefill padding
    trick); positions past the ACCEPTED prefix hold garbage the length
    mask never reads — the host advances ``lane.length`` only to the
    accepted end, and the next tick overwrites from there.  Rejection
    therefore "rewinds" by pointer arithmetic alone: no block ever
    leaves the lane's reservation, so nothing can leak.

    Returns ``(out, tokens_out, pool_k, pool_v, keys_out)`` where
    ``out`` is ``[2, n]`` (accepted count, correction token) — one
    host readback for the whole tick.  ``n`` and ``width`` are static:
    executables stay ``<= len(verify_widths) * len(lane_counts)``.
    """
    w = width
    seq = jnp.concatenate([tokens_full[:n, None], props], axis=1)  # [n,w]
    x = jnp.take(params["embed"], seq, axis=0)  # [n,w,D]
    pos = lens[:, None] + jnp.arange(w)[None, :]  # [n,w]
    writable = jnp.arange(w)[None, :] <= counts[:, None]
    col = jnp.minimum(pos // block_size, tables.shape[1] - 1)
    blk = jnp.where(
        writable, jnp.take_along_axis(tables, col, axis=1),
        KvBlockPool.TRASH,
    )
    x, pool_k, pool_v = paged_layers(
        params, x, pool_k, pool_v, tables, pos,
        lambda pool, rows: _write_rows(
            pool, blk.reshape(-1), (pos % block_size).reshape(-1), rows),
        cfg, block_size)
    logits = _mm(x, params["lm_head"]).astype(jnp.float32)  # [n,w,V]
    keys = jax.vmap(functools.partial(jax.random.split, num=w + 1))(
        keys_full[:n]
    )  # [n, w+1, 2]: w-1 accept draws, 1 correction sample, 1 carry
    n_acc, corr = jax.vmap(
        functools.partial(accept_lane, width=w)
    )(logits, props, counts, temps, topks, keys)
    tokens_out = tokens_full.at[:n].set(corr)
    keys_out = keys_full.at[:n].set(keys[:, w])
    out = jnp.stack([n_acc, corr])  # [2, n]: one readback per tick
    return out, tokens_out, pool_k, pool_v, keys_out


def paged_prefill_chunk(params, chunk, pool_k, pool_v, table, start,
                        prompt_len, key, temperature, top_k, *, cfg,
                        block_size):
    """One prefill chunk ([1, C] tokens at logical positions
    start..start+C-1) written straight into the paged pool.

    Positions >= prompt_len (bucket padding) are never attended (the
    length mask), so padding is inert.  A chunk of whole blocks (C a
    multiple of ``block_size``) starts on a block edge: the engine's plan
    (``policy.chunk_plan``) starts behind whole adopted blocks and steps
    by its widest bucket, which every multi-chunk plan's chunks have.  It
    writes whole blocks: one wholly at or past ``prompt_len`` goes to the
    trash block; the one that holds ``prompt_len`` is the lane's own, and
    what lands in it past the prompt is never read and overwritten as the
    lane decodes.  Any other width writes rows, padding's to the trash
    block.  The returned token is the sampled/greedy first generation
    token — only the FINAL chunk's return is meaningful (its chunk
    contains position prompt_len - 1)."""
    c = chunk.shape[1]
    x = jnp.take(params["embed"], chunk, axis=0)  # [1,C,D]
    pos = start + jnp.arange(c)  # [C] logical positions
    if c % block_size:
        blk = jnp.where(
            pos < prompt_len, table[pos // block_size], KvBlockPool.TRASH)
        write = lambda pool, rows: _write_rows(
            pool, blk, pos % block_size, rows)
    else:
        first = pos[::block_size]  # [C / block_size] each block's first
        blks = jnp.where(first < prompt_len, table[first // block_size],
                         KvBlockPool.TRASH)
        write = lambda pool, rows: _write_blocks(pool, rows, blks)
    x, pool_k, pool_v = paged_layers(
        params, x, pool_k, pool_v, table[None], pos[None], write, cfg,
        block_size)
    last = jnp.clip(prompt_len - 1 - start, 0, c - 1)
    xsel = jnp.take(x, last[None], axis=1)  # [1,1,D]
    logits = _mm(xsel[:, 0], params["lm_head"]).astype(jnp.float32)[0]
    k_sample, k_carry = jax.random.split(key)
    tok = select_token(logits, k_sample, temperature, top_k)
    return tok, pool_k, pool_v, k_carry


def lm_flops_per_token(cfg, context=0):
    """Model FLOPs one generated token costs (the MFU denominator for
    `tokens/sec` headlines, the LM analog of vision.cnn_flops_per_image).

    Counts 2 FLOPs per weight element in every matmul a token traverses
    (the PaLM 2N convention): attention projections, FFN (top_k experts
    for MoE configs — the routed math, not the dense formulation's
    all-experts execution), and the lm_head.  ``context`` > 0 adds the
    attention score/combine term (4 * n_heads * head_dim * context per
    layer), which depends on live sequence length; pass a typical
    context (e.g. prompt_len + max_tokens/2) for decode-phase MFU.
    """
    hd = cfg.head_dim
    attn_w = (
        cfg.d_model * cfg.n_heads * hd          # wq
        + 2 * cfg.d_model * cfg.n_kv_heads * hd  # wk, wv
        + cfg.n_heads * hd * cfg.d_model         # wo
    )
    ffn_active = 3 * cfg.d_model * cfg.d_ff
    if cfg.n_experts > 0:
        ffn_active *= cfg.top_k
        ffn_active += cfg.d_model * cfg.n_experts  # router
    per_layer = 2 * (attn_w + ffn_active)
    per_layer += 4 * cfg.n_heads * hd * int(context)  # scores + combine
    head = 2 * cfg.d_model * cfg.vocab_size
    return cfg.n_layers * per_layer + head


def _next_token_nll(logits, targets):
    """Mean next-token cross-entropy: logits [B,T,V] f32, targets [B,T]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.mean(-jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss_fn(params, tokens, cfg, mesh=None, attn_impl="plain"):
    """Next-token cross-entropy over tokens [B,T] (+ router aux for MoE)."""
    logits, aux = forward(
        params, tokens[:, :-1], cfg, mesh, attn_impl, with_aux=True
    )
    loss = _next_token_nll(logits, tokens[:, 1:])
    if cfg.n_experts > 0:
        loss = loss + cfg.router_aux_coef * aux
    return loss


def _make_adam_step(loss, learning_rate):
    """Shared Adam scaffolding: (loss(params, tokens) -> scalar) → jitted
    ``step(params, opt_state, tokens) -> (params, opt_state, loss)``."""
    import optax

    opt = optax.adam(learning_rate)

    def step(params, opt_state, tokens):
        value, grads = jax.value_and_grad(loss)(params, tokens)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, value

    return opt, jax.jit(step, donate_argnums=(0, 1))


def make_train_step(cfg, mesh=None, attn_impl="plain", learning_rate=1e-3):
    """Jitted Adam train step.  With a mesh, callers should device_put params
    per ``parallel.param_specs`` and the batch per ``parallel.batch_spec``;
    GSPMD propagates those shardings through grads and optimizer state."""
    return _make_adam_step(
        lambda params, tokens: loss_fn(params, tokens, cfg, mesh, attn_impl),
        learning_rate,
    )


def quantize_params(params):
    """Int8 weight-only quantization of the serving weights.

    Every 2D projection (attention, dense MLP, LM head) becomes a
    {"q": int8, "s": f32} pair consumed by the Pallas dequant-matmul
    (client_tpu.ops.quant) — halving weight HBM traffic on the
    bandwidth-bound decode path.  The embedding stays full-precision (it is
    a gather, not a matmul); MoE expert stacks keep their einsum path.
    This is a serving transform: quantized params are not trainable.
    """
    from client_tpu.ops.quant import quantize_int8

    def q_layer(layer):
        out = {
            # a projection in the serving layout (``serving_params``) is
            # quantized as published: the kernel reads [in, out]
            "attn": {k.removesuffix("_t"): quantize_int8(
                         w.T if k.endswith("_t") else w)
                     for k, w in layer["attn"].items()},
            "ln_attn": layer["ln_attn"],
            "ln_mlp": layer["ln_mlp"],
        }
        if "mlp" in layer:
            out["mlp"] = {
                k: quantize_int8(w) for k, w in layer["mlp"].items()
            }
        if "moe" in layer:
            out["moe"] = layer["moe"]
        return out

    return {
        "embed": params["embed"],
        "layers": [q_layer(layer) for layer in params["layers"]],
        "ln_f": params["ln_f"],
        "lm_head": quantize_int8(params["lm_head"]),
    }


def serving_params(params):
    """The serving layout of the decoder's params, made once where params
    enter serving (``language._LmRunner``): each layer's ``wq``, ``wk`` and
    ``wv`` held as [out, in] under ``wq_t``, ``wk_t`` and ``wv_t``.  The
    compiled products read a projection with its contracting dimension
    minor; handed the published [in, out], a tick or a chunk copies all
    three of every layer into that layout on each call.  An int8 pair
    stays as it is: its kernel reads [in, out].  ``_qkv`` reads either
    form, so the serial path and training (which keeps the published tree)
    are unchanged.

    Takes the params over: a layer is replaced in the params' own list as
    its copy is made, so the published projections are let go a layer at
    a time and not all of them at the end."""
    layers = params.get("layers", [])  # a runner may be given none
    for i, layer in enumerate(layers):
        attn = dict(layer["attn"])
        for name in ("wq", "wk", "wv"):
            if name in attn and not is_quantized(attn[name]):
                attn[name + "_t"] = attn.pop(name).T
        layers[i] = {**layer, "attn": attn}
    return params


def stack_pipeline_params(params, n_stages):
    """Re-lay the per-layer list as pipeline stages (parallel.pipeline)."""
    from client_tpu.parallel.pipeline import stack_stage_params

    return {
        "embed": params["embed"],
        "stages": stack_stage_params(params["layers"], n_stages),
        "ln_f": params["ln_f"],
        "lm_head": params["lm_head"],
    }


def forward_pipelined(pparams, tokens, cfg, mesh, n_microbatches):
    """Full-sequence logits with the layer stack pipelined over ``pp``.

    Embedding and LM head run outside the pipeline region (replicated);
    each stage scans its local layer block over the incoming microbatch,
    whose batch dim shards over ``dp`` (parallel.pipeline batch_axis).
    """
    from client_tpu.parallel.pipeline import pipeline_apply

    b, t = tokens.shape
    x = jnp.take(pparams["embed"], tokens, axis=0)
    positions = jnp.arange(t)

    def stage_fn(stage_layers, h):
        def layer_step(hh, layer):
            hh, _ = _attention_block(layer, hh, cfg, positions, None, "plain")
            hh, _ = _ffn_block(layer, hh, cfg)
            return hh, None

        h, _ = lax.scan(layer_step, h, stage_layers)
        return h

    x = pipeline_apply(stage_fn, pparams["stages"], x, mesh, n_microbatches)
    x = _rms_norm(x, pparams["ln_f"])
    return _mm(x, pparams["lm_head"]).astype(jnp.float32)


def make_pipeline_train_step(cfg, mesh, n_microbatches, learning_rate=1e-3):
    """Jitted Adam train step over pipeline-stacked params: gradients flow
    back through the scan + ppermute schedule (reverse ppermute).  Pipeline
    composes with data parallelism (the microbatch shards over ``dp``
    inside the region — parallel.pipeline); stage weights are replicated
    over tp/ep within the region, and MoE router aux loss is not collected
    on this path."""

    def loss(pparams, tokens):
        logits = forward_pipelined(
            pparams, tokens[:, :-1], cfg, mesh, n_microbatches
        )
        return _next_token_nll(logits, tokens[:, 1:])

    return _make_adam_step(loss, learning_rate)


@functools.lru_cache(maxsize=8)
def _jitted_steps(cfg):
    """Per-config jitted prefill/decode (cfg is a frozen dataclass, hashable);
    caching here keeps repeated generate() calls on the same compiled programs."""
    return (
        jax.jit(functools.partial(prefill, cfg=cfg)),
        jax.jit(functools.partial(decode_step, cfg=cfg)),
    )


def generate(params, cfg, prompt, max_new_tokens, temperature=0.0, key=None,
             readback_depth=8, stop_tokens=()):
    """Greedy/sampled generation; yields one int token id at a time.

    Python-level loop over jitted prefill/decode steps — each yield maps to
    one decoupled KServe response in the streaming serving path.  Generation
    stops early if the KV cache fills (prompt_len + new tokens > cfg.max_seq)
    or a ``stop_tokens`` id is produced (the stop token is still yielded).

    The decode loop is pipelined: step i's token is selected on device and
    its D2H copy started with ``copy_to_host_async`` while decode step i+1
    is dispatched, keeping up to ``readback_depth`` readbacks in flight.
    Token selection stays on device, so the compute schedule — and the token
    stream — is identical to the serial order (``readback_depth=0``); only
    the host-side readback is deferred.  Over a high-RTT link this lifts the
    per-token cost from one full round trip (the blocking ``np.asarray`` in
    the serial loop) to ~RTT/depth, and on a local chip it overlaps readback
    with decode compute.

    Cost of the pipeline: a stop token is only *known* on host one readback
    latency after its decode step ran, so up to ``readback_depth`` decode
    steps past the stop get dispatched and discarded.  That waste is
    information-theoretic for any scheme that keeps the link busy (the host
    cannot know sooner), and bounded by depth; ``readback_depth=0`` restores
    the strict serial no-waste schedule.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim == 1:
        prompt = prompt[None, :]
    if temperature > 0.0 and key is None:
        key = jax.random.PRNGKey(0)
    # the cache slot for step i's token is prompt_len + i; the last usable
    # slot is max_seq - 1
    max_new_tokens = min(max_new_tokens, cfg.max_seq - prompt.shape[1])
    cache = init_cache(cfg, prompt.shape[0])
    prefill_fn, decode_fn = _jitted_steps(cfg)
    logits, cache = prefill_fn(params, prompt, cache=cache)
    depth = max(int(readback_depth), 0)
    stop = frozenset(int(t) for t in stop_tokens)
    pending = collections.deque()
    for i in range(max_new_tokens):
        if temperature > 0.0:
            key, sub = jax.random.split(key)
            token = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            token = jnp.argmax(logits, axis=-1)
        token = token.astype(jnp.int32)
        token.copy_to_host_async()
        pending.append(token)
        if i + 1 < max_new_tokens:
            logits, cache = decode_fn(params, token, cache=cache)
        while len(pending) > depth:
            t = int(np.asarray(pending.popleft())[0])
            yield t
            if t in stop:
                return  # stop dispatching; in-flight steps are discarded
    while pending:
        t = int(np.asarray(pending.popleft())[0])
        yield t
        if t in stop:
            return


class DecoderPrograms:
    """A model family as the serving path sees it, handed out by the
    family's configuration (``cfg.family``; ``sambay.SambaYPrograms`` is
    the other one) so that neither the engine nor the runner picks a family
    by type.

    ``cfg.family(cfg, block_size)`` is what ``LmEngine`` dispatches:
    ``prefill`` runs one (1, C) chunk of a lane's prompt, ``tick`` one
    (n, 1) decode step, the program ``make_verify`` gives one (n, w)
    speculative verify step; ``prefill`` and ``tick`` take the
    ``KvBlockPool`` and leave the arrays their program returned in it.
    This one is the decoder of identical layers (``TransformerConfig``): a
    lane is its blocks ([n_kv_heads, block_size, head_dim] each, the layout
    all three families hold and ``ops/paged_decode`` reads in place), so of
    the lane arguments (host values, which only a family that uses them
    sends to the device) ``slot`` and ``fresh`` have nothing to act on;
    ``live`` tells the tick which lanes read and write.

    On the class, what ``_LmRunner`` asks before any program exists:
    ``init_params``, and ``generate`` / ``quantize_params`` /
    ``serving_params``, each None in a family that has no contiguous serial
    path, no int8 weights or serves its weights as published."""

    # why a lane's cache cannot be rebuilt from its blocks ("" = it can):
    # the engine switches off what assumes it can, and a family that sets
    # this has no ``make_verify``
    recurrent = ""
    init_params = staticmethod(init_params)
    generate = staticmethod(generate)
    quantize_params = staticmethod(quantize_params)
    serving_params = staticmethod(serving_params)

    def __init__(self, cfg, block_size):
        self.cfg, self.block_size = cfg, block_size
        # donate the KV pool buffers (args 2/3 of the programs): the
        # functional .at[].set update would otherwise materialize a full
        # copy of every per-layer block pool on EACH dispatch — ~2x the
        # dominant HBM allocation and a whole-pool copy per token.  The
        # pool is reassigned from the outputs immediately, so the donated
        # inputs are never touched again.  CPU (the test platform) has no
        # donation support; jit would just warn
        self.donate = (2, 3) if jax.default_backend() != "cpu" else ()
        self.flops_per_token = lm_flops_per_token(cfg)
        self.window = None  # positions a window layer keeps, if any
        # what ``paged_layers`` will find of the pool's blocks on a tick
        self._in_place = reads_in_place(jax.ShapeDtypeStruct(
            (block_size, cfg.head_dim), cfg.jdtype))
        self.prefill_jit = self._jit(paged_prefill_chunk)

    def _jit(self, program, **static):
        """``program`` jitted as a partial: the benchmark's trace readers
        find the tick and the chunk under the name jit gives one, until
        they and the programs' names change together (ROADMAP S7)."""
        return jax.jit(
            functools.partial(
                program, cfg=self.cfg, block_size=self.block_size, **static),
            donate_argnums=self.donate,
        )

    def attended_positions(self, max_pos, table_width):
        """Positions a lane that ``paged_attention`` reads in a call whose
        largest query position is ``max_pos``: the program's own rule."""
        widths = attention_widths(table_width)
        index = attention_width_index(max_pos, table_width, self.block_size)
        return widths[min(index, len(widths) - 1)] * self.block_size

    def _tick_reads(self, lengths, table_width):
        """The cache positions of each lane that a decode tick's attention
        reads, for lanes at ``lengths`` (an array) before the tick's
        write: where the tick reads in place, each lane's own length, this
        tick's row with it, rounded up to the kernel's step (its trip
        count, ``paged_decode.steps_read``); None where it takes
        ``paged_attention``, whose read ``attended_positions`` gives."""
        if not self._in_place:
            return None
        return (steps_read(lengths + 1, self.block_size)
                * (STEP_BLOCKS * self.block_size)).tolist()

    def tick_fields(self, kind, lengths, **_):
        """For a decode tick's ``tick_trace()`` entry where the tick reads
        in place: ``kv_steps``, the steps the kernel took over the entry's
        lanes and every layer, and ``kv_steps_full``, those on its
        straight-line path (``paged_decode.tick_steps``)."""
        if kind != "decode" or not self._in_place:
            return {}
        return tick_steps(np.asarray(lengths) + 1, self.block_size,
                          self.cfg.n_layers)

    def prefill(self, params, kv, chunk, table, slot, start, prompt_len,
                fresh, key, temperature, top_k):
        tok, kv.pools["k"], kv.pools["v"], key = self.prefill_jit(
            params, chunk, kv.pools["k"], kv.pools["v"], table, start,
            prompt_len, key, temperature, top_k,
        )
        return tok, key

    def make_tick(self, n):
        return self._jit(paged_decode_tick, n=n)

    def tick(self, fn, params, kv, tokens, tables, lens, live, temps, topks,
             keys):
        tokens, kv.pools["k"], kv.pools["v"], keys = fn(
            params, tokens, kv.pools["k"], kv.pools["v"], tables, lens,
            live, temps, topks, keys,
        )
        return tokens, keys

    def make_verify(self, n, width):
        return self._jit(paged_verify_tick, n=n, width=width)

    def verify(self, fn, params, kv, tokens, tables, lens, temps, topks,
               keys, props, counts):
        out, tokens, kv.pools["k"], kv.pools["v"], keys = fn(
            params, tokens, kv.pools["k"], kv.pools["v"], tables, lens,
            temps, topks, keys, props, counts,
        )
        return out, tokens, keys
