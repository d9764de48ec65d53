"""The ``longcat_flash`` decoder (the language model of LongCat-Flash-Omni,
``LongcatFlashForCausalLM``) as a sixth family that ``serve/lm.LmEngine``
serves: one chip's share of an expert-parallel deployment.

A layer is a DOUBLE layer with a shortcut-connected mixture of experts.
``x`` is the float32 residual stream, every norm an RMSNorm (float32
statistics, a scale, no bias):

    h1 = RMSNorm(x);  x = x + Mla_0(h1)
    h2 = RMSNorm(x);  m = Moe(h2);  x = x + Ffn_0(h2)       (m waits)
    h3 = RMSNorm(x);  x = x + Mla_1(h3)
    h4 = RMSNorm(x);  x = x + Ffn_1(h4) + m

- ``Mla_j``: ``axk1.mla``, the multi-head latent attention of the ``axk1``
  family over its own paged layer of latent rows, with the LoRA scales
  ``mla_scale_q_lora`` (the query times ``(d_model / q_lora_rank)^0.5``, 2
  at the published widths) and ``mla_scale_kv_lora`` (the normed latent
  times ``(d_model / kv_lora_rank)^0.5``, sqrt(12); not the rotary key),
  plain rotary at ``rope_theta`` (no YaRN: a factor of 1, a softmax scale of
  ``(nope + rope)^-0.5``).  The program folds both scales into the float32
  stage of their norms, before the cast: the stored row is the scaled
  latent, which the chunk's expanded form and the tick's absorbed form read
  alike.  A double layer's two rows of a position go to pool layers ``2l``
  and ``2l + 1``.
- ``Ffn_i``: ``W_d(silu(W_g h) * W_u h)`` at ``d_dense``.
- ``Moe``: ``experts.routed`` with a softmax over ``n_experts`` routed
  experts and ``n_zero`` zero-compute slots after them; the ``top_k``
  largest of ``p + bias`` (``e_score_correction_bias``) are the picks and
  ``routed_scale * p`` their weights, not renormalised; a pick of a held
  expert adds ``w E(h2)``, a pick of a zero slot ``w h2``; no shared expert.
- Head: ``RMSNorm(x) W_head`` over the held rows; embedding and head are
  separate matrices.

The embedding, the head, the cache views and the steps around ``_layers``
are ``axk1``'s.  The programs ``longcat_decode_tick`` and
``longcat_prefill_chunk`` are jitted under those names so that a device
trace tells them apart, and return the counts of ``COUNTERS``.
"""

import dataclasses

import jax
import jax.numpy as jnp

from client_tpu.ops.sampling import select_token
from client_tpu.serve.models import axk1, experts
from client_tpu.serve.models.axk1 import MlaShape, _dense_ffn, _rms_norm, mla
from client_tpu.serve.models.cohere2moe import COUNTERS as EXPERT_COUNTERS

# the expert families' counts, then the zero slots': (tick_trace() field,
# Prometheus series or None, "counter" | "gauge", help), summed over layers
COUNTERS = EXPERT_COUNTERS + (
    ("zero_pairs", "ctpu_lm_zero_pairs_total", "counter",
     "(token, pick) pairs of real rows that fell on zero-compute slots, "
     "summed over expert layers and dispatches"),
    ("pairs", None, None, None),     # (token, pick) pairs of real rows
)


@dataclasses.dataclass(frozen=True)
class LongcatConfig(MlaShape):
    vocab_size: int = 16384          # the rows of embedding and head held
    d_model: int = 6144
    n_layers: int = 4                # double layers
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    nope_dim: int = 128              # qk_nope_head_dim
    rope_dim: int = 64               # qk_rope_head_dim
    v_dim: int = 128                 # v_head_dim
    d_dense: int = 12288             # ffn_hidden_size
    d_ff: int = 2048                 # expert_ffn_hidden_size
    n_experts: int = 512             # routed experts, all of the deployment
    n_zero: int = 256                # zero_expert_num: identity slots
    top_k: int = 12                  # moe_topk
    experts_held: tuple = tuple(range(16))  # which routed experts live here
    routed_scale: float = 6.0
    rope_theta: float = 10000000.0
    norm_eps: float = 1e-5
    max_seq: int = 8704
    dtype: str = "bfloat16"

    rope_factor = 1.0   # no rope_scaling: plain rotary, softmax gain 1

    def __post_init__(self):
        if self.rope_dim % 2 or self.v_dim > self.kv_lora_rank + self.rope_dim:
            raise ValueError("rope_dim even, a value a slice of the cache row")
        held = tuple(int(e) for e in self.experts_held)
        if not held or len(set(held)) != len(held) or not all(
                0 <= e < self.n_experts for e in held):
            raise ValueError("experts_held: distinct ids under n_experts")
        object.__setattr__(self, "experts_held", held)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def q_gain(self):
        """``mla_scale_q_lora``: the query's LoRA scale."""
        return (self.d_model / self.q_lora_rank) ** 0.5

    @property
    def kv_gain(self):
        """``mla_scale_kv_lora``: the latent's LoRA scale."""
        return (self.d_model / self.kv_lora_rank) ** 0.5

    @property
    def slots(self):
        """The router's width: the routed experts, then the zero slots."""
        return self.n_experts + self.n_zero

    @property
    def state_spec(self):
        """(paged layers, {pool: a block's shape}, no per-lane state): TWO
        latent layers a double layer, one for each attention sublayer, in
        ``axk1``'s block shape."""
        return 2 * self.n_layers, {"latent": (1, None, self.row_width)}, {}

    @property
    def family(self):
        return LongcatPrograms


# -- parameters -----------------------------------------------------------------

def init_params(key, cfg):
    """[in, out] matrices (``x @ w``).  A double layer holds ``attn``, its
    two attention sublayers in ``axk1.init_params``' layout with their input
    norm ``ln``; ``mlp``, its two dense feed-forwards (``ln``, the norm
    before each, ``w_gate_up`` [D, 2 d_dense] gate columns then up,
    ``w_down``); ``moe``: ``router`` [D, slots], ``bias`` [slots] float32,
    and the held experts' ``w_gate_up`` [held, D, 2F] and ``w_down`` [held,
    F, D].  ``benchmark/weights_longcat.py`` makes the same tree from a
    seed, a layer a call."""
    dt = cfg.jdtype
    d, h, c = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    keys = iter(jax.random.split(key, 24 * cfg.n_layers + 2))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, dt) * float(fan_in ** -0.5)

    def attn():
        return {
            "ln": jnp.ones((d,), dt),
            "w_qa": dense((d, cfg.q_lora_rank), d),
            "ln_q": jnp.ones((cfg.q_lora_rank,), dt),
            "w_qb": dense((cfg.q_lora_rank,
                           h * (cfg.nope_dim + cfg.rope_dim)), cfg.q_lora_rank),
            "w_kva": dense((d, c + cfg.rope_dim), d),
            "ln_kv": jnp.ones((c,), dt),
            "w_uk": dense((h, cfg.nope_dim, c), c),
            "w_uv": dense((h, c, cfg.v_dim), c),
            "w_o": dense((h * cfg.v_dim, d), h * cfg.v_dim),
        }

    def mlp():
        return {"ln": jnp.ones((d,), dt),
                "w_gate_up": dense((d, 2 * cfg.d_dense), d),
                "w_down": dense((cfg.d_dense, d), cfg.d_dense)}

    def moe():
        n = len(cfg.experts_held)
        return {"router": dense((d, cfg.slots), d),
                "bias": jnp.zeros((cfg.slots,), jnp.float32),
                "w_gate_up": dense((n, d, 2 * cfg.d_ff), d),
                "w_down": dense((n, cfg.d_ff, d), cfg.d_ff)}

    layers = [{"attn": [attn(), attn()], "mlp": [mlp(), mlp()], "moe": moe()}
              for _ in range(cfg.n_layers)]
    return {"embed": dense((cfg.vocab_size, d), d), "layers": layers,
            "ln_f": jnp.ones((d,), dt),
            "head": dense((cfg.vocab_size, d), d)}


def lm_flops_per_token(cfg, context=0):
    """Model FLOPs a generated token costs HERE, 2 a weight element it
    meets: two attention sublayers, two dense feed-forwards, the router and
    the share of its ``top_k`` picks that the held experts get under even
    routing over all the slots (a zero pick costs nothing), the sliced
    head; ``context`` adds the absorbed form's attention over that many
    cache rows a sublayer."""
    d, h, c = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    attn = (d * cfg.q_lora_rank
            + cfg.q_lora_rank * h * (cfg.nope_dim + cfg.rope_dim)
            + d * (c + cfg.rope_dim) + h * c * (cfg.nope_dim + cfg.v_dim)
            + h * cfg.v_dim * d)
    picks_here = cfg.top_k * len(cfg.experts_held) / cfg.slots
    layer = (2 * attn + 2 * 3 * d * cfg.d_dense + d * cfg.slots
             + 3 * d * cfg.d_ff * picks_here)
    per_key = 2 * h * (2 * c + cfg.rope_dim)
    return int(2 * (cfg.n_layers * layer + d * cfg.vocab_size)
               + per_key * 2 * cfg.n_layers * int(context))


# -- the step, over a cache view -------------------------------------------------

def _layers(params, x, pool, cfg, view):
    """Every double layer over the embedded ``x`` [B,T,D]: the final norm's
    output, the pool after, and the counts summed over the layers (experts
    hit, rows, busiest expert's rows, zero pairs, all pairs of real rows).
    The residual stream is float32; the matrix products read and write the
    activations' type."""
    pool = list(pool)
    b, t = x.shape[:2]
    real = view.real.reshape(-1)
    x = x.astype(jnp.float32)
    counts = jnp.zeros((4,), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        (a0, a1), (f0, f1), moe = layer["attn"], layer["mlp"], layer["moe"]
        out, pool[2 * i] = mla(_rms_norm(x, a0["ln"], cfg), a0, pool[2 * i],
                               cfg, view)
        x = x + out
        h2 = _rms_norm(x, f0["ln"], cfg)
        shortcut, hit = experts.routed(
            h2.reshape(b * t, -1), moe, cfg.experts_held, cfg.top_k, real,
            scale=cfg.routed_scale, score="softmax", bias=moe["bias"],
            normalize=False, n_zero=cfg.n_zero)
        x = x + _dense_ffn(h2, f0)
        out, pool[2 * i + 1] = mla(_rms_norm(x, a1["ln"], cfg), a1,
                                   pool[2 * i + 1], cfg, view)
        x = x + out
        x = x + _dense_ffn(_rms_norm(x, f1["ln"], cfg), f1)
        x = x + shortcut.reshape(b, t, -1)
        counts = counts + hit
    pairs = cfg.n_layers * cfg.top_k * jnp.sum(real, dtype=jnp.int32)
    return (_rms_norm(x, params["ln_f"], cfg), pool,
            jnp.concatenate([counts, pairs[None]]))


def _counters(cfg, counts):
    held = jnp.int32(cfg.n_layers * len(cfg.experts_held))
    return jnp.concatenate([held[None], counts])


# -- the two programs, and the family as the engine asks for it ----------------

def longcat_decode_tick(params, tokens_full, pool, tables, lens, live, temps,
                        topks, keys_full, *, cfg, n, block_size):
    """One batched decode step over the first ``n`` lanes, with the token
    choice on the device as ``transformer.paged_decode_tick`` makes it."""
    logits, pool, counts = axk1.decode_step(
        params, tokens_full[:n], pool, tables, lens, live, cfg, block_size,
        layers=_layers)
    pairs = jax.vmap(lambda key: jax.random.split(key, 2))(keys_full[:n])
    nxt = jax.vmap(select_token)(logits, pairs[:, 0], temps, topks)
    return (tokens_full.at[:n].set(nxt), pool,
            keys_full.at[:n].set(pairs[:, 1]), _counters(cfg, counts))


def longcat_prefill_chunk(params, chunk, pool, table, start, prompt_len, key,
                          temperature, top_k, *, cfg, block_size):
    """One prefill chunk of a lane; the returned token is the first
    generated one where the chunk holds the prompt's last position."""
    logits, pool, counts = axk1.prefill_step(
        params, chunk, pool, table, start, prompt_len, cfg, block_size,
        layers=_layers)
    k_sample, k_carry = jax.random.split(key)
    tok = select_token(logits, k_sample, temperature, top_k)
    return tok, pool, k_carry, _counters(cfg, counts)


class LongcatPrograms(axk1.AxK1Programs):
    """This family behind the interface of ``transformer.DecoderPrograms``,
    handed out as ``cfg.family``: ``axk1.AxK1Programs`` over its own
    programs, annotations, counters and parameters, with its reason for
    having no verify program.  Its ``tick_fields`` count over all ``2 *
    n_layers`` paged layers."""

    counters = COUNTERS
    init_params = staticmethod(init_params)
    _programs = (longcat_prefill_chunk, longcat_decode_tick)
    _annotations = ("lm.longcat_prefill_chunk", "lm.longcat_decode_tick")
    _token_flops = staticmethod(lm_flops_per_token)
