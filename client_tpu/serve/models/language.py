"""Language-model serving stack: tokenizer + streaming decoder LM.

The BASELINE.md config-5 shape ("Llama-3 ensemble: tokenizer → LLM streaming
infer"): a byte-level tokenizer model, a decoupled LM that streams one
response per generated token (the KServe decoupled/LLM pattern the reference
exercises via Triton's repeat/decoupled models), and an end-to-end text
ensemble that chains them server-side.

The LM is the flagship transformer (models/transformer.py) at a small
byte-vocab configuration so it runs hermetically; swap ``TransformerConfig``
for a full-size model on real deployments.  Token streaming maps one yielded
dict to one decoupled KServe response, which the gRPC frontend delivers over
ModelStreamInfer.
"""

import numpy as np

import jax

from client_tpu.serve.model_runtime import Model, TensorSpec
from client_tpu.serve.models import transformer as tfm
from client_tpu.utils import InferenceServerException

# byte-level vocab: 256 bytes + BOS + EOS
_BOS = 256
_EOS = 257
_VOCAB = 258

# The hermetic serving configuration (swap for a full-size model on real
# deployments).  Module-level so harnesses can compute
# tfm.lm_flops_per_token without instantiating a runner's params.
DEFAULT_LM_CONFIG = tfm.TransformerConfig(
    vocab_size=_VOCAB,
    d_model=256,
    n_layers=4,
    n_heads=8,
    n_kv_heads=4,
    d_ff=768,
    max_seq=512,
)


def encode_text(text):
    """Byte-level tokenize: BOS + utf-8 bytes."""
    if isinstance(text, str):
        text = text.encode("utf-8")
    return np.array([_BOS] + list(text), dtype=np.int32)


def decode_tokens(tokens):
    """Tokens -> utf-8 text (BOS/EOS stripped, lone surrogates replaced)."""
    return bytes(t for t in tokens if 0 <= t < 256).decode(
        "utf-8", errors="replace"
    )


def tokenizer_model(name="tokenizer"):
    """BYTES text -> INT32 token ids (ragged rows padded with EOS)."""

    def fn(inputs, params, ctx):
        texts = np.atleast_1d(inputs["TEXT"]).reshape(-1)
        rows = [encode_text(t) for t in texts]
        width = max(len(r) for r in rows)
        out = np.full((len(rows), width), _EOS, dtype=np.int32)
        for i, r in enumerate(rows):
            out[i, : len(r)] = r
        lengths = np.array([len(r) for r in rows], dtype=np.int32)
        return {"TOKENS": out, "LENGTHS": lengths}

    return Model(
        name,
        inputs=[TensorSpec("TEXT", "BYTES", [-1])],
        outputs=[
            TensorSpec("TOKENS", "INT32", [-1, -1]),
            TensorSpec("LENGTHS", "INT32", [-1]),
        ],
        fn=fn,
        platform="python",
    )


def detokenizer_model(name="detokenizer"):
    """INT32 token ids -> BYTES text."""

    def fn(inputs, params, ctx):
        tokens = np.atleast_2d(inputs["TOKENS"])
        texts = [decode_tokens(row).encode("utf-8") for row in tokens]
        return {"TEXT": np.array(texts, dtype=np.object_)}

    return Model(
        name,
        inputs=[TensorSpec("TOKENS", "INT32", [-1, -1])],
        outputs=[TensorSpec("TEXT", "BYTES", [-1])],
        fn=fn,
        platform="python",
    )


class _LmRunner:
    """Owns the params of any family + the serial path's jitted decode
    programs.  What a family has is asked of ``cfg.family`` (a
    ``TransformerConfig``'s or a ``sambay.SambaYConfig``'s): one without a
    contiguous-cache ``generate`` is served by the continuous-batching
    engine alone (``lm_streaming_batched_model(runner=...)``), one without
    ``quantize_params`` has no int8 weights."""

    def __init__(self, cfg=None, seed=0, quantize=False, params=None):
        self.cfg = cfg or DEFAULT_LM_CONFIG
        family = self.cfg.family
        if quantize and family.quantize_params is None:
            raise ValueError("int8 weights cover the decoder family only")
        if params is None:
            params = family.init_params(jax.random.PRNGKey(seed), self.cfg)
        self.params = params
        # id 257 ends a stream only under the byte-level tokenizer's
        # vocabulary: in any other it is a token like the rest, and a
        # stream ends at its budget
        self.eos_id = _EOS if self.cfg.vocab_size == _VOCAB else None
        if quantize:
            # int8 weight-only serving (client_tpu.ops.quant): ~2x weight
            # capacity per chip, same decode programs via the _mm dispatch
            self.params = family.quantize_params(self.params)
        if family.serving_params is not None:
            # once, here: the layout the family's compiled programs read
            self.params = family.serving_params(self.params)

    def check_prompt(self, n_prompt_tokens):
        """Reject prompts the KV cache cannot hold with a clear 400 instead
        of an opaque shape error out of the jitted prefill (r1 advisor)."""
        if n_prompt_tokens >= self.cfg.max_seq:
            raise InferenceServerException(
                f"prompt of {n_prompt_tokens} tokens exceeds the model's "
                f"maximum context of {self.cfg.max_seq} (need at least one "
                "free slot to generate)",
                status="400",
            )
        if n_prompt_tokens == 0:
            raise InferenceServerException("empty prompt", status="400")

    def stream(self, tokens, max_tokens, temperature=0.0, seed=0,
               top_k=0, tenant=""):
        self.check_prompt(int(np.asarray(tokens).reshape(-1).shape[0]))
        generate = self.cfg.family.generate
        if generate is None:
            raise InferenceServerException(
                "this model streams through the continuous-batching "
                "engine only (lm_streaming_batched_model)", status="400",
            )
        if top_k and int(top_k) > 0:
            raise InferenceServerException(
                "top_k sampling needs the continuous-batching engine "
                "(lm_streaming_batched); this model samples the full "
                "distribution", status="400",
            )
        key = jax.random.PRNGKey(seed) if temperature > 0 else None
        for tok in generate(
            self.params, self.cfg, tokens, max_tokens,
            temperature=temperature, key=key,
            stop_tokens=() if self.eos_id is None else (self.eos_id,),
        ):
            yield tok
            if tok == self.eos_id:
                return


def lm_streaming_model(name="lm_streaming", runner=None):
    """Decoupled LM: one KServe response per generated token.

    Inputs: TOKENS (prompt ids), MAX_TOKENS; optional request parameters
    ``temperature`` and ``seed``.  Each response carries the token id and its
    decoded text piece — the Triton LLM-streaming response shape.
    """
    runner = runner or _LmRunner()

    def fn(inputs, params, ctx):
        tokens = np.asarray(inputs["TOKENS"]).reshape(-1).astype(np.int32)
        max_tokens = int(np.asarray(inputs["MAX_TOKENS"]).flatten()[0])
        temperature = float(params.get("temperature", 0.0) or 0.0)
        seed = int(params.get("seed", 0) or 0)
        # top_k rides as a request parameter; __tenant__ is the RESERVED
        # caller identity the engine stamps from x-tenant-id (decoupled
        # models bypass the front door, so lane quotas are enforced at
        # decode-lane admission inside the LM engine instead)
        top_k = int(params.get("top_k", 0) or 0)
        tenant = str(params.get("__tenant__", "") or "")
        for tok in runner.stream(tokens, max_tokens, temperature, seed,
                                 top_k=top_k, tenant=tenant):
            piece = decode_tokens([tok]).encode("utf-8")
            yield {
                "TOKEN": np.array([tok], dtype=np.int32),
                "TEXT": np.array([piece], dtype=np.object_),
            }

    model = Model(
        name,
        inputs=[
            TensorSpec("TOKENS", "INT32", [-1]),
            TensorSpec("MAX_TOKENS", "INT32", [1]),
        ],
        outputs=[
            TensorSpec("TOKEN", "INT32", [1]),
            TensorSpec("TEXT", "BYTES", [1]),
        ],
        fn=fn,
        decoupled=True,
    )
    # which program this name resolved to (the serial _LmRunner or the
    # engine's BatchedLmRunner): lm_streaming_int8 differs by backend, and
    # chip_smoke.py prints what it exercised
    model.runner = runner
    return model


def lm_streaming_batched_model(name="lm_streaming_batched", runner=None,
                               max_slots=8, response_cache=None,
                               speculative=None, **engine_kwargs):
    """Decoupled LM with CONTINUOUS BATCHING: concurrent streams share one
    batched decode tick per token step (serve/lm: paged KV cache, bucketed
    + chunked prefill, KV prefix caching, lane autoscaling), so aggregate
    tokens/sec scales with active streams instead of serializing whole
    per-request decode programs.  Per-request ``temperature``/``top_k``/
    ``seed`` sample inside the jitted tick via per-lane RNG keys; same
    request/response surface as lm_streaming — the model IS
    lm_streaming_model with the batched runner behind it.

    ``response_cache`` is the per-model cache-hint config block; its
    ``prefix_cache`` sub-block carries the KV prefix-cache knobs this
    model's engine honors: ``{"prefix_cache": {"enable": bool,
    "min_prefix_blocks": int}}`` (the response-cache half is moot here —
    decoupled models never hit the unary response cache — but the block
    rides the model config so operators read one policy surface).

    ``speculative`` turns on speculative decoding for this model's
    engine (off by default): ``{"k": 4, "drafter": "ngram", ...}`` —
    see serve/lm/spec.py:SpecConfig for the full knob set.  Greedy
    streams keep byte-exact output; temperature streams stay
    distribution-exact via rejection sampling.

    What ends a stream early is the runner's ``eos_id``: the byte-level
    tokenizer's EOS where the vocabulary is that tokenizer's, else
    nothing but the budget."""
    from client_tpu.serve.lm import BatchedLmRunner

    prefix_knobs = dict((response_cache or {}).get("prefix_cache") or {})
    if "enable" in prefix_knobs:
        engine_kwargs.setdefault("prefix_cache",
                                 bool(prefix_knobs["enable"]))
    if "min_prefix_blocks" in prefix_knobs:
        engine_kwargs.setdefault("min_prefix_blocks",
                                 int(prefix_knobs["min_prefix_blocks"]))
    if speculative is not None:
        engine_kwargs.setdefault("speculative", speculative)
    base = runner or _LmRunner()
    batched = BatchedLmRunner(
        base.params, base.cfg, max_slots=max_slots, eos_id=base.eos_id,
        check_prompt=base.check_prompt, **engine_kwargs,
    )
    model = lm_streaming_model(name=name, runner=batched)
    model.response_cache = dict(response_cache or {}) or None
    # the scheduler's thread + paged KV pool release with the engine
    model.closer = batched.scheduler.close

    def bind(engine):
        """Late-bind the owning InferenceEngine's observability + QoS
        (add_model calls this): lane/KV/prefix gauges land in the
        server's /metrics registry, and tenant decode-lane quotas + preemption priority classes come
        from the front door's TenantQoS."""
        sched = batched.scheduler
        sched.set_registry(engine.metrics)
        sched.flight = sched.prof.flight = getattr(engine, "flight", None)
        if getattr(engine, "prof", None) is not None:
            # the scheduler's per-tick profiler joins the server's so
            # /v2/debug/prof and flight dumps cover the LM engine
            engine.prof.adopt(sched.prof)
        if engine.qos is not None:
            sched.tenant_lane_share = engine.qos.lane_share
            sched.tenant_priority = engine.qos.priority
        if getattr(engine, "fleet", None) is not None:
            # cross-replica prefix tier: submit-side peer lookups,
            # prefill-completion exports, parked-stream migration
            sched.set_fleet(engine.fleet)

    model.binder = bind
    return model


def text_ensemble_model(name="text_generator", runner=None):
    """End-to-end ensemble: BYTES prompt -> streamed BYTES pieces.

    Chains tokenizer -> LM server-side, the ensemble pattern of BASELINE
    config 5 (client sends text, receives a token stream)."""
    runner = runner or _LmRunner()

    def fn(inputs, params, ctx):
        text = np.asarray(inputs["PROMPT"]).reshape(-1)[0]
        max_tokens = int(np.asarray(inputs["MAX_TOKENS"]).flatten()[0])
        temperature = float(params.get("temperature", 0.0) or 0.0)
        seed = int(params.get("seed", 0) or 0)
        tokens = encode_text(text)
        for tok in runner.stream(tokens, max_tokens, temperature, seed):
            piece = decode_tokens([tok]).encode("utf-8")
            yield {"TEXT": np.array([piece], dtype=np.object_)}

    return Model(
        name,
        inputs=[
            TensorSpec("PROMPT", "BYTES", [1]),
            TensorSpec("MAX_TOKENS", "INT32", [1]),
        ],
        outputs=[TensorSpec("TEXT", "BYTES", [1])],
        fn=fn,
        platform="ensemble",
        decoupled=True,
    )


def language_models(shared_runner=True, speculative=None,
                    int8_batched=None):
    """The full language set; one shared LM runner keeps params/compile warm.

    ``lm_streaming_int8`` serves the same architecture from int8-quantized
    weights (weight-only; client_tpu.ops.quant).  On TPU it serves through
    the continuous-batching engine exactly like the float model (the int8
    dequant-matmul is the same ``_mm`` dispatch the engine's jitted
    tick/prefill/verify programs already route through); off-TPU the
    Pallas kernel only runs in interpret mode — hundreds of ms per
    dispatch, which would bury the engine's scheduling wins — so the
    serial path stays the default there.  ``int8_batched`` overrides the
    auto-detection either way.

    ``speculative`` enables speculative decoding on the batched engines
    (see :func:`lm_streaming_batched_model`); the perf CLI's
    ``--speculative K --drafter ngram`` lands here.
    """
    runner = _LmRunner() if shared_runner else None
    # the int8 runner quantizes the SHARED weights (no second param init)
    int8_runner = _LmRunner(
        cfg=runner.cfg if runner else None,
        params=runner.params if runner else None,
        quantize=True,
    )
    if int8_batched is None:
        int8_batched = jax.default_backend() == "tpu"
    int8_model = (
        lm_streaming_batched_model(
            name="lm_streaming_int8", runner=int8_runner,
            speculative=speculative,
        )
        if int8_batched else
        lm_streaming_model(name="lm_streaming_int8", runner=int8_runner)
    )
    return [
        tokenizer_model(),
        detokenizer_model(),
        lm_streaming_model(runner=runner),
        int8_model,
        lm_streaming_batched_model(runner=runner,
                                   speculative=speculative),
        text_ensemble_model(runner=runner),
    ]
