"""The ``stream()`` provider that puts :class:`LmEngine` behind a served
model (``models/language.lm_streaming_batched_model``)."""

import numpy as np

from client_tpu.ops.sampling import TOPK_CAP
from client_tpu.serve.lm.engine import LmEngine
from client_tpu.utils import InferenceServerException


class BatchedLmRunner:
    """Drop-in ``stream()`` provider backed by the continuous-batching
    engine — signature-compatible with language._LmRunner.stream so the
    batched model reuses lm_streaming_model verbatim.  Per-request
    sampling (temperature / top_k / seed) runs inside the jitted tick
    with per-lane RNG keys; temperature 0 lanes take the on-device
    argmax, so mixed greedy/sampled batches share one executable.
    ``tenant`` feeds the engine's per-tenant decode-lane quotas."""

    def __init__(self, params, cfg, max_slots=4, eos_id=None,
                 check_prompt=None, **engine_kwargs):
        self.cfg = cfg
        self.scheduler = LmEngine(
            params, cfg, max_slots=max_slots, eos_id=eos_id,
            check_prompt=check_prompt, **engine_kwargs,
        )

    def stream(self, tokens, max_tokens, temperature=0.0, seed=0,
               top_k=0, tenant=""):
        if int(top_k) > TOPK_CAP:
            # the jitted tick's per-lane filter has a static width: a
            # silently-truncated k would sample a different distribution
            # than the client asked for
            raise InferenceServerException(
                f"top_k {int(top_k)} exceeds the engine's static cap of "
                f"{TOPK_CAP}; use top_k <= {TOPK_CAP} or 0 (unfiltered)",
                status="400",
            )
        if self.scheduler.check_prompt is not None:
            self.scheduler.check_prompt(
                int(np.asarray(tokens).reshape(-1).shape[0])
            )
        q, handle = self.scheduler.submit(
            tokens, max_tokens, temperature=temperature, top_k=top_k,
            seed=seed, tenant=tenant,
        )
        try:
            while True:
                tok = q.get()
                if tok is LmEngine.CLOSE:
                    return
                yield tok
        finally:
            self.scheduler.cancel(handle)
