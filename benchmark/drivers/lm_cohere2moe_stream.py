"""``lm_stream``'s closed-loop token streams through ``LmEngine``, for a
configuration of the ``cohere2_moe`` family as one chip of its deployment
runs it (``configs/command-a-plus-05-2026-ep8-d4.json``).  Clients, window,
sample and end-to-end metrics are ``lm_stream.Run``'s; the window's place
in the one order of sizes (``traffic.first_index``) and the sums of what a
window layer met are ``lm_sambay_stream.Run``'s, which reads the same
``sliding_window`` key.  What differs is the model that is built (told which
experts and which rows of the vocabulary it holds), the plain reference
that decides ``token_gap`` (``reference_cohere2moe``, given the same share),
and the counts, which also sum the routed pairs the program counted on the
device."""

import gc

import numpy as np

from benchmark import reference_cohere2moe, traffic, weights_cohere2moe
from benchmark.drivers import lm_sambay_stream

END_TO_END = lm_sambay_stream.END_TO_END


class Run(lm_sambay_stream.Run):

    def build_model(self):
        try:
            from client_tpu.serve.models import cohere2moe
        except ImportError as e:
            raise SystemExit(
                f"benchmark: {self.cell['name']} needs a program that serves "
                "the cohere2_moe family (client_tpu.serve.models.cohere2moe): "
                f"{e}")
        from client_tpu.serve.models.language import (
            _LmRunner, lm_streaming_batched_model)

        c = self.config
        share = c["deployment"]
        period = c["layer_types"].index("full_attention") + 1
        kinds = c["layer_types"][:c["num_hidden_layers"]]
        cfg = cohere2moe.Cohere2MoeConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], n_experts=share["router_experts"],
            top_k=c["num_experts_per_tok"],
            experts_held=tuple(share["experts_held"]),
            n_shared=c["num_shared_experts"], window=c["sliding_window"],
            full_every=period, rope_theta=float(c["rope_theta"]),
            norm_eps=c["layer_norm_eps"], logit_scale=float(c["logit_scale"]),
            max_seq=self.max_seq, dtype=c["torch_dtype"],
        )
        if len(cfg.experts_held) != c["num_experts"] or [
                "full_attention" if cfg.is_full(i) else "sliding_attention"
                for i in range(cfg.n_layers)] != kinds:
            raise ValueError("the program derives another share or other "
                             "layer kinds")
        runner = _LmRunner(cfg, params=weights_cohere2moe.cohere2moe_params(
            c, self.seed))
        args = dict(self.engine_args)
        args["lane_counts"] = tuple(args["lane_counts"])
        return lm_streaming_batched_model(name=self.model_name, runner=runner,
                                          **args)

    def close(self):
        """``lm_stream``'s close, and the served model's 12.7 GB gone from
        the device before the reference asks for its own: server, engine and
        runner hold one another in cycles, which only a collection frees (a
        run that happened to collect in time was correct; one that did not
        found 0.9 GB free and failed to load the reference's first layer)."""
        super().close()
        gc.collect()
        import jax

        used = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
        self.log(f"closed; device bytes in use {used}")

    def counts(self, records, t_a, t_b):
        """``lm_sambay_stream``'s counts (``lm_stream``'s, and beside each
        sum of context lengths the part inside a window layer's window),
        and the routed pairs that fell on held experts, as the program's
        ticks dispatched between the two instants counted them on the
        device."""
        out = super().counts(records, t_a, t_b)
        out["expert_rows"] = sum(
            t.get("expert_rows", 0) for t in self.engine.tick_trace()
            if t_a <= t["t0"] < t_b)
        return out

    def check(self, window, quant=None):
        """``token_gap_mean``: the mean gap, in logits, of the served tokens,
        where a token's gap is how far its reference logit lies below the
        reference's best at its position, over the sampled streams; with
        ``quant`` the tokens that the control puts first stand in the served
        ones' place.  Not the WIDEST gap of the other LM cells: here a top-8
        choice that flips on rounding swaps one expert for a near-equal one
        at one position in a hundred and moves that position's logits by 0.1
        to 1.3, in a sound bf16 run as in the fp8 control, so the widest gaps
        of the two overlap, and the gap that 99 of 100 tokens stay under
        stands at the edge of those positions (0.065 and 0.157 in two sound
        runs), while the control is wrong at five hundred positions of two
        thousand for the sound run's twenty (PERF.md section 6, PR 33).
        The reference is ``reference_cohere2moe``, a stream at a time."""
        def verdict(gap):
            return {"token_gap_mean": {
                "value": gap, "limit": self.cell["limits"]["token_gap_mean"]}}

        c = self.config
        sample = self.sample(window)
        if not sample:
            return verdict(float("inf"))
        # one shape to a cell, whatever the sample: the mix's longest stream
        width = -(-int(self.traffic["prompt_tokens"]["max"]
                       + self.traffic["output_tokens"]["max"]) // 256) * 256
        most = int(self.traffic["output_tokens"]["max"])
        rows = np.zeros((self.traffic["check_requests"], width), np.int32)
        at = np.zeros((len(rows), most), np.int32)
        served = np.zeros((len(rows), most), np.int32)
        for s, r in enumerate(sample):
            prompt = traffic.prompt_tokens(self.traffic, self.seed, r["index"],
                                           r["prompt_tokens"], c["vocab_size"])
            tokens = np.asarray(r["tokens"], np.int32)
            if ((tokens < 0) | (tokens >= c["vocab_size"])).any():
                return verdict(float("inf"))
            seq = np.concatenate([prompt, tokens])
            rows[s, :len(seq)] = seq   # the causal mask hides what follows
            # the positions that put out the stream's tokens: the prompt's
            # last and every served token but the last (padding repeats it)
            first = r["prompt_tokens"] - 1
            at[s] = np.minimum(first + np.arange(most),
                               first + len(tokens) - 1)
            served[s, :len(tokens)] = tokens
            served[s, len(tokens):] = tokens[-1]
        ends = weights_cohere2moe.cohere2moe_ends(c, self.seed)
        quants = (None,) if quant is None else (None, quant)
        hidden = reference_cohere2moe.hidden_states(
            c, rows, at, ends,
            lambda i: weights_cohere2moe.cohere2moe_layer(c, self.seed, i),
            quants)
        control = None if quant is None else (hidden[1], quant)
        gaps = np.asarray(reference_cohere2moe.token_gaps(
            c, hidden[0], served, ends, control))
        compared = np.concatenate([gaps[s, :len(r["tokens"])]
                                   for s, r in enumerate(sample)])
        # the widest gaps are the positions where a top-8 choice flipped:
        # the log shows how far they stand out
        widest = ", ".join(f"{g:.3f}" for g in np.sort(compared)[-5:][::-1])
        self.log(f"check: {len(compared)} tokens of {len(sample)} streams, "
                 f"padded to {width}; widest gaps {widest}; "
                 f"{int((compared > 0.1).sum())} over 0.1, 99 of 100 under "
                 f"{traffic.percentile(compared.tolist(), 99):.3f}, mean "
                 f"{compared.mean():.4f}")
        return verdict(float(compared.mean()))
