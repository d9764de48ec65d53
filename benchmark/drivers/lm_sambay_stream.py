"""``lm_stream``'s closed-loop token streams through ``LmEngine``, for a
configuration of the SambaY family (``configs/phi-4-mini-flash-reasoning
.json``).  Clients, window, sample and end-to-end metrics are
``lm_stream.Run``'s; what differs is the model that is built, the plain
reference that decides ``token_gap``, the counts, which also sum what the
window layers met, and where in the one order of sizes the window's ramp
starts, which the cell's file may name."""

import numpy as np

from benchmark import reference_sambay, traffic, weights_sambay
from benchmark.drivers import lm_stream

END_TO_END = lm_stream.END_TO_END


class Run(lm_stream.Run):

    def build_model(self):
        try:
            from client_tpu.serve.models import sambay
        except ImportError as e:
            raise SystemExit(
                f"benchmark: {self.cell['name']} needs a program that serves "
                f"the SambaY family (client_tpu.serve.models.sambay): {e}")
        from client_tpu.serve.models.language import (
            _LmRunner, lm_streaming_batched_model)

        c = self.config
        mamba = c["assumed"]["mamba"]
        cfg = sambay.SambaYConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], max_seq=self.max_seq,
            window=c["sliding_window"], mb_per_layer=c["mb_per_layer"],
            d_inner=mamba["expand"] * c["hidden_size"],
            d_state=mamba["d_state"], d_conv=mamba["d_conv"],
            dt_rank=mamba["dt_rank"], norm_eps=c["layer_norm_eps"],
            dtype=c["torch_dtype"],
        )
        kinds = [reference_sambay.layer_kind(c, i)
                 for i in range(c["num_hidden_layers"])]
        if list(cfg.kinds) != kinds:
            raise ValueError("the program derives other layer kinds")
        runner = _LmRunner(cfg, params=weights_sambay.sambay_params(
            c, self.seed))
        args = dict(self.engine_args)
        args["lane_counts"] = tuple(args["lane_counts"])
        return lm_streaming_batched_model(name=self.model_name, runner=runner,
                                          **args)

    def measure(self, seconds, tracer):
        """``lm_stream``'s window, opened at the cell's place in the one
        order of sizes (``traffic.first_index``: the index of the ramp's
        first request; ``traffic.request_sizes`` gives every index its
        sizes, the same for every seed).  Where the cell names none, the
        window follows the warm-up's requests as in ``lm_stream``."""
        self.next_index = self.traffic.get("first_index", self.next_index)
        return super().measure(seconds, tracer)

    def counts(self, records, t_a, t_b):
        """``lm_stream``'s counts, and beside each sum of context lengths
        the part of it that lies inside a window layer's window."""
        out = super().counts(records, t_a, t_b)
        w = self.config["sliding_window"]
        out["window_context_sum"] = out["decode_window_sum"] = 0
        for r in records:
            p = r["prompt_tokens"]
            for i, t in enumerate(r["times"]):
                if not t_a <= t < t_b:
                    continue
                if i == 0:  # the prompt's positions: min(1, w) .. min(p, w)
                    full = min(p, w)
                    out["window_context_sum"] += (full * (full + 1) // 2
                                                  + (p - full) * w)
                else:
                    out["window_context_sum"] += min(p + i, w)
                    out["decode_window_sum"] += min(p + i, w)
        return out

    def check(self, window, quant=None):
        """As ``lm_stream.Run.check``: the widest gap by which a served
        token's reference logit lies below the reference's best, over the
        sampled streams; with ``quant`` the tokens that the control puts
        first stand in the served ones' place.  The reference is
        ``reference_sambay``, which takes the gap a block of positions at
        a time: the logits of all positions would be 3.3 GB."""
        def verdict(gap):
            return {"token_gap": {
                "value": gap, "limit": self.cell["limits"]["token_gap"]}}

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
        ends = weights_sambay.sambay_ends(c, self.seed)
        quants = (None,) if quant is None else (None, quant)
        hidden = reference_sambay.hidden_states(
            c, rows, at, ends,
            lambda i: weights_sambay.sambay_layer(c, self.seed, i), quants)
        control = None if quant is None else (hidden[1], quant)
        gaps = np.asarray(reference_sambay.token_gaps(
            c, hidden[0], served, ends, control))
        worst, compared = 0.0, 0
        for s, r in enumerate(sample):
            n = len(r["tokens"])
            worst, compared = max(worst, float(gaps[s, :n].max())), compared + n
        self.log(f"check: {compared} tokens of {len(sample)} streams, "
                 f"padded to {width}")
        return verdict(worst)
