"""``lm_stream``'s closed-loop token streams through ``LmEngine``, for a
configuration of the ``longcat_flash`` family as one chip of its deployment
runs it (``configs/longcat-flash-omni-ep32-d4.json``).  Clients, window,
sample and end-to-end metrics are ``lm_stream.Run``'s; the window's place
in the one order of sizes (``traffic.first_index``) is
``lm_sambay_stream.Run``'s and the collection after the close
``lm_cohere2moe_stream.Run``'s, as for ``lm_axk1_stream``.  What differs is
the model that is built (double layers of latent attention, dense
feed-forwards and a shortcut mixture of experts with zero-compute slots,
told which experts and which rows of the vocabulary it holds), the plain
reference that decides ``token_gap_mean`` (``reference_longcat``, given the
same share), and the counts: ``lm_stream``'s (every sublayer attends the
whole context: no window's sums) and the routed pairs the program counted
on the device."""

import numpy as np

from benchmark import reference_longcat, traffic, weights_longcat
from benchmark.drivers import lm_cohere2moe_stream, lm_stream

END_TO_END = lm_stream.END_TO_END


class Run(lm_cohere2moe_stream.Run):

    def build_model(self):
        try:
            from client_tpu.serve.models import longcat
        except ImportError as e:
            raise SystemExit(
                f"benchmark: {self.cell['name']} needs a program that serves "
                "the longcat_flash family (client_tpu.serve.models.longcat): "
                f"{e}")
        from client_tpu.serve.models.language import (
            _LmRunner, lm_streaming_batched_model)

        c = self.config
        share = c["deployment"]
        cfg = longcat.LongcatConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_layers"], n_heads=c["num_attention_heads"],
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            nope_dim=c["qk_nope_head_dim"], rope_dim=c["qk_rope_head_dim"],
            v_dim=c["v_head_dim"], d_dense=c["ffn_hidden_size"],
            d_ff=c["expert_ffn_hidden_size"],
            n_experts=share["router_experts"], n_zero=c["zero_expert_num"],
            top_k=c["moe_topk"], experts_held=tuple(share["experts_held"]),
            routed_scale=float(c["routed_scaling_factor"]),
            rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
            max_seq=self.max_seq, dtype=c["torch_dtype"],
        )
        if len(cfg.experts_held) != c["n_routed_experts"] \
                or c["zero_expert_type"] != "identity" \
                or not (c["mla_scale_q_lora"] and c["mla_scale_kv_lora"]):
            raise ValueError("the program derives another share, another "
                             "kind of zero expert or LoRA scales")
        runner = _LmRunner(cfg, params=weights_longcat.longcat_params(
            c, self.seed))
        args = dict(self.engine_args)
        args["lane_counts"] = tuple(args["lane_counts"])
        return lm_streaming_batched_model(name=self.model_name, runner=runner,
                                          **args)

    def counts(self, records, t_a, t_b):
        """``lm_stream``'s counts, and the routed pairs that fell on held
        experts, as the program's ticks dispatched between the two instants
        counted them on the device."""
        out = lm_stream.Run.counts(self, records, t_a, t_b)
        out["expert_rows"] = sum(
            t.get("expert_rows", 0) for t in self.engine.tick_trace()
            if t_a <= t["t0"] < t_b)
        return out

    def check(self, window, quant=None):
        """``token_gap_mean``, as ``lm_axk1_stream`` decides it and for its
        reason (a top-k choice that flips on rounding moves single
        positions' logits in sound runs and control alike, so the widest
        gaps overlap and the mean separates): the mean gap, in logits, of
        the served tokens under the reference's best at their positions,
        over the sampled streams; with ``quant`` the tokens that the control
        puts first stand in the served ones' place.  The reference is
        ``reference_longcat``, a stream at a time, at the widths and the
        share the timed path ran."""
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
        ends = weights_longcat.longcat_ends(c, self.seed)
        quants = (None,) if quant is None else (None, quant)
        hidden = reference_longcat.hidden_states(
            c, rows, at, ends,
            lambda i: weights_longcat.longcat_layer(c, self.seed, i), quants)
        control = None if quant is None else (hidden[1], quant)
        gaps = np.asarray(reference_longcat.token_gaps(
            c, hidden[0], served, ends, control))
        compared = np.concatenate([gaps[s, :len(r["tokens"])]
                                   for s, r in enumerate(sample)])
        # how far the flipped picks stand out, and whether the streams vary
        widest = ", ".join(f"{g:.3f}" for g in np.sort(compared)[-5:][::-1])
        distinct = min(len(set(r["tokens"])) / len(r["tokens"])
                       for r in sample)
        self.log(f"check: {len(compared)} tokens of {len(sample)} streams, "
                 f"padded to {width}; widest gaps {widest}; "
                 f"{int((compared > 0.1).sum())} over 0.1, 99 of 100 under "
                 f"{traffic.percentile(compared.tolist(), 99):.3f}, mean "
                 f"{compared.mean():.4f}; least distinct share of a "
                 f"stream's tokens {distinct:.2f}")
        return verdict(float(compared.mean()))
