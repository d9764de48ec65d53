"""``lm_stream``'s closed-loop token streams through ``LmEngine``, for a
configuration of the ``sdar_moe`` family as the first stage of its pipeline
runs it (``configs/sdar-30b-a3b-chat-d7.json``): generation by diffusion
over blocks.  Clients, window, sample and end-to-end metrics are
``lm_stream.Run``'s, the window's place in the one order of sizes
(``traffic.first_index``) ``lm_sambay_stream.Run``'s and the collection
after the close ``lm_cohere2moe_stream.Run``'s.  The client
(``lm_client.py``) takes one ``TOKEN`` a response, so a block goes out as so
many responses in a row, and its count and times stay right: ``tokens_per_s``
is tokens received a second, ``ttft_p95_ms`` the send to the first token of
the first delivered block (prefill and that block's denoising passes).

What differs: the model that is built; that the traffic draws its ids under
the mask id; the ``block_gap_ms`` series (the gap between the first tokens
of a stream's successive blocks, which stands where ``token_gap_ms`` does
for a token a tick); the counts, which follow the static schedule of passes
(``work_sdar.stream_blocks``); and the comparison.  The ORDER in which a
block's positions were fixed reaches the check from the engine's bounded
record of finished streams (``LmEngine.pass_trace()``, read before the
close), matched to the client's record by its prompt and its tokens.

``correct`` holds two numbers to the cell's limits, over the sampled
streams, the reference (``reference_sdar``) run once a stream over prompt
and served tokens with every block's states rebuilt from the recorded
passes: ``token_gap_mean``, the mean over served tokens of how far the
reference's logit of the served token lies under the reference's best AT
THE PASS that fixed it; ``place_gap_mean``, the mean over passes of how far
the reference's confidence at the position the program fixed lies under the
reference's best over the positions still masked.  A stream's last block
counts only where it was delivered whole (what it computed past the budget
was dropped, and its states cannot be rebuilt)."""

import numpy as np

from benchmark import reference_sdar, traffic, weights_sdar, work_sdar
from benchmark.drivers import lm_cohere2moe_stream, lm_stream

END_TO_END = lm_stream.END_TO_END


class Run(lm_cohere2moe_stream.Run):

    def build_model(self):
        try:
            from client_tpu.serve.models import sdar
        except ImportError as e:
            raise SystemExit(
                f"benchmark: {self.cell['name']} needs a program that serves "
                f"the sdar family (client_tpu.serve.models.sdar): {e}")
        from client_tpu.serve.models.language import (
            _LmRunner, lm_streaming_batched_model)

        c = self.config
        share, rule = c["deployment"], c["assumed"]
        for key in ("block_length", "denoising_steps", "remasking_strategy"):
            if self.traffic[key] != rule[key]:
                raise ValueError(
                    f"the cell's {key} is not the configuration's")
        cfg = sdar.SdarConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["moe_intermediate_size"], n_experts=share["router_experts"],
            top_k=c["num_experts_per_tok"],
            experts_held=tuple(share["experts_held"]),
            block_length=rule["block_length"],
            denoising_steps=rule["denoising_steps"],
            mask_id=rule["mask_token_id"], rope_theta=float(c["rope_theta"]),
            norm_eps=c["rms_norm_eps"], max_seq=self.max_seq,
            dtype=c["torch_dtype"],
        )
        if len(cfg.experts_held) != c["num_experts"]:
            raise ValueError("the program derives another share")
        runner = _LmRunner(cfg, params=weights_sdar.sdar_params(c, self.seed))
        args = dict(self.engine_args)
        args["lane_counts"] = tuple(args["lane_counts"])
        return lm_streaming_batched_model(name=self.model_name, runner=runner,
                                          **args)

    @property
    def ids_under(self):
        """The traffic's ids are drawn under the mask id."""
        return self.config["assumed"]["mask_token_id"]

    def clients(self, plan):
        """``lm_stream``'s clients, told the mask id as their vocabulary's
        size, and their records kept for ``measure``."""
        config = self.config
        self.config = dict(config, vocab_size=self.ids_under)
        try:
            self.records = super().clients(plan)
        finally:
            self.config = config
        return self.records

    def block_starts(self, record):
        """Indices into a record's tokens of each block's first."""
        b = work_sdar.block_length(self.config)
        head = b - record["prompt_tokens"] % b
        return [0] + list(range(head, len(record["tokens"]), b))

    def measure(self, seconds, tracer):
        window = super().measure(seconds, tracer)
        t_start, t_end = window["t_start"], window["t_start"] + seconds
        gaps = []
        for r in self.records:
            if "error" in r or not r["tokens"]:
                continue
            firsts = [r["times"][i] for i in self.block_starts(r)]
            gaps += [1e3 * (b - a) for a, b in zip(firsts, firsts[1:])
                     if t_start <= b < t_end]
        window["series"]["block_gap_ms"] = gaps
        # the engine's record of the passes, before the close drops it
        self.passes = {
            (p["prompt"].tobytes(), tuple(p["tokens"])): p["fixed_at"]
            for p in self.engine.pass_trace()}
        return window

    def counts(self, records, t_a, t_b):
        """``lm_stream``'s counts (``output_tokens`` received between the two
        instants is what ``tokens_per_s`` divides), and what the blocks
        whose first token arrived there cost by the static schedule
        (``work_sdar.stream_blocks``): rows through the layers and through
        the head, keys met; the prompts whose first block arrived there as
        the positions their prefill stored; and the routed pairs the
        program's ticks dispatched there counted on the device."""
        out = lm_stream.Run.counts(self, records, t_a, t_b)
        c = self.config
        b = work_sdar.block_length(c)
        out.update(stored_tokens=0, prefill_keys=0, pass_rows=0, head_rows=0,
                   pass_keys=0, blocks=0)
        for r in records:
            blocks = work_sdar.stream_blocks(c, r["prompt_tokens"],
                                             r["max_tokens"])
            for at, block in zip(self.block_starts(r), blocks):
                if not t_a <= r["times"][at] < t_b:
                    continue
                _, _, rows, head_rows, keys = block
                out["blocks"] += 1
                out["pass_rows"] += rows
                out["head_rows"] += head_rows
                out["pass_keys"] += keys
                if at == 0:
                    stored = r["prompt_tokens"] // b * b
                    out["stored_tokens"] += stored
                    out["prefill_keys"] += work_sdar.keys_met(c, 0, stored)
        out["expert_rows"] = sum(
            t.get("expert_rows", 0) for t in self.engine.tick_trace()
            if t_a <= t["t0"] < t_b)
        return out

    def check(self, window, quant=None):
        """The module's top; with ``quant`` the control stands in the
        program's place on the same states: the tokens it puts first, and
        the positions its own confidences would fix."""
        limits = self.cell["limits"]

        def verdict(token, place):
            return {"token_gap_mean": {"value": token,
                                       "limit": limits["token_gap_mean"]},
                    "place_gap_mean": {"value": place,
                                       "limit": limits["place_gap_mean"]}}

        c = self.config
        b = work_sdar.block_length(c)
        sample = self.sample(window)
        if not sample:
            return verdict(float("inf"), float("inf"))
        # one shape to a cell, whatever the sample: the mix's longest stream,
        # and the longest generated region (a known head and the outputs)
        most = int(self.traffic["output_tokens"]["max"])
        width = -(-int(self.traffic["prompt_tokens"]["max"] + most)
                  // 256) * 256
        region = -(-(most + b - 1) // b) * b
        n = self.traffic["check_requests"]
        rows = np.zeros((n, width), np.int32)
        firsts = np.zeros((n,), np.int32)
        final = np.zeros((n, region), np.int32)
        fixed = np.full((n, region), -1, np.int32)
        for s, r in enumerate(sample):
            prompt = traffic.prompt_tokens(self.traffic, self.seed, r["index"],
                                           r["prompt_tokens"], self.ids_under)
            tokens = np.asarray(r["tokens"], np.int32)
            fixed_at = self.passes.get((prompt.tobytes(), tuple(r["tokens"])))
            if fixed_at is None or ((tokens < 0)
                                    | (tokens >= c["vocab_size"])).any():
                return verdict(float("inf"), float("inf"))
            seq = np.concatenate([prompt, tokens])
            rows[s, :len(seq)] = seq   # later blocks are not seen
            firsts[s] = first = len(prompt) // b * b
            whole = len(seq) // b * b - first
            final[s, :whole] = seq[first:first + whole]
            known = len(prompt) - first
            fixed[s, known:whole] = fixed_at[:whole - known]
        noisy = np.stack([
            reference_sdar.noisy_copies(c, final[s], fixed[s], firsts[s])[0]
            for s in range(n)])
        ends = weights_sdar.sdar_ends(c, self.seed)
        quants = (None,) if quant is None else (None, quant)
        hidden = reference_sdar.hidden_states(
            c, rows, noisy, firsts, ends,
            lambda i: weights_sdar.sdar_layer(c, self.seed, i), quants)
        token_gaps, place_gaps = [], []
        for s in range(len(sample)):
            control = None if quant is None else (hidden[1][1][s], quant)
            tg, pg = reference_sdar.pass_gaps(
                c, hidden[0][1][s], final[s], fixed[s], ends, control)
            token_gaps.append(tg)
            place_gaps.append(pg)
        token_gaps = np.concatenate(token_gaps)
        place_gaps = np.concatenate(place_gaps)
        widest = ", ".join(f"{g:.3f}" for g in np.sort(token_gaps)[-5:][::-1])
        distinct = min(len(set(r["tokens"])) / len(r["tokens"])
                       for r in sample)
        self.log(f"check: {len(token_gaps)} tokens and {len(place_gaps)} "
                 f"passes of {len(sample)} streams, padded to {width} and "
                 f"{b} copies of {region}; widest token gaps {widest}; "
                 f"{int((token_gaps > 0.1).sum())} over 0.1, mean "
                 f"{token_gaps.mean():.4f}; place gaps: "
                 f"{int((place_gaps > 0).sum())} passes fixed another "
                 f"position than the reference's, widest "
                 f"{place_gaps.max():.4f}, mean {place_gaps.mean():.5f}; "
                 f"least distinct share of a stream's tokens {distinct:.2f}")
        return verdict(float(token_gaps.mean()), float(place_gaps.mean()))
