"""Closed-loop token streams over ``ModelStreamInfer`` through ``LmEngine``.
The clients run in one child process (``lm_client.py``), which never loads
JAX; this process holds the chip and the server."""

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmark import reference, traffic, weights

END_TO_END = ("tokens_per_s", "ttft_p95_ms")

# The one number compared, ``token_gap``: the widest gap, in logits, by which
# a served token lies below the reference's best at its position.  Its limit
# is the cell's (``limits`` in its file), set from that cell's own readings:
# PERF.md, "How correct is decided".

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # of the checkout
CHILD_LEAD_S = 2.5   # from spawning the clients to their first request
STREAM_TIMEOUT_S = 120


def chunk_plan(prompt_len, chunk):
    """(start, tokens) of each prefill chunk of an unshared prompt: whole
    chunks, then the rest.  What the algorithm needs; the engine pads the
    last one to a bucket."""
    return [(s, min(chunk, prompt_len - s))
            for s in range(0, prompt_len, chunk)]


class Run:
    def __init__(self, cell, config, seed, log):
        self.cell, self.config, self.seed, self.log = cell, config, seed, log
        self.traffic = cell["traffic"]
        self.engine_args = dict(config["engine"])
        self.model_name = self.engine_args.pop("model_name")
        self.max_seq = self.engine_args.pop("max_seq")
        self.work_dir = os.path.join(ROOT, ".bench_tmp", cell["name"])
        self.next_index = 0

    # -- set-up ---------------------------------------------------------------

    def build_model(self):
        from client_tpu.serve.models.language import (
            _LmRunner, lm_streaming_batched_model)
        from client_tpu.serve.models.transformer import TransformerConfig

        c = self.config
        cfg = TransformerConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
            max_seq=self.max_seq, rope_theta=c["rope_theta"],
            dtype=c["torch_dtype"],
        )
        if cfg.head_dim != c["head_dim"]:
            raise ValueError("the program derives another head size")
        runner = _LmRunner(cfg, params=weights.lm_params(c, self.seed))
        args = dict(self.engine_args)
        args["lane_counts"] = tuple(args["lane_counts"])
        return lm_streaming_batched_model(name=self.model_name, runner=runner,
                                          **args)

    def setup(self):
        from client_tpu.serve import Server

        os.makedirs(self.work_dir, exist_ok=True)
        self.model = self.build_model()
        self.engine = self.model.runner.scheduler
        self.server = Server(models=[self.model], http_port=0, grpc_port=0,
                             with_default_models=False).start()
        self.url = self.server.grpc_address
        # one prompt for each prefill width the mix will meet (a prompt past
        # the chunk runs whole chunks): with the decode tick, every shape
        chunk = self.engine.buckets[-1]
        widths = sorted({
            next(b for b in self.engine.buckets if b >= min(p, chunk))
            for p, _ in traffic.size_block(self.traffic)
        })
        records = self.clients({"requests": [[w, 4] for w in widths]})
        self.log(f"warm-up: prefill widths {widths}, "
                 f"{sum(len(r['tokens']) for r in records)} tokens")

    def clients(self, plan):
        """Run ``lm_client.py`` on ``plan`` to its end; its records."""
        plan = dict(plan, url=self.url, model=self.model_name,
                    traffic=self.traffic, seed=self.seed,
                    vocab_size=self.config["vocab_size"],
                    first_index=self.next_index, timeout_s=STREAM_TIMEOUT_S)
        plan_path = os.path.join(self.work_dir, "plan.json")
        out_path = os.path.join(self.work_dir, "records.jsonl")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        child = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "lm_client.py"),
             plan_path, out_path], env=env, cwd=ROOT)
        try:
            limit = plan.get("t_end", time.monotonic()) - time.monotonic()
            rc = child.wait(timeout=limit + 2 * STREAM_TIMEOUT_S)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        if rc != 0:
            raise RuntimeError(f"lm_client.py exited with {rc}")
        with open(out_path) as f:
            records = [json.loads(line) for line in f]
        self.next_index = max([r["index"] for r in records],
                              default=self.next_index) + 1
        return records

    # -- the window -----------------------------------------------------------

    def measure(self, seconds, tracer):
        t_ramp = time.monotonic() + CHILD_LEAD_S
        t_start = t_ramp + self.traffic["ramp_seconds"]
        t_end = t_start + seconds
        if tracer is not None:
            tracer.arm(t_start, seconds)
        records = self.clients({"t_ramp": t_ramp, "t_start": t_start,
                                "t_end": t_end})
        traced = tracer.join() if tracer is not None else None
        ticks = [t for t in self.engine.tick_trace()
                 if t_start <= t["t0"] < t_end]
        # the window's requests are those sent inside it; the ramp's streams
        # count with the tokens that arrive inside it
        sent = [r for r in records if r["send"] >= t_start]
        good = [r for r in records if "error" not in r and r["tokens"]]
        first = [r for r in good if r["send"] >= t_start]
        window = {
            "t_start": t_start, "seconds": seconds,
            "attempted": len(sent), "failed": len(records) - len(good),
            "ttft_ms": [1e3 * (r["times"][0] - r["send"]) for r in first],
            "series": {"token_gap_ms": [
                1e3 * (b - a) for r in good
                for a, b in zip(r["times"], r["times"][1:])
                if t_start <= b < t_end]},
            "ticks": ticks,
            "counts": self.counts(good, t_start, t_end),
            "finished": [r for r in good if not r.get("cut")
                         and r["times"][-1] >= t_start],
        }
        window["series"]["ttft_ms"] = window["ttft_ms"]
        window["tokens"] = window["counts"]["output_tokens"]
        ttft = sorted(window["ttft_ms"])
        self.log("ttft ms over %d requests: mean %.1f p50 %.1f p80 %.1f p90 "
                 "%.1f p95 %.1f max %.1f" % (
                     len(ttft), sum(ttft) / max(len(ttft), 1),
                     *(traffic.percentile(ttft or [0], q)
                       for q in (50, 80, 90, 95, 100))))
        slowest = sorted(first, key=lambda r: r["send"] - r["times"][0])[:5]
        self.log("slowest first tokens (ms, s into the window, prompt): "
                 + ", ".join("%.0f at %.1f of %d" % (
                     1e3 * (r["times"][0] - r["send"]), r["send"] - t_start,
                     r["prompt_tokens"]) for r in slowest))
        early = [r["index"] for r in good if not r.get("cut")
                 and len(r["tokens"]) < r["max_tokens"]]
        self.log(f"streams ended early (EOS): {early}; engine: prefix "
                 f"{self.engine.prefix_stats()}, preempt "
                 f"{self.engine.preempt_stats()}")
        if traced is not None:
            window["traced_span"] = traced
            window["traced_seconds"] = traced[1] - traced[0]
            window["traced_counts"] = self.counts(good, *traced)
        return window

    def counts(self, records, t_a, t_b):
        """What the streams had done between two instants: output tokens
        received; prompt tokens of the requests whose first token came there
        (a prefill ends in the first token) with their chunks; and for each
        later token, one decode lane-step over the positions before it."""
        chunk = self.engine_args["prefill_chunk"]
        out = {"output_tokens": 0, "prompt_tokens": 0, "lane_steps": 0,
               "context_sum": 0, "decode_context_sum": 0, "chunks": []}
        for r in records:
            p = r["prompt_tokens"]
            for i, t in enumerate(r["times"]):
                if not t_a <= t < t_b:
                    continue
                out["output_tokens"] += 1
                if i == 0:
                    out["prompt_tokens"] += p
                    out["chunks"] += chunk_plan(p, chunk)
                    out["context_sum"] += p * (p + 1) // 2
                else:
                    out["lane_steps"] += 1
                    out["context_sum"] += p + i
                    out["decode_context_sum"] += p + i
        return out

    def end_to_end(self, window):
        return {
            "tokens_per_s": (window["tokens"] / window["seconds"], "tokens/s"),
            "ttft_p95_ms": (traffic.percentile(window["ttft_ms"], 95), "ms"),
        }

    # -- after the window -----------------------------------------------------

    def close(self):
        self.server.stop()
        self.engine.close()
        self.server = self.engine = self.model = None

    def sample(self, window):
        """The finished requests to compare: the longest, and others drawn
        from the seed."""
        done = sorted(window["finished"], key=lambda r: r["index"])
        if not done:
            return []
        longest = max(done, key=lambda r: r["prompt_tokens"] + len(r["tokens"]))
        rest = [r for r in done if r is not longest]
        n = min(self.traffic["check_requests"] - 1, len(rest))
        picks = np.random.default_rng([self.seed, 7]).choice(
            len(rest), n, replace=False)
        return [longest] + [rest[i] for i in sorted(picks.tolist())]

    def check(self, window, quant=None):
        """The widest gap by which a served token's reference logit lies
        below the reference's best, over the sampled streams.  With ``quant``
        the control stands in the program's place: at each position, the
        token that the lower precision puts first."""
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
        rows = np.zeros((self.traffic["check_requests"], width), np.int32)
        for s, r in enumerate(sample):
            prompt = traffic.prompt_tokens(self.traffic, self.seed, r["index"],
                                           r["prompt_tokens"], c["vocab_size"])
            seq = np.concatenate([prompt, np.asarray(r["tokens"], np.int32)])
            rows[s, :len(seq)] = seq   # the causal mask hides what follows
        # the positions that put out a stream's tokens: the prompt's last and
        # every served token but the last (the row's padding repeats the last)
        most = int(self.traffic["output_tokens"]["max"])
        at = np.zeros((len(rows), most), np.int32)
        for s, r in enumerate(sample):
            first = r["prompt_tokens"] - 1
            at[s] = np.minimum(first + np.arange(most),
                               first + len(r["tokens"]) - 1)
        quants = (None,) if quant is None else (None, quant)
        logits = reference.decoder_logits(
            c, rows, at, weights.lm_ends(c, self.seed),
            lambda i: weights.lm_layer(c, self.seed, i), quants)
        worst, compared = 0.0, 0
        for s, r in enumerate(sample):
            n = len(r["tokens"])
            ref = np.asarray(logits[0][s, :n])
            served = (np.asarray(r["tokens"]) if quant is None else
                      np.asarray(logits[1][s, :n]).argmax(-1))
            inside = (served >= 0) & (served < c["vocab_size"])
            if not inside.all():
                return verdict(float("inf"))
            gap = ref.max(-1) - ref[np.arange(n), served]
            worst, compared = max(worst, float(gap.max())), compared + n
        self.log(f"check: {compared} tokens of {len(sample)} streams, "
                 f"padded to {width}")
        return verdict(worst)
