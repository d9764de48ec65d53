"""The LM cells' clients: closed-loop ``ModelStreamInfer`` streams in a
process of their own, so that their callbacks do not share the server's
interpreter lock.  Started by ``drivers/lm_stream.py`` under
``JAX_PLATFORMS=cpu``; imports the gRPC client and numpy, never JAX.

    python benchmark/lm_client.py <plan.json> <records.jsonl>

The plan: url, model, traffic, seed, vocab_size, first_index, and either
``requests`` (a list of [prompt tokens, output tokens], sent all at once: the
warm-up) or ``t_ramp``/``t_start``/``t_end`` on ``time.monotonic``'s clock,
which this host's processes share (the window: the clients start one after
another between ``t_ramp`` and ``t_start``, so that the window opens on
streams at every stage and not on a burst of prompts; each sends its next
request when its last one ends, until ``t_end``; a stream still open then is
cancelled once its first token is in).  One JSON line per request.
"""

import json
import os
import queue
import sys
import threading
import time

import numpy as np

# run as a script: the checkout's root in this directory's place on the path
sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import client_tpu.grpc as grpcclient  # noqa: E402

from benchmark import traffic  # noqa: E402


class Client:
    """One client: one channel, one stream, one request in flight."""

    def __init__(self, plan):
        self.plan = plan
        self.results = queue.Queue()
        self.client = grpcclient.InferenceServerClient(plan["url"])
        self.client.start_stream(
            callback=lambda result, error: self.results.put((result, error)))

    def request(self, index, prompt_len, max_tokens):
        plan = self.plan
        prompt = traffic.prompt_tokens(plan["traffic"], plan["seed"], index,
                                       prompt_len, plan["vocab_size"])
        t_in = grpcclient.InferInput("TOKENS", [len(prompt)], "INT32")
        t_in.set_data_from_numpy(prompt)
        m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        m_in.set_data_from_numpy(np.array([max_tokens], np.int32))
        tokens, times = [], []
        record = {"index": index, "prompt_tokens": prompt_len,
                  "max_tokens": max_tokens, "tokens": tokens, "times": times}
        cut_at = plan.get("t_end")
        record["send"] = send = time.monotonic()
        self.client.async_stream_infer(
            plan["model"], [t_in, m_in], enable_empty_final_response=True)
        while True:
            now = time.monotonic()
            if cut_at is not None and tokens and now >= cut_at:
                # the window has closed and this stream's first token is in:
                # nothing more of it counts, so it is cancelled, not waited for
                self.client.stop_stream(cancel_requests=True)
                self.close()
                return dict(record, cut=True)
            try:
                result, error = self.results.get(timeout=0.1)
            except queue.Empty:
                if now - send > plan["timeout_s"]:
                    return dict(record, error="timed out")
                continue
            if error is not None:
                return dict(record, error=str(error))
            response = result.get_response()
            if response.parameters["triton_final_response"].bool_param:
                return record
            tokens.append(int(result.as_numpy("TOKEN")[0]))
            times.append(time.monotonic())

    def close(self):
        if self.client is not None:
            self.client.stop_stream()
            self.client.close()
            self.client = None


def main():
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    out = open(sys.argv[2], "w")
    lock = threading.Lock()
    next_index = [plan["first_index"]]
    late = []

    def emit(record):
        with lock:
            out.write(json.dumps(record) + "\n")

    def windowed(client, nth):
        ramp = plan["t_start"] - plan["t_ramp"]
        begin = plan["t_ramp"] + ramp * nth / plan["traffic"]["clients"]
        wait = begin - time.monotonic()
        if wait < 0:
            late.append(-wait)
        time.sleep(max(wait, 0))
        while time.monotonic() < plan["t_end"]:
            with lock:
                index = next_index[0]
                next_index[0] += 1
            sizes = traffic.request_sizes(plan["traffic"], plan["seed"], index)
            emit(client.request(index, *sizes))

    def once(client, index, sizes):
        emit(client.request(index, *sizes))

    if "requests" in plan:
        jobs = [(once, (plan["first_index"] + i, sizes))
                for i, sizes in enumerate(plan["requests"])]
    else:
        jobs = [(windowed, (nth,)) for nth in range(plan["traffic"]["clients"])]
    clients = [Client(plan) for _ in jobs]
    threads = [threading.Thread(target=fn, args=(c, *args))
               for c, (fn, args) in zip(clients, jobs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for c in clients:
        c.close()
    out.close()
    if late:
        sys.exit(f"lm_client: {len(late)} clients were ready "
                 f"{max(late):.3f}s after their start")


if __name__ == "__main__":
    main()
