"""Closed-loop unary clients over gRPC with inputs and outputs in TPU-shm
regions: the client loop of ``chip_smoke.py``'s ``vision_phase``, with the
regions filled once in set-up and a ring of them to a client.

The clients are threads of the process that holds the chip: a chip belongs
to one process, so a TPU-shm client lives with its server.
"""

import threading
import time

import numpy as np

from benchmark import reference, traffic, weights

END_TO_END = ("rows_per_s", "request_p95_ms")

# The one number compared, ``score_gap``: the worst |score - reference| over
# the largest |reference score| of a request.  Its limit is the cell's
# (``limits`` in its file), set from that cell's own readings: PERF.md, "How
# correct is decided".


class Run:
    def __init__(self, cell, config, seed, log):
        self.cell, self.config, self.seed, self.log = cell, config, seed, log
        self.traffic = cell["traffic"]
        self.rows = self.traffic["rows_per_request"]
        self.shape = [self.rows, config["in_channels"], config["image_size"],
                      config["image_size"]]
        self.classes = config["num_classes"]
        self.model_name = config["server"]["model_name"]

    # -- set-up ---------------------------------------------------------------

    def build_model(self):
        """The served model, as a deployment would declare it, with the
        benchmark's weights in the runner's place for its own."""
        from client_tpu.serve.models.vision import resnet50_model

        model = resnet50_model(
            name=self.model_name, image_size=self.config["image_size"],
            max_batch_size=self.config["server"]["max_batch_size"],
        )
        # from the configuration's weights_seed, not from --seed: the program
        # compiles these into its executables (configs/resnet50-224.json)
        model.fn.params = weights.resnet50_params(
            self.config, self.config["weights_seed"])
        return model

    def setup(self):
        import jax

        import client_tpu.grpc as grpcclient
        from client_tpu.serve import Server
        from client_tpu.utils import tpu_shared_memory as tpushm

        self.tpushm, self.grpc = tpushm, grpcclient
        model = self.build_model()
        self.server = Server(models=[model], http_port=0, grpc_port=0,
                             with_default_models=False).start()
        self.url = self.server.grpc_address
        self.control = grpcclient.InferenceServerClient(self.url)
        in_bytes = int(np.prod(self.shape)) * 4
        out_bytes = self.rows * self.classes * 4
        device = jax.devices()[0]
        # per client, per ring position: (input name, handle, output name,
        # handle); the rows of region number n are weights.rows(seed, n)
        self.regions = []
        for c in range(self.traffic["clients"]):
            ring = []
            for r in range(self.traffic["regions_per_client"]):
                names = (f"in_{c}_{r}", f"out_{c}_{r}")
                h_in = tpushm.create_shared_memory_region(names[0], in_bytes)
                h_out = tpushm.create_shared_memory_region(names[1], out_bytes)
                x = jax.device_put(
                    weights.rows(self.seed, self.region_number(c, r),
                                 self.shape), device)
                tpushm.set_shared_memory_region(h_in, [x])
                for name, handle, size in ((names[0], h_in, in_bytes),
                                           (names[1], h_out, out_bytes)):
                    self.control.register_tpu_shared_memory(
                        name, tpushm.get_raw_handle(handle), 0, size)
                ring.append((names[0], h_in, names[1], h_out))
            self.regions.append(ring)
        self.in_bytes, self.out_bytes = in_bytes, out_bytes
        self.log(f"server up, {len(self.regions)} rings of regions filled")
        self.warm_up(model)

    def region_number(self, client, ring):
        return client * self.traffic["regions_per_client"] + ring

    def warm_up(self, model):
        """Every shape the window will use: the fused forward at each arity
        the batcher can gather from requests of this size, through the
        batcher's own jitted callable (ModelBatcher.warmup does the same, for
        sizes this cell never sends), then one request to each region through
        the served path."""
        import jax

        batcher = self.server.engine._batcher_for(model)
        fused = batcher._fused_jit()
        top = min(batcher.max_fused_arity, batcher.max_batch // self.rows,
                  self.traffic["clients"])
        parts = [self.tpushm.get_contents_as_jax(ring[0][1])
                 for ring in self.regions[:top]]
        for k in range(1, top + 1):
            jax.block_until_ready(fused({"INPUT0": tuple(parts[:k])}))
        self.log(f"fused forward warm at arities 1..{top}")
        done = self.drive(lambda i, now: i < self.traffic["regions_per_client"])
        self.log(f"warm-up: arities 1..{top}, {len(done)} requests served")

    # -- the clients ----------------------------------------------------------

    def drive(self, go_on, keep=()):
        """Run every client until ``go_on(i, now)`` says no to its i-th
        request.  Returns [(client, i, send, ack, done, scores or None)];
        scores are kept for the regions in ``keep``."""
        records, lock = [], threading.Lock()
        failures = []

        def client_loop(c):
            mine = []
            try:
                with self.grpc.InferenceServerClient(self.url) as client:
                    calls = []
                    for in_name, _, out_name, _ in self.regions[c]:
                        inp = self.grpc.InferInput("INPUT0", self.shape, "FP32")
                        inp.set_shared_memory(in_name, self.in_bytes)
                        out = self.grpc.InferRequestedOutput("OUTPUT0")
                        out.set_shared_memory(out_name, self.out_bytes)
                        calls.append(([inp], [out]))
                    i = 0
                    while go_on(i, time.monotonic()):
                        r = i % len(calls)
                        send = time.monotonic()
                        client.infer(self.model_name, calls[r][0],
                                     outputs=calls[r][1])
                        ack = time.monotonic()
                        # the read-back is the completion: the ack came at
                        # dispatch
                        scores = self.tpushm.get_contents_as_numpy(
                            self.regions[c][r][3], "FP32",
                            [self.rows, self.classes])
                        done = time.monotonic()
                        kept = (np.array(scores)
                                if self.region_number(c, r) in keep else None)
                        mine.append((c, i, send, ack, done, kept))
                        i += 1
            except Exception as e:  # noqa: BLE001 - counted, reported, fatal
                failures.append(f"client {c}: {type(e).__name__}: {e}")
            with lock:
                records.extend(mine)

        threads = [threading.Thread(target=client_loop, args=(c,))
                   for c in range(self.traffic["clients"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.failures = failures
        return records

    def stats(self):
        s = self.control.get_inference_statistics(self.model_name).model_stats[0]
        return {
            "success": s.inference_stats.success.count,
            "fail": s.inference_stats.fail.count,
            "queue_ns": s.inference_stats.queue.ns,
            "executions": s.execution_count,
            "rows": s.inference_count,
        }

    # -- the window -----------------------------------------------------------

    def measure(self, seconds, tracer):
        n_regions = (self.traffic["clients"]
                     * self.traffic["regions_per_client"])
        rng = np.random.default_rng([self.seed, 7])
        keep = set(rng.choice(n_regions, self.traffic["check_regions"],
                              replace=False).tolist())
        before = self.stats()
        t_start = time.monotonic()
        t_end = t_start + seconds
        if tracer is not None:
            tracer.arm(t_start, seconds)
        records = self.drive(lambda i, now: now < t_end, keep)
        after = self.stats()
        traced = tracer.join() if tracer is not None else None
        # a request sent in the window counts in the tail; its rows count in
        # the rate where it was read back inside the window
        inside = [r for r in records if r[4] <= t_end]
        per_second = np.bincount(
            [int(r[4] - t_start) for r in inside], minlength=int(seconds))
        self.log("requests read back in each second: "
                 + " ".join(map(str, per_second.tolist())))
        window = {
            "t_start": t_start, "seconds": seconds,
            "attempted": len(records) + len(self.failures),
            "failed": len(self.failures),
            "request_ms": [1e3 * (r[4] - r[2]) for r in records],
            "series": {"ack_ms": [1e3 * (r[3] - r[2]) for r in records]},
            "stats": {k: after[k] - before[k] for k in after},
            "counts": {"rows": len(inside) * self.rows},
            "kept": [(self.region_number(r[0], r[1] % len(self.regions[0])),
                      r[5]) for r in records if r[5] is not None],
        }
        if traced is not None:
            t_a, t_b = traced
            window["traced_seconds"] = t_b - t_a
            window["traced_counts"] = {"rows": self.rows * sum(
                1 for r in records if t_a <= r[4] < t_b)}
        return window

    def end_to_end(self, window):
        return {
            "rows_per_s": (window["counts"]["rows"] / window["seconds"],
                           "rows/s"),
            "request_p95_ms": (traffic.percentile(window["request_ms"], 95),
                               "ms"),
        }

    # -- after the window -----------------------------------------------------

    def close(self):
        """Stop the server and free what the program held on the device."""
        self.control.unregister_tpu_shared_memory()
        self.control.close()
        for ring in self.regions:
            for _, h_in, _, h_out in ring:
                self.tpushm.destroy_shared_memory_region(h_in)
                self.tpushm.destroy_shared_memory_region(h_out)
        self.regions = []
        self.server.stop()
        self.server = None

    def check(self, window, quant=None):
        """Every answer the window read back from the sampled regions,
        against the plain reference on those regions' rows.  ``quant`` puts
        the low-precision control in the program's place."""
        params = weights.resnet50_params(self.config,
                                         self.config["weights_seed"])
        worst, compared, reference_of = 0.0, 0, {}
        for number, served in window["kept"]:
            if number not in reference_of:
                x = weights.rows(self.seed, number, self.shape)
                reference_of[number] = [
                    np.asarray(reference.resnet50_scores(self.config, params,
                                                         x, q))
                    for q in ((None,) if quant is None else (None, quant))]
            want = reference_of[number][0]
            got = served if quant is None else reference_of[number][1]
            ok = got.shape == want.shape and np.isfinite(got).all()
            gap = (float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
                   if ok else float("inf"))
            worst, compared = max(worst, gap), compared + 1
        if compared == 0:
            worst = float("inf")
        self.log(f"check: {compared} answers of {len(reference_of)} regions, "
                 f"worst gap {worst:.3g}")
        return {"score_gap": {
            "value": worst, "limit": self.cell["limits"]["score_gap"]}}
