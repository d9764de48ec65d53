#!/usr/bin/env python
"""TPU device-buffer shared memory over HTTP — the framework's CUDA-shm
analog (reference simple_http_cudashm_client.py): tensors live in HBM
regions, requests carry only region references.  Against a standalone
server that holds the chip, run with JAX_PLATFORMS=cpu (host-window face)
or --hermetic: a TPU belongs to one process at a time."""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import client_tpu.http as httpclient  # noqa: E402
from client_tpu.utils import tpu_shared_memory as tpushm  # noqa: E402

def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("-u", "--url", default="localhost:8000")
    parser.add_argument("--hermetic", action="store_true")
    args = parser.parse_args()

    server = None
    url = args.url
    if args.hermetic:
        from client_tpu.serve import Server

        server = Server(http_port=0).start()
        url = server.http_address

    try:
        i0 = np.arange(16, dtype=np.float32).reshape(1, 16)
        in_h = tpushm.create_shared_memory_region("tpu_in_http", i0.nbytes)
        out_h = tpushm.create_shared_memory_region("tpu_out_http", i0.nbytes)
        try:
            tpushm.set_shared_memory_region(in_h, [i0])
            with httpclient.InferenceServerClient(url) as client:
                client.unregister_tpu_shared_memory()
                client.register_tpu_shared_memory(
                    "tpu_in_http", tpushm.get_raw_handle(in_h), 0, i0.nbytes)
                client.register_tpu_shared_memory(
                    "tpu_out_http", tpushm.get_raw_handle(out_h), 0, i0.nbytes)
                inp = httpclient.InferInput("INPUT0", [1, 16], "FP32")
                inp.set_shared_memory("tpu_in_http", i0.nbytes)
                out = httpclient.InferRequestedOutput("OUTPUT0")
                out.set_shared_memory("tpu_out_http", i0.nbytes)
                client.infer("identity", [inp], outputs=[out])
                got = tpushm.get_contents_as_numpy(out_h, np.float32, [1, 16])
                np.testing.assert_array_equal(got, i0)
                client.unregister_tpu_shared_memory()
            print("PASS: http tpushm infer")
        finally:
            tpushm.destroy_shared_memory_region(in_h)
            tpushm.destroy_shared_memory_region(out_h)
    finally:
        if server:
            server.stop()


if __name__ == "__main__":
    main()
