#!/usr/bin/env python
"""TPU shared-memory infer — the framework's analog of the reference's
simple_grpc_cudashm_client.py (SURVEY.md §3.5): allocate HBM regions, pass
the serialized raw handle to the server, run zero-copy infer with
inputs/outputs resident in device memory, read results back.

In-process (--hermetic) the server resolves the regions broker-side with no
host copies; against an out-of-process same-host server the region reaches
the server through its host window.  A TPU belongs to one process at a time:
when that server holds the chip, run this client with JAX_PLATFORMS=cpu (its
region then stages in host memory); without it the client fails with a
message saying so (verified on a v5e, PR 21).
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import client_tpu.grpc as grpcclient  # noqa: E402
from client_tpu.utils import tpu_shared_memory as tpushm  # noqa: E402


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("-u", "--url", default="localhost:8001")
    parser.add_argument("--hermetic", action="store_true")
    args = parser.parse_args()

    server = None
    url = args.url
    if args.hermetic:
        from client_tpu.serve import Server

        server = Server(grpc_port=0).start()
        url = server.grpc_address

    i0 = np.arange(16, dtype=np.int32).reshape(1, 16)
    i1 = np.full((1, 16), 2, dtype=np.int32)
    staging = None if args.hermetic else "/tpu_simple_in"
    out_staging = None if args.hermetic else "/tpu_simple_out"
    in_handle = tpushm.create_shared_memory_region(
        "tpu_input", i0.nbytes + i1.nbytes, staging_key=staging
    )
    out_handle = tpushm.create_shared_memory_region(
        "tpu_output", i0.nbytes + i1.nbytes, staging_key=out_staging
    )
    try:
        tpushm.set_shared_memory_region(in_handle, [i0, i1])  # one H2D
        with grpcclient.InferenceServerClient(url) as client:
            client.unregister_tpu_shared_memory()
            client.register_tpu_shared_memory(
                "tpu_input", tpushm.get_raw_handle(in_handle), 0,
                i0.nbytes + i1.nbytes,
            )
            client.register_tpu_shared_memory(
                "tpu_output", tpushm.get_raw_handle(out_handle), 0,
                i0.nbytes + i1.nbytes,
            )
            inputs = [
                grpcclient.InferInput("INPUT0", [1, 16], "INT32"),
                grpcclient.InferInput("INPUT1", [1, 16], "INT32"),
            ]
            inputs[0].set_shared_memory("tpu_input", i0.nbytes)
            inputs[1].set_shared_memory("tpu_input", i1.nbytes,
                                        offset=i0.nbytes)
            outputs = [
                grpcclient.InferRequestedOutput("OUTPUT0"),
                grpcclient.InferRequestedOutput("OUTPUT1"),
            ]
            outputs[0].set_shared_memory("tpu_output", i0.nbytes)
            outputs[1].set_shared_memory("tpu_output", i1.nbytes,
                                         offset=i0.nbytes)
            client.infer("simple", inputs, outputs=outputs)
            sum_ = tpushm.get_contents_as_numpy(out_handle, "INT32", [1, 16])
            diff = tpushm.get_contents_as_numpy(out_handle, "INT32", [1, 16],
                                                offset=i0.nbytes)
            for i in range(16):
                print(f"{i0[0][i]} + {i1[0][i]} = {sum_[0][i]}")
                if (i0[0][i] + i1[0][i]) != sum_[0][i]:
                    sys.exit("error: incorrect sum")
                if (i0[0][i] - i1[0][i]) != diff[0][i]:
                    sys.exit("error: incorrect difference")
            client.unregister_tpu_shared_memory()
            print("PASS: tpu shared memory")
    finally:
        tpushm.destroy_shared_memory_region(in_handle)
        tpushm.destroy_shared_memory_region(out_handle)
        if server:
            server.stop()


if __name__ == "__main__":
    main()
