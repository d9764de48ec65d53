"""The yardstick's arithmetic: the chip's published peaks, and the operations
and bytes that each timed program needs, worked out from shapes alone: what
the algorithm needs, whatever implements it.  Nothing here imports the
program.

Every work function takes the configuration (the JSON object of
``configs/<name>.json``) and ``counts``, what the run counted in the interval
it measured, and returns ``{"flops": ..., "bytes": ...}``.
"""

# One entry per ``device_kind`` as JAX names it.  A device that is not listed
# is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add them to "
            "benchmark/work.py PEAKS with their source"
        )
    return PEAKS[device_kind]


def roofline_seconds(work, device_kind):
    """The least time the chip could take for ``work``, and which of the two
    bounds set it: (seconds, "flops" | "hbm")."""
    peak = peaks(device_kind)
    by_flops = work["flops"] / peak["flops_per_s"]
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "hbm")


# -- ResNet-50 ---------------------------------------------------------------

def _conv_flops(out_ch, in_ch, k, out_hw):
    return 2 * out_ch * in_ch * k * k * out_hw * out_hw


def resnet50_row_flops(config):
    """Forward FLOPs of one image, 2 per multiply-add, convolutions and the
    head: 8.18e9 at 224 (4.09 GMAC, torchvision's resnet50)."""
    def out_of(hw, stride):
        return (hw + stride - 1) // stride

    hw = out_of(config["image_size"], 2)
    flops = _conv_flops(config["stem_channels"], config["in_channels"], 7, hw)
    hw = out_of(hw, 2)  # 3x3 max pool, stride 2
    prev = config["stem_channels"]
    for mid, blocks, first_stride in config["stages"]:
        out = mid * config["expansion"]
        for b in range(blocks):
            stride = first_stride if b == 0 else 1
            flops += _conv_flops(mid, prev, 1, hw)      # at the input size
            hw_out = out_of(hw, stride)                 # v1.5: 3x3 strides
            flops += _conv_flops(mid, mid, 3, hw_out)
            flops += _conv_flops(out, mid, 1, hw_out)
            if prev != out or stride != 1:
                flops += _conv_flops(out, prev, 1, hw_out)
            prev, hw = out, hw_out
    return flops + 2 * prev * config["num_classes"]


def resnet50_param_count(config):
    n = config["stem_channels"] * config["in_channels"] * 49
    prev = config["stem_channels"]
    for mid, blocks, first_stride in config["stages"]:
        out = mid * config["expansion"]
        for b in range(blocks):
            stride = first_stride if b == 0 else 1
            n += mid * prev + mid * mid * 9 + out * mid
            if prev != out or stride != 1:
                n += out * prev
            prev = out
    return n + prev * config["num_classes"] + config["num_classes"]


def resnet50_forward(config, counts):
    """``counts``: ``rows`` classified in ``calls`` forward programs.  Bytes:
    the float32 rows in, the scores out, and the bf16 weights once a call;
    activations are the implementation's business."""
    rows, calls = counts["rows"], counts["calls"]
    row_bytes = 4 * (config["in_channels"] * config["image_size"] ** 2
                     + config["num_classes"])
    return {
        "flops": resnet50_rows(config, counts)["flops"],
        "bytes": rows * row_bytes + calls * 2 * resnet50_param_count(config),
    }


# -- the decoder ---------------------------------------------------------------

def lm_layer_params(config):
    d, hd = config["hidden_size"], config["head_dim"]
    attn = d * hd * (2 * config["num_attention_heads"]
                     + 2 * config["num_key_value_heads"])
    return attn + 3 * d * config["intermediate_size"]


def lm_matmul_params(config):
    """Weights a token meets in matrix products: every layer and the head
    (the embedding is a look-up of one row)."""
    return (config["num_hidden_layers"] * lm_layer_params(config)
            + config["hidden_size"] * config["vocab_size"])


def lm_param_count(config):
    d = config["hidden_size"]
    return (lm_matmul_params(config) + d * config["vocab_size"]
            + d * (2 * config["num_hidden_layers"] + 1))


def lm_kv_bytes_per_token(config):
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * config["head_dim"] * 2)


def _attn_flops(config, context_sum):
    # scores and the weighted sum: 2 * 2 * heads * head size per key met
    return (4 * config["num_attention_heads"] * config["head_dim"]
            * config["num_hidden_layers"] * context_sum)


def lm_decode(config, counts):
    """``calls`` decode ticks that advanced ``lane_steps`` streams by a token
    each, over ``context_sum`` cached positions in all (each lane's real
    length, summed).  A tick reads every matrix once (7.25 GB at depth 16)
    and each lane's keys and values once."""
    steps, ctx = counts["lane_steps"], counts["context_sum"]
    return {
        "flops": 2 * steps * lm_matmul_params(config) + _attn_flops(config, ctx),
        "bytes": (counts["calls"] * 2 * lm_matmul_params(config)
                  + (ctx + steps) * lm_kv_bytes_per_token(config)),
    }


def lm_prefill(config, counts):
    """``chunks``: (start, tokens) of each prefill chunk, real tokens only;
    ``scale``, where given, multiplies their work.
    Each token passes the layers; one position of a chunk passes the head;
    position p attends p + 1 keys."""
    chunks, scale = counts["chunks"], counts.get("scale", 1.0)
    tokens = sum(n for _, n in chunks)
    ctx = sum(n * start + n * (n + 1) // 2 for start, n in chunks)
    layers = config["num_hidden_layers"] * lm_layer_params(config)
    head = config["hidden_size"] * config["vocab_size"]
    kv = lm_kv_bytes_per_token(config)
    # ``scale``: the chunks are the clients' record of an interval, the calls
    # the trace's: the work of the one brought to the count of the other
    return {
        "flops": scale * (2 * tokens * layers + 2 * len(chunks) * head
                          + _attn_flops(config, ctx)),
        "bytes": scale * (len(chunks) * 2 * (layers + head)
                          + sum(start + 2 * n for start, n in chunks) * kv),
    }


def lm_tokens(config, counts):
    """The whole step's work for ``mfu``: every prompt token through the
    layers, every output token through layers and head, and attention over
    the ``context_sum`` keys that those tokens met."""
    layers = config["num_hidden_layers"] * lm_layer_params(config)
    head = config["hidden_size"] * config["vocab_size"]
    done = counts["prompt_tokens"] + counts["output_tokens"]
    return {
        "flops": (2 * done * layers + 2 * counts["output_tokens"] * head
                  + _attn_flops(config, counts["context_sum"])),
        "bytes": 0,
    }


def resnet50_rows(config, counts):
    """The whole step's work for ``mfu``: ``rows`` classified."""
    return {"flops": counts["rows"] * resnet50_row_flops(config), "bytes": 0}
