"""The yardstick's arithmetic for the SambaY family (``configs/phi-4-mini-
flash-reasoning.json``): the operations and bytes that each timed program
needs, from shapes alone, whatever implements it.  Nothing here imports the
program.  As in ``work.py``, every work function takes the configuration and
``counts`` and returns ``{"flops": ..., "bytes": ...}``.

A layer's kind follows from its index (``kinds``).  Per lane the model keeps
a conv tail and an SSM state for each of its recurrent layers, a window of
keys and values for each window layer, and one K/V cache that grows, which
the full layer writes and the full and cross layers read.
"""

BYTES = 2          # bf16 weights, keys and values
STATE_BYTES = 4    # the SSM state, float32


def kinds(config):
    n, every = config["num_hidden_layers"], config["mb_per_layer"]
    half = n // 2
    out = []
    for i in range(n):
        recurrent = i % every == 0
        if i < half:
            out.append("mamba" if recurrent else "window")
        elif i == half:
            out.append("memory")
        elif i == half + 1:
            out.append("full")
        else:
            out.append("gmu" if recurrent else "cross")
    return out


def _sizes(config):
    mamba = config["assumed"]["mamba"]
    d = config["hidden_size"]
    return (d, mamba["expand"] * d, mamba["d_state"], mamba["d_conv"],
            mamba["dt_rank"], config["num_attention_heads"] * config["head_dim"],
            config["num_key_value_heads"] * config["head_dim"])


def mixer_params(config, kind):
    """Matrix parameters of one mixer (biases, norms and the conv's four
    taps a channel are not matrices: under 0.1% of a layer)."""
    d, di, ds, _, dt_rank, q_out, kv_out = _sizes(config)
    if kind in ("mamba", "memory"):
        return d * 2 * di + di * (dt_rank + 2 * ds) + dt_rank * di + di * d
    if kind in ("window", "full"):
        return d * (q_out + 2 * kv_out) + q_out * d
    if kind == "gmu":
        return 2 * d * di
    return d * q_out + q_out * d  # cross


def layer_params(config):
    """Matrix parameters of all layers: mixers and the SwiGLU MLPs."""
    mlp = 3 * config["hidden_size"] * config["intermediate_size"]
    return sum(mixer_params(config, k) + mlp for k in kinds(config))


def head_params(config):
    """The tied head: the embedding, read whole as a matrix once a tick."""
    return config["hidden_size"] * config["vocab_size"]


def matmul_params(config):
    return layer_params(config) + head_params(config)


def kv_row_bytes(config):
    """Keys and values of one position in one attention layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BYTES


def lane_state_bytes(config):
    """A lane's fixed state: (recurrent layers' conv tails and SSM states,
    window layers' rings)."""
    _, di, ds, d_conv, _, _, _ = _sizes(config)
    k = kinds(config)
    recurrent = (k.count("mamba") + k.count("memory")) * (
        di * ds * STATE_BYTES + di * (d_conv - 1) * BYTES)
    rings = k.count("window") * config["sliding_window"] * kv_row_bytes(config)
    return recurrent, rings


def _attention(config, window_keys, full_keys):
    """(flops, bytes read) of attention over ``window_keys`` keys met in
    each window layer and ``full_keys`` in the full layer and in each cross
    layer.  A differential head's scores cost 2 hd a key and its weighted
    sum, over a value twice as wide, 4 hd: 6 hd a key a query head."""
    k = kinds(config)
    keys = k.count("window") * window_keys + (1 + k.count("cross")) * full_keys
    per_key = 6 * config["num_attention_heads"] * config["head_dim"]
    return per_key * keys, keys * kv_row_bytes(config)


def _scan_flops(config, positions):
    # a state element a position: exp(dt A), two multiply-adds, one for C
    _, di, ds, _, _, _, _ = _sizes(config)
    k = kinds(config)
    return 6 * (k.count("mamba") + k.count("memory")) * di * ds * positions


def decode_tick(config, counts):
    """``calls`` decode ticks that advanced ``lane_steps`` streams by a
    token each, over ``context_sum`` cached positions in all (each lane's
    real length, summed) of which ``window_sum`` lie inside a window (the
    sum of ``min(length, window)``).  A tick reads every matrix once; a
    lane-step reads and writes its recurrent states, reads each window
    layer's window and writes a row of it, reads the full K/V once for the
    full layer and once for each cross layer, and writes a row of it."""
    steps = counts["lane_steps"]
    k = kinds(config)
    attn_flops, attn_bytes = _attention(config, counts["window_sum"],
                                        counts["context_sum"])
    recurrent, _ = lane_state_bytes(config)
    rows_written = (k.count("window") + 1) * steps * kv_row_bytes(config)
    return {
        "flops": (2 * steps * matmul_params(config) + attn_flops
                  + _scan_flops(config, steps)),
        "bytes": (counts["calls"] * BYTES * matmul_params(config)
                  + 2 * steps * recurrent + attn_bytes + rows_written),
    }


def _chunk_keys(config, start, n):
    """(keys met in a window layer, keys met in the full layer) by the
    ``n`` positions from ``start``: position p attends min(p + 1, window)
    and p + 1 keys."""
    w = config["sliding_window"]
    full = n * start + n * (n + 1) // 2
    window = sum(min(p + 1, w) for p in range(start, start + n))
    return window, full


def prefill_chunk(config, counts):
    """``chunks``: (start, tokens) of each prefill chunk, real tokens only;
    ``scale``, where given, multiplies their work.  Each token passes the
    layers; one position of a chunk passes the head.  Bytes: the matrices
    once a chunk, the lane's recurrent states read and written, the window
    before the chunk and the full K/V before it read once a layer that
    reads them, and the chunk's own rows written."""
    chunks, scale = counts["chunks"], counts.get("scale", 1.0)
    k = kinds(config)
    tokens = sum(n for _, n in chunks)
    window_keys = full_keys = window_rows = 0
    for start, n in chunks:
        wk, fk = _chunk_keys(config, start, n)
        window_keys, full_keys = window_keys + wk, full_keys + fk
        window_rows += min(start, config["sliding_window"])
    attn_flops, _ = _attention(config, window_keys, full_keys)
    recurrent, _ = lane_state_bytes(config)
    row = kv_row_bytes(config)
    read = (k.count("window") * window_rows
            + (1 + k.count("cross")) * sum(s for s, _ in chunks)) * row
    written = (k.count("window") + 1) * tokens * row
    return {
        "flops": scale * (2 * tokens * layer_params(config)
                          + 2 * len(chunks) * head_params(config)
                          + attn_flops + _scan_flops(config, tokens)),
        "bytes": scale * (len(chunks) * (BYTES * matmul_params(config)
                                         + 2 * recurrent) + read + written),
    }


def tokens(config, counts):
    """The whole step's work for ``mfu``: every prompt token through the
    layers, every output token through layers and head, attention over the
    ``context_sum`` keys those tokens met in the full and cross layers and
    the ``window_context_sum`` they met in a window layer, and the scan."""
    done = counts["prompt_tokens"] + counts["output_tokens"]
    attn_flops, _ = _attention(config, counts["window_context_sum"],
                               counts["context_sum"])
    return {
        "flops": (2 * done * layer_params(config)
                  + 2 * counts["output_tokens"] * head_params(config)
                  + attn_flops + _scan_flops(config, done)),
        "bytes": 0,
    }
