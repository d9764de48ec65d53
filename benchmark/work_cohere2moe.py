"""The yardstick's arithmetic for the ``cohere2_moe`` family as one chip of
its deployment runs it (``configs/command-a-plus-05-2026-ep8-d4.json``): the
operations and bytes that each timed program needs, from shapes and from
what the routing and the lanes' lengths made of them, whatever implements
it.  Nothing here imports the program.  As in ``work.py``, every work
function takes the configuration and ``counts`` and returns ``{"flops": ...,
"bytes": ...}``.

What only the device knows comes in ``counts`` from the program's own
counters: ``expert_rows``, the (token, pick) pairs that fell on held
experts, and ``experts_hit``, the held experts with at least one row, both
summed over the expert layers.  FLOPs count 2 per weight element a row
meets: attention, router, shared experts, ONLY the routed pairs that fell
here, the head.  Bytes count the attention, router, shared and head
matrices once a call, a routed expert's matrices once for each time it was
hit, and keys and values by the positions attention may see
(``kv_positions_live``: a window layer's are capped at the window).
"""

BYTES = 2          # bf16 weights, keys and values


def _sizes(config):
    hd = config["head_dim"]
    return (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"] * hd,
            config["num_key_value_heads"] * hd)


def attention_params(config):
    d, _, q_out, kv_out = _sizes(config)
    return d * (q_out + 2 * kv_out) + q_out * d


def router_params(config):
    return config["hidden_size"] * config["deployment"]["router_experts"]


def expert_params(config):
    """One expert, routed or shared: gate, up and down."""
    d, ff, _, _ = _sizes(config)
    return 3 * d * ff


def layer_dense_params(config):
    """What every row of a layer meets: attention, router, shared experts."""
    return (attention_params(config) + router_params(config)
            + config["num_shared_experts"] * expert_params(config))


def head_params(config):
    """The tied head: the held rows of the embedding, read once a call."""
    return config["hidden_size"] * config["vocab_size"]


def held_params(config):
    """Every matrix parameter this chip holds."""
    return (config["num_hidden_layers"] * (
        layer_dense_params(config)
        + config["num_experts"] * expert_params(config))
        + head_params(config))


def kv_row_bytes(config):
    """Keys and values of one position in one layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BYTES


def _windows(config):
    """(window layers, full layers) among the layers that are run."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return kinds.count("sliding_attention"), kinds.count("full_attention")


def _per_key_flops(config):
    # a key costs a query head 2 hd for its score and 2 hd for its share of
    # the weighted sum
    return 4 * config["num_attention_heads"] * config["head_dim"]


def _dense_bytes(config):
    """The matrices a call reads whatever was routed: once a call."""
    return BYTES * (config["num_hidden_layers"] * layer_dense_params(config)
                    + head_params(config))


def decode_tick(config, counts):
    """``calls`` decode ticks that advanced ``lane_steps`` streams by a
    token each; ``expert_rows`` pairs fell on held experts and hit them
    ``experts_hit`` times in all; attention could see ``kv_positions_live``
    positions (over lanes and layers).  A tick reads every dense matrix
    once, each hit expert's matrices once, the live keys and values once,
    and writes a row of them a lane-step a layer."""
    steps = counts["lane_steps"]
    layers = config["num_hidden_layers"]
    return {
        "flops": (2 * steps * (layers * layer_dense_params(config)
                               + head_params(config))
                  + 2 * counts["expert_rows"] * expert_params(config)
                  + _per_key_flops(config) * counts["kv_positions_live"]),
        "bytes": (counts["calls"] * _dense_bytes(config)
                  + counts["experts_hit"] * BYTES * expert_params(config)
                  + (counts["kv_positions_live"] + layers * steps)
                  * kv_row_bytes(config)),
    }


def chunk_keys(config, start, n):
    """(keys met in a window layer, keys met in a full layer) by the ``n``
    positions from ``start``: position p attends min(p + 1, window) and
    p + 1 keys."""
    w = config["sliding_window"]
    full = n * start + n * (n + 1) // 2
    capped = sum(min(p + 1, w) for p in range(start, start + n))
    return capped, full


def _keys_met(config, chunks):
    windowed, full = _windows(config)
    met = [chunk_keys(config, start, n) for start, n in chunks]
    return (windowed * sum(w for w, _ in met) + full * sum(f for _, f in met))


def prefill_chunk(config, counts):
    """``chunks``: (start, tokens) of each prefill chunk, real tokens only;
    ``expert_rows`` and ``experts_hit`` as the chunks' programs counted
    them.  Each token passes the layers; one position of a chunk passes the
    head.  Bytes: the dense matrices once a chunk, each hit expert's once,
    the keys and values before the chunk that a layer may see read once,
    and the chunk's own rows written."""
    chunks = counts["chunks"]
    windowed, full = _windows(config)
    layers = config["num_hidden_layers"]
    tokens = sum(n for _, n in chunks)
    w = config["sliding_window"]
    before = sum(full * start + windowed * min(start, w - 1)
                 for start, _ in chunks)
    return {
        "flops": (2 * tokens * layers * layer_dense_params(config)
                  + 2 * len(chunks) * head_params(config)
                  + 2 * counts["expert_rows"] * expert_params(config)
                  + _per_key_flops(config) * _keys_met(config, chunks)),
        "bytes": (len(chunks) * _dense_bytes(config)
                  + counts["experts_hit"] * BYTES * expert_params(config)
                  + (before + layers * tokens) * kv_row_bytes(config)),
    }


def tokens(config, counts):
    """The whole step's work for ``mfu``: every prompt token through the
    layers' dense matrices, every output token through those and the head,
    the routed pairs that fell on held experts in the window
    (``expert_rows``, from the engine's ticks), attention over the
    ``context_sum`` keys those tokens met in a full layer and the
    ``window_context_sum`` they met in a window layer."""
    done = counts["prompt_tokens"] + counts["output_tokens"]
    windowed, full = _windows(config)
    keys = (windowed * counts["window_context_sum"]
            + full * counts["context_sum"])
    return {
        "flops": (2 * done * config["num_hidden_layers"]
                  * layer_dense_params(config)
                  + 2 * counts["output_tokens"] * head_params(config)
                  + 2 * counts["expert_rows"] * expert_params(config)
                  + _per_key_flops(config) * keys),
        "bytes": 0,
    }
