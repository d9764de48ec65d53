"""The yardstick's arithmetic for the ``sdar_moe`` family as the first stage
of its pipeline runs it (``configs/sdar-30b-a3b-chat-d7.json``): the
operations and bytes that each timed program needs, from shapes and from
what the schedule, the routing and the lanes' lengths made of them, whatever
implements it.  Nothing here imports the program.  As in ``work.py``, every
work function takes the configuration and ``counts`` and returns ``{"flops":
..., "bytes": ...}``.

Generation by diffusion over blocks: a lane's pass holds a block of ``B``
positions.  What the MATHEMATICS needs of a pass: every row of the block
through the layers (attention, router, its picked experts); logits at the
positions that are still MASKED, and only in a denoising pass (a commit pass
needs none: it stores the finished block's keys and values); attention of
each row over the lane's ``length + B`` positions.  FLOPs count 2 per weight
element a row meets.  Bytes count the attention and router matrices once a
call, the head once a call that has a masked row, a routed expert's matrices
once for each time it was hit (``experts_hit``, from the program's own
counter, as ``expert_rows`` is), and keys and values by the positions
attention may see (``kv_positions_live``) and the rows written.
"""

BYTES = 2          # bf16 weights, keys and values


def _sizes(config):
    hd = config["head_dim"]
    return (config["hidden_size"], config["moe_intermediate_size"],
            config["num_attention_heads"] * hd,
            config["num_key_value_heads"] * hd)


def block_length(config):
    return config["assumed"]["block_length"]


def fixed_a_pass(config):
    return block_length(config) // config["assumed"]["denoising_steps"]


def attention_params(config):
    d, _, q_out, kv_out = _sizes(config)
    return d * (q_out + 2 * kv_out) + q_out * d


def router_params(config):
    return config["hidden_size"] * config["num_experts"]


def expert_params(config):
    """One expert: gate, up and down."""
    d, ff, _, _ = _sizes(config)
    return 3 * d * ff


def layer_dense_params(config):
    """What every row of a layer meets: attention and the router."""
    return attention_params(config) + router_params(config)


def head_params(config):
    """The untied head (the embedding is as large, and a look-up)."""
    return config["hidden_size"] * config["vocab_size"]


def held_params(config):
    """Every matrix parameter this chip holds."""
    return (config["num_hidden_layers"] * (
        layer_dense_params(config)
        + config["num_experts"] * expert_params(config))
        + 2 * head_params(config))


def kv_row_bytes(config):
    """Keys and values of one position in one layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BYTES


def _per_key_flops(config):
    # a key costs a query head 2 hd for its score and 2 hd for its share of
    # the weighted sum
    return 4 * config["num_attention_heads"] * config["head_dim"]


def block_tick(config, counts):
    """``calls`` ticks whose lanes' passes held ``block_rows`` positions,
    ``masked_rows`` of them masked in a denoising pass; ``expert_rows``
    pairs hit experts ``experts_hit`` times in all; a lane's rows could see
    ``kv_positions_live`` positions (over lanes and layers), each of its B
    rows every one of them.  A tick reads every dense matrix once, the head
    once (every tick of a busy engine has a masked row: a tick of commit
    passes alone is counted as if it had), each hit expert's matrices once,
    the live keys and values once, and writes a row of them a position a
    layer."""
    rows, layers = counts["block_rows"], config["num_hidden_layers"]
    live = counts["kv_positions_live"]
    head = head_params(config) if counts["masked_rows"] else 0
    return {
        "flops": (2 * rows * layers * layer_dense_params(config)
                  + 2 * counts["masked_rows"] * head_params(config)
                  + 2 * counts["expert_rows"] * expert_params(config)
                  + _per_key_flops(config) * block_length(config) * live),
        "bytes": (counts["calls"] * BYTES * (
                      layers * layer_dense_params(config) + head)
                  + counts["experts_hit"] * BYTES * expert_params(config)
                  + (live + layers * rows) * kv_row_bytes(config)),
    }


def keys_met(config, start, n):
    """Keys the ``n`` positions from ``start`` meet under block-causal
    attention: position p sees its own block whole and every block before
    it, ``(p // B + 1) * B`` keys."""
    b = block_length(config)
    return sum((p // b + 1) * b for p in range(start, start + n))


def prefill_chunk(config, counts):
    """``chunks``: (start, tokens) of each prefill chunk, real tokens only;
    ``expert_rows`` and ``experts_hit`` as the chunks' programs counted
    them.  Each token passes the layers; NO position passes the head (the
    prompt's positions predict nothing that generation uses).  Bytes: the
    dense matrices once a chunk, each hit expert's once, the keys and values
    before the chunk read once, and the chunk's own rows written."""
    chunks = counts["chunks"]
    layers = config["num_hidden_layers"]
    tokens = sum(n for _, n in chunks)
    before = sum(start for start, _ in chunks)
    met = sum(keys_met(config, start, n) for start, n in chunks)
    return {
        "flops": (2 * tokens * layers * layer_dense_params(config)
                  + 2 * counts["expert_rows"] * expert_params(config)
                  + _per_key_flops(config) * layers * met),
        "bytes": (len(chunks) * BYTES * layers * layer_dense_params(config)
                  + counts["experts_hit"] * BYTES * expert_params(config)
                  + layers * (before + tokens) * kv_row_bytes(config)),
    }


def stream_blocks(config, prompt_len, max_tokens):
    """The generated blocks of a stream, in order: ``(first position, new
    tokens delivered, rows through the layers, rows through the head, keys
    its rows met in a layer)``, from the static schedule alone: a block
    whose first ``known`` positions are the prompt's tail has ``masks = B -
    known`` masked positions, takes ``ceil(masks / fixed_a_pass)`` denoising
    passes (each of B rows, logits at the positions still masked) and one
    commit pass, which the stream's last block does not get."""
    b, fix = block_length(config), fixed_a_pass(config)
    end = prompt_len + max_tokens
    out = []
    for start in range(prompt_len // b * b, end, b):
        masks = b - max(prompt_len - start, 0)
        left = list(range(masks, 0, -fix))          # masked at each pass
        passes = len(left) + (start + b < end)
        out.append((start, min(start + b, end) - max(start, prompt_len),
                    b * passes, sum(left), b * passes * (start + b)))
    return out


def tokens(config, counts):
    """The whole step's work for ``mfu``: ``stored_tokens`` prompt positions
    through the layers' dense matrices (no head), ``pass_rows`` rows of
    block passes through those and ``head_rows`` of them through the head
    (``stream_blocks``, summed over the blocks delivered in the window), the
    routed pairs in the window (``expert_rows``, from the engine's ticks),
    attention over the ``prefill_keys`` and ``pass_keys`` those rows met in
    a layer."""
    layers = config["num_hidden_layers"]
    rows = counts["stored_tokens"] + counts["pass_rows"]
    return {
        "flops": (2 * rows * layers * layer_dense_params(config)
                  + 2 * counts["head_rows"] * head_params(config)
                  + 2 * counts["expert_rows"] * expert_params(config)
                  + _per_key_flops(config) * layers
                  * (counts["prefill_keys"] + counts["pass_keys"])),
        "bytes": 0,
    }
