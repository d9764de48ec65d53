"""The yardstick's arithmetic for the ``longcat_flash`` family as one chip of
its deployment runs it (``configs/longcat-flash-omni-ep32-d4.json``): the
operations and bytes that each timed program needs, from shapes and from
what the routing and the lanes' lengths made of them, whatever implements
it.  Nothing here imports the program.  As in ``work.py``, every work
function takes the configuration and ``counts`` and returns ``{"flops":
..., "bytes": ...}``.

A double layer holds two attention sublayers of ``work_axk1``'s latent
attention (the same widths: its functions count them), two dense
feed-forwards, a router over the routed experts and the zero slots, and the
held experts.  What only the device knows comes in ``counts`` from the
program's own counters: ``expert_rows``, the (token, pick) pairs that fell
on held experts, and ``experts_hit``, the held experts with at least one
row, both summed over the layers.  FLOPs count 2 per weight element a row
meets: the attention sublayers' five products each, the dense
feed-forwards, the router, ONLY the routed pairs that fell here (a pick of
a zero slot adds its input times a weight: no product), the head.
Attention itself in the cheaper form for the program at hand, as
``work_axk1`` counts it, once for each of the ``2 x num_layers``
sublayers: a decode tick ABSORBED, a chunk EXPANDED with no rebuilding of
the keys and values before the chunk counted.  Bytes count every dense
matrix once a call, a routed expert's matrices once for each time it was
hit, and a live cache row ONCE a sublayer at the ``latent + rope`` values
it needs (1,152 B).
"""

from benchmark.work_axk1 import (
    BYTES, absorbed_key_flops, attention_params, chunk_pairs,
    expanded_pair_flops, latent_row_bytes)


def sublayers(config):
    """The attention sublayers that are run, each with its latent pool."""
    return 2 * config["num_layers"]


def router_params(config):
    """The router over every routed expert of the deployment and the zero
    slots."""
    return config["hidden_size"] * (config["deployment"]["router_experts"]
                                    + config["zero_expert_num"])


def expert_params(config):
    """One routed expert: gate, up and down."""
    return 3 * config["hidden_size"] * config["expert_ffn_hidden_size"]


def dense_ffn_params(config):
    """One of a double layer's two dense feed-forwards."""
    return 3 * config["hidden_size"] * config["ffn_hidden_size"]


def layer_dense_params(config):
    """What every row meets in a double layer: two attention sublayers, two
    dense feed-forwards, the router."""
    return (2 * attention_params(config) + 2 * dense_ffn_params(config)
            + router_params(config))


def layers_dense_params(config):
    return config["num_layers"] * layer_dense_params(config)


def head_params(config):
    """The untied head over the held rows (the embedding is a look-up)."""
    return config["hidden_size"] * config["vocab_size"]


def held_params(config):
    """Every matrix parameter this chip holds, the embedding among them."""
    return (layers_dense_params(config) + config["num_layers"]
            * config["n_routed_experts"] * expert_params(config)
            + 2 * head_params(config))


def _dense_bytes(config):
    """The matrices a call reads whatever was routed: once a call."""
    return BYTES * (layers_dense_params(config) + head_params(config))


def decode_tick(config, counts):
    """``calls`` decode ticks that advanced ``lane_steps`` streams by a
    token each; ``expert_rows`` pairs fell on held experts and hit them
    ``experts_hit`` times in all; attention could see ``kv_positions_live``
    cache rows (over lanes and sublayers).  A tick reads every dense matrix
    and the head once, each hit expert's matrices once, the live rows
    once, and writes a row a lane-step a sublayer."""
    steps, rows = counts["lane_steps"], counts["kv_positions_live"]
    return {
        "flops": (2 * steps * (layers_dense_params(config)
                               + head_params(config))
                  + 2 * counts["expert_rows"] * expert_params(config)
                  + absorbed_key_flops(config) * rows),
        "bytes": (counts["calls"] * _dense_bytes(config)
                  + counts["experts_hit"] * BYTES * expert_params(config)
                  + latent_row_bytes(config)
                  * (rows + sublayers(config) * steps)),
    }


def prefill_chunk(config, counts):
    """``chunks``: (start, tokens) of each prefill chunk, real tokens only;
    ``expert_rows`` and ``experts_hit`` as the chunks' programs counted
    them.  Each token passes the layers; one position of a chunk passes the
    head.  Bytes: the dense matrices once a chunk, each hit expert's once,
    the rows before the chunk read once a sublayer, the chunk's own
    written."""
    chunks = counts["chunks"]
    tokens = sum(n for _, n in chunks)
    pairs = sum(chunk_pairs(start, n) for start, n in chunks)
    before = sum(start for start, _ in chunks)
    return {
        "flops": (2 * tokens * layers_dense_params(config)
                  + 2 * len(chunks) * head_params(config)
                  + 2 * counts["expert_rows"] * expert_params(config)
                  + expanded_pair_flops(config) * sublayers(config) * pairs),
        "bytes": (len(chunks) * _dense_bytes(config)
                  + counts["experts_hit"] * BYTES * expert_params(config)
                  + sublayers(config) * (before + tokens)
                  * latent_row_bytes(config)),
    }


def tokens(config, counts):
    """The whole step's work for ``mfu``: every prompt token through the
    layers' dense matrices, every output token through those and the head,
    the routed pairs that fell on held experts in the window
    (``expert_rows``, from the engine's ticks), attention over the keys the
    prompts' positions met in the expanded form (``context_sum`` less
    ``decode_context_sum``) and over the rows the decoded tokens met in the
    absorbed form, in every sublayer."""
    done = counts["prompt_tokens"] + counts["output_tokens"]
    decoded = counts["decode_context_sum"]
    return {
        "flops": (2 * done * layers_dense_params(config)
                  + 2 * counts["output_tokens"] * head_params(config)
                  + 2 * counts["expert_rows"] * expert_params(config)
                  + sublayers(config)
                  * (expanded_pair_flops(config)
                     * (counts["context_sum"] - decoded)
                     + absorbed_key_flops(config) * decoded)),
        "bytes": 0,
    }
