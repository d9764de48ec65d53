"""The one traffic generator.  A traffic mix is the ``traffic`` object of a
``workloads/<cell>.json``: numbers only.  numpy and the standard library
alone, so that a client process can import it without loading JAX.

Every seed gets the same sizes in the same order: the sizes of a block are
fixed points of the mix's distributions, each block is shuffled by its
number alone, and the seed draws the token ids (and, in the drivers, the
weights).  The order is not the seed's because in a closed loop the order
decides which prompts meet, and with some fifty long requests in a window
that alone moved the first tokens' 95th percentile by 13% from seed to seed
while two runs of one seed agreed to 0.2% (PERF.md).
"""

import numpy as np


def _quantile(dist, u):
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        return lo + (hi - lo) * u
    if dist["dist"] == "log_uniform":
        return lo * (hi / lo) ** u
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def size_block(traffic):
    """The block of (prompt tokens, output tokens) pairs that every seed
    serves: ``block`` evenly spaced quantiles of each distribution, paired by
    a fixed permutation so that long prompts meet short and long outputs."""
    n = traffic["block"]
    u = (np.arange(n) + 0.5) / n
    prompts = np.rint(_quantile(traffic["prompt_tokens"], u)).astype(int)
    outputs = np.rint(_quantile(traffic["output_tokens"], u)).astype(int)
    pairing = np.random.default_rng(n).permutation(n)
    return list(zip(prompts.tolist(), outputs[pairing].tolist()))


def request_sizes(traffic, seed, index):
    """(prompt tokens, output tokens) of request ``index``, the same for every
    seed (see the top of the file; ``seed`` stays in the signature for a mix
    that has cause to use it)."""
    block = size_block(traffic)
    which, at = divmod(index, len(block))
    order = np.random.default_rng([len(block), 1, which]).permutation(len(block))
    return block[order[at]]


def prompt_tokens(traffic, seed, index, length, vocab_size):
    """Token ids of request ``index``: uniform over the vocabulary, behind
    ``shared_prefix_tokens`` ids that every request of the seed shares."""
    shared = min(int(traffic.get("shared_prefix_tokens", 0)), length - 1)
    head = np.random.default_rng([seed, 2]).integers(0, vocab_size, shared)
    tail = np.random.default_rng([seed, 3, index]).integers(
        0, vocab_size, length - shared)
    return np.concatenate([head, tail]).astype(np.int32)


def percentile(values, q):
    """The q-th percentile, by the nearest rank at or above it."""
    ordered = sorted(values)
    rank = max(int(np.ceil(q / 100.0 * len(ordered))) - 1, 0)
    return float(ordered[rank])
