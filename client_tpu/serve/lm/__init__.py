"""Continuous-batching LM serving subsystem.

The engine knows no model: a family's programs live in the family's own
module under ``serve/models`` (``transformer.py``, ``sambay.py``,
``cohere2moe.py``, ``axk1.py``, ``sdar.py``, ``longcat.py``) and its
configuration hands them out (``cfg.family``), with the pools a lane owns
(``cfg.state_spec``).
What the package holds:

- **prompt-length bucketing** (:mod:`.policy`) — prompts pad to a small
  geometric set of prefill widths so the compiled prefill-executable
  count is bounded by ``len(buckets)`` instead of growing with every
  novel prompt length;
- **chunked prefill** (:class:`.engine.LmEngine`) — prefill dispatches in
  fixed-width chunks interleaved 1:1 with decode ticks, so one novel
  long prompt can no longer freeze every active token stream for the
  length of its prefill (or its XLA compile);
- **paged KV cache** (:mod:`.kv`) — a block-table KV pool with
  fixed-size blocks and static shapes; HBM is pooled across lanes and
  requests reserve only the blocks their own ``prompt + max_tokens``
  needs, instead of every lane pinning ``max_seq`` rows forever;
- **lane autoscaling + per-tenant lane quotas** — the engine steps
  between a small precompiled set of decode lane counts on sustained
  queue depth, and admission is tenant-aware so one tenant cannot occupy
  every decode lane while another waits;
- **prefix cache** (:mod:`.prefix`) — a radix trie over token-block
  chains adopts cached full prompt-prefix blocks BY REFERENCE at
  admission (per-block refcounts in :mod:`.kv`), so chunked prefill
  starts at the first non-cached block; retiring requests hand their
  prompt blocks to the cache (LRU, evicted only under pool pressure);
- **preemption / swap** (:class:`.engine.LmEngine`) — under pool
  exhaustion with a strictly higher-priority tenant waiting, the
  lowest-priority lane swaps its KV to a bounded host-side store (or
  drops it for recompute), its stream pausing — not erroring — until
  blocks free up, byte-exact with an unpreempted run on the swap path;
- **speculative decoding** (:mod:`.spec`) — a drafter and an adaptive
  draft length a lane, verified by the family's verify program where it
  has one (a family without says why, and is refused);
- **the block tick** (:class:`.engine.LmEngine`) — a family may say that
  a lane's tick holds a BLOCK of positions (``block``; generation by
  diffusion over blocks, ``serve/models/sdar.py``): most of a lane's
  ticks then deliver nothing and do not advance it, one delivers the
  block's tokens at once, one advances the lane by the block; the family's
  static schedule (``advance``) tells the host which, so ticks are
  dispatched ahead as for a token a tick, and ``pass_trace()`` keeps the
  order in which a stream's positions were fixed.

Per-lane sampling (temperature / top-k via per-lane RNG keys inside the
jitted tick) removes the old "greedy only" limitation.
"""

from client_tpu.serve.lm.engine import LmEngine
from client_tpu.serve.lm.kv import KvBlockPool
from client_tpu.serve.lm.policy import (
    LaneAutoscaler,
    bucket_for,
    geometric_buckets,
    pad_prompt,
)
from client_tpu.serve.lm.prefix import PrefixCache
from client_tpu.serve.lm.runner import BatchedLmRunner

__all__ = [
    "BatchedLmRunner",
    "LmEngine",
    "KvBlockPool",
    "LaneAutoscaler",
    "PrefixCache",
    "bucket_for",
    "geometric_buckets",
    "pad_prompt",
]
