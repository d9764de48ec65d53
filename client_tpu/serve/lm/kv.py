"""Paged KV cache: a refcounted block-table pool shared by every decode
lane (and, since the prefix cache, by every REQUEST that shares a prompt
prefix).

The fixed-lane prototype allocated per layer ``[lanes, max_seq, kv, hd]``
— every lane pinned max-seq-len rows of HBM whether it held a 5-token
request, a 500-token one, or nothing.  The pool here is per layer
``[n_blocks, kv, block_size, hd]`` (a block's heads outside its positions,
so that each head's rows lie together and ``ops/paged_decode`` reads a
block where it lies: every family's ``state_spec`` says so) with a
host-side free list: a request
reserves exactly ``ceil((prompt_len + max_tokens) / block_size)`` blocks
at admission and frees them at completion/cancel, so HBM capacity is a
function of *aggregate live tokens*, not ``lanes * max_seq``.

Blocks carry a REFERENCE COUNT: ``alloc`` hands out blocks at refcount 1,
``retain`` adds a reference (a second request adopting a shared prompt-
prefix block, or the prefix cache keeping a retired request's blocks
warm), and ``release`` decrements — a block returns to the free list only
when its last reference drops.  That is what makes block-granular KV
sharing safe: an 80%-shared prompt adopts its prefix blocks by reference
instead of recomputing them, and nobody can free a block out from under
another holder.

LAYER KINDS: the pool asks the configuration what a lane owns
(``cfg.state_spec``).  A decoder of identical layers pages every layer;
a family with other kinds of layer (``models/sambay.py``) says how many
layers page their K/V (one block table a lane serves them all) and which
FIXED per-lane state lives beside the pool — a ring of ``window``
positions for a window-attention layer, a conv tail and an SSM state for
a recurrent one — so a window layer never pages the whole context and a
recurrent layer pages nothing.  ``lane_state`` holds those arrays,
``[lanes, ...]`` each, allocated here with the pool and donated to the
jitted programs like it.

Static shapes throughout (TPU-first): the device arrays never change
shape; splice/free are index bookkeeping on the host plus
scatter/gather through per-lane block tables inside the jitted programs.
Block 0 is reserved as the *trash block*: idle lanes and write-masked
pad positions scatter there, so the jitted tick needs no per-lane
branch.  Nothing ever reads it (the length mask excludes every position
a table maps to trash).
"""

import threading

import jax.numpy as jnp

_KV_HELP = {
    "ctpu_lm_kv_blocks_used": "Paged-KV blocks currently referenced",
    "ctpu_lm_kv_blocks_free": "Paged-KV blocks free in the pool",
    "ctpu_lm_state_bytes":
        "Fixed per-lane state allocated beside the paged pool (window "
        "rings, recurrent state), bytes over all lanes",
}


class KvBlockPool:
    """Device block pool + host free-list/refcount accounting.

    ``n_blocks`` counts usable blocks; one extra trash block (index 0) is
    allocated on top, so the device arrays hold ``n_blocks + 1`` blocks.
    ``lanes`` sizes the fixed per-lane state of configurations that have
    any (``lane_state``; empty for a decoder of identical layers).
    """

    TRASH = 0

    def __init__(self, cfg, n_blocks, block_size, registry=None, lanes=0):
        if block_size <= 0 or n_blocks <= 0:
            raise ValueError("block_size and n_blocks must be positive")
        self.cfg = cfg
        self.block_size = int(block_size)
        self.n_blocks = int(n_blocks)
        self.registry = registry
        paged_layers, block, lane_spec = cfg.state_spec
        shape = (self.n_blocks + 1,) + tuple(
            self.block_size if d is None else d for d in block)
        self.pools = {
            "k": [jnp.zeros(shape, cfg.jdtype) for _ in range(paged_layers)],
            "v": [jnp.zeros(shape, cfg.jdtype) for _ in range(paged_layers)],
        }
        self.lane_state = {
            name: [jnp.zeros((int(lanes),) + tuple(shp), dtype)
                   for shp, dtype in layers]
            for name, layers in lane_spec.items()
        }
        self.state_bytes = sum(
            a.size * a.dtype.itemsize
            for layers in self.lane_state.values() for a in layers
        )
        self._lock = threading.Lock()
        self._free = list(range(1, self.n_blocks + 1))
        self._refs = {}  # block -> live reference count (absent = free)

    def blocks_for(self, n_tokens):
        """Blocks a sequence of ``n_tokens`` total (prompt + generation
        budget) reserves."""
        return -(-int(n_tokens) // self.block_size)

    def alloc(self, n):
        """Reserve ``n`` blocks at refcount 1; returns the block index
        list or None when the pool cannot satisfy the reservation
        (admission backpressure — the caller evicts cache blocks,
        preempts a lane, or retries once completions free blocks)."""
        n = int(n)
        with self._lock:
            if n > len(self._free):
                return None
            taken = self._free[:n]
            del self._free[:n]
            for block in taken:
                self._refs[block] = 1
            self._gauges_locked()
            return taken

    def retain(self, blocks):
        """Add one reference to each block (prefix-cache adoption; the
        block must already be live — retaining a freed block is a bug)."""
        with self._lock:
            for block in blocks:
                self._refs[block] += 1

    def release(self, blocks):
        """Drop one reference from each block; blocks whose last
        reference drops return to the free list.  Every ``alloc``/
        ``retain`` must be paired with exactly one release — the
        REFCOUNT-PAIR lint rule guards the shape (a leaked reference
        bricks the pool: the block is never free and never readable)."""
        if not blocks:
            return
        with self._lock:
            for block in blocks:
                left = self._refs[block] - 1
                if left > 0:
                    self._refs[block] = left
                else:
                    del self._refs[block]
                    self._free.append(block)
            self._gauges_locked()

    def ref_count(self, block):
        """Live reference count of one block (0 = free)."""
        with self._lock:
            return self._refs.get(block, 0)

    def ref_counts(self):
        """{block: refcount} snapshot of every live block (leak audits)."""
        with self._lock:
            return dict(self._refs)

    @property
    def free_blocks(self):
        with self._lock:
            return len(self._free)

    @property
    def used_blocks(self):
        with self._lock:
            return self.n_blocks - len(self._free)

    def _gauges_locked(self):
        if self.registry is None:
            return
        free = len(self._free)
        self.registry.set("ctpu_lm_kv_blocks_used", None,
                          self.n_blocks - free,
                          help_=_KV_HELP["ctpu_lm_kv_blocks_used"])
        self.registry.set("ctpu_lm_kv_blocks_free", None, free,
                          help_=_KV_HELP["ctpu_lm_kv_blocks_free"])
        self.registry.set("ctpu_lm_state_bytes", None, self.state_bytes,
                          help_=_KV_HELP["ctpu_lm_state_bytes"])

    def set_registry(self, registry):
        """Late-bind a metrics registry (the engine learns its server's
        registry at add_model time) and publish the current gauges."""
        with self._lock:
            self.registry = registry
            self._gauges_locked()
