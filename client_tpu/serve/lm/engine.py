"""Continuous-batching LM engine: bucketed chunked prefill over a paged
KV pool, prefix-cache block sharing, priority preemption with host-side
swap, lane autoscaling, per-lane sampling, tenant-aware admission.

Scheduling model (one scheduler thread, every device dispatch outside
the condition lock — the LOCK-DISPATCH/BLOCK-UNDER-LOCK invariant the
lint gate enforces):

Each scheduler pass runs AT MOST one prefill chunk and then one decode
tick.  That 1:1 interleave is the head-of-line fix: a novel max-length
prompt used to run its whole prefill (and, for a novel length, a full
XLA compile) between decode ticks, stalling every active token stream;
now the stall per pass is bounded by one fixed-width chunk whose shape
comes from a small geometric bucket set (``policy.chunk_plan``), so the
compile set is bounded too.

The host runs ahead of the device: a pass dispatches, then reads back
the oldest dispatched results only as far as the device time still
queued behind them covers the host's own next pass
(``_drain_ahead``).  Each dispatched tick and chunk is priced at the
running median of its kind's and width's device times, and the cover
is the host's recent pass time (everything but its waits on the
device) times a margin.  A request admitted now therefore starts its
first chunk behind about that much device work, not behind a fixed
count of ticks.  ``readback_depth`` caps the results in flight (0:
every pass reads back all it dispatched); until a kind's device times
have landed, the cap alone decides.

Static shapes everywhere (TPU-first):

- decode ticks run at one of a few precompiled lane counts
  (``lane_counts``), stepped by :class:`policy.LaneAutoscaler` on
  sustained queue depth — one executable per count, ever;
- the KV cache is a paged block pool (:class:`kv.KvBlockPool`): per-lane
  block tables gather the logical cache inside the jitted programs, and
  the new token's K/V scatters to ``(table[pos // bs], pos % bs)``.
  Idle lanes and write-masked pad positions scatter to the reserved
  trash block, which the length mask guarantees is never read;
- sampling happens inside the jitted tick with per-lane RNG keys,
  temperatures and top-k — greedy lanes (temperature 0) take the
  on-device argmax, so mixed greedy/sampled batches share one program.

Prefix cache (serve/lm/prefix.py): admission walks the prompt's full
token blocks through a radix trie and ADOPTS every cached match by
reference (per-block refcounts in kv.py), so chunked prefill starts at
the first miss; retiring requests hand their full prompt blocks to the
cache instead of freeing them, and the cache yields blocks back (LRU,
leaves first) only under pool pressure.

Preemption: when the pool is exhausted and a strictly higher-priority
tenant (TenantQoS priority classes via the ``tenant_priority`` hook) is
waiting, the lowest-priority active lane is swapped out — its written
KV blocks copied to a bounded host-side store (or, past the swap
budget, dropped for recompute), its stream PAUSED (no CLOSE, no error)
— and swapped back in once blocks free up, byte-exact with an
unpreempted run on the swap path.

Safety of block recycling: device dispatches from the scheduler thread
execute in dispatch order on one stream, so a stale in-flight tick's
scatter into a freed block always lands before the block's next owner
writes (and every position the next owner ever *reads* is one its own
later dispatches wrote).  Cached blocks extend the argument: no program
ever WRITES a cached block — decode writes at ``pos >= prompt_len`` and
prefill writes at ``pos >= adopted_start``, both past the full-prompt-
block region the cache holds — so adopting one is a pure read of
content whose producing dispatch already ordered before the adopter's.
"""

import functools
import queue
import statistics
import threading
import time
from collections import OrderedDict, deque

import numpy as np

import jax
import jax.numpy as jnp

from client_tpu.serve._completion import CompletionObserver
from client_tpu.serve.lm.kv import KvBlockPool
from client_tpu.serve.lm.policy import (
    LaneAutoscaler,
    bucket_for,
    chunk_plan,
    geometric_buckets,
    pad_prompt,
    verify_widths,
)
from client_tpu.serve.lm.prefix import PrefixCache
from client_tpu.serve.lm.spec import LaneSpec, SpecConfig
from client_tpu.serve.metrics import FLEET_HELP, LM_PREFIX_HELP, LM_SPEC_HELP
from client_tpu.serve.prof import NULL_PHASE, NULL_TICK, PhaseProfiler

# sentinel object closing a stream's token queue
_CLOSE = object()

# How far the host dispatches ahead (``_drain_ahead``).  A tick or chunk
# is priced at the median of the last AHEAD_PRICE_OVER device times of
# its kind and width, once AHEAD_PRICE_NEEDS of them have landed: a
# shape's first device time can hold its compile.
AHEAD_PRICE_OVER, AHEAD_PRICE_NEEDS = 32, 3
# The host's time for a pass is taken over the last AHEAD_COVER_OVER
# passes that dispatched work, once AHEAD_COVER_NEEDS have, at the
# quantile AHEAD_COVER_AT: a chunk's pass, which comes every few passes,
# sets it, and a few interpreter pauses in the window do not (a pause
# of 60-140 ms costs the device the time the queue does not cover,
# where eight ticks in flight hid it).
AHEAD_COVER_OVER, AHEAD_COVER_NEEDS, AHEAD_COVER_AT = 64, 8, 0.9
# The queue kept is that time AHEAD_MARGIN times over, for the passes
# slower than the quantile.
AHEAD_MARGIN = 2.0

# placed-marker for a handle cancelled while its prefill job was in
# flight (chunks dispatch outside _cv); the job step sees it, frees the
# reservation and closes the queue
_CANCELLED = object()

_LANE_HELP = {
    "ctpu_lm_lanes": "Configured decode lane count (autoscaled)",
    "ctpu_lm_active_lanes": "Decode lanes currently streaming",
    "ctpu_lm_attended_positions": (
        "Cache positions a lane that the last tick's or chunk's attention "
        "read (of max_seq: the table width its longest lane reached, or "
        "that lane's own read where a tick reads each lane to its length)"
    ),
    "ctpu_lm_ahead_drains_total": (
        "Scheduler passes whose read-back went below readback_depth "
        "because the device time queued ahead covered the host"
    ),
    "ctpu_lm_ahead_seconds": (
        "Priced device time dispatched and not yet read back, after the "
        "last pass's read-back"
    ),
}


def _price_key(entry):
    """What a tick_trace() entry's device time is priced by: its kind and
    a chunk's width or a tick's lane count."""
    return entry["kind"], entry.get("width", entry["n_lanes"])


def _adopt(tokens, keys, slot, tok, key):
    """Install an admitted request's first token + RNG carry into lane
    ``slot`` (traced index: one executable regardless of slot)."""
    return tokens.at[slot].set(tok), keys.at[slot].set(key)


class _Lane:
    __slots__ = ("gen", "active", "queue", "remaining", "produced",
                 "length", "limit", "tenant", "temperature", "top_k",
                 "table", "blocks", "prompt", "tokens", "handle", "spec",
                 "masks", "fixed")

    def __init__(self, table_width):
        self.gen = 0        # bumped on every (re)assignment and cancel
        self.active = False
        self.queue = None
        self.remaining = 0
        self.produced = 0
        self.length = 0     # logical sequence length (next write position)
        self.limit = 0      # prompt_len + max_tokens: last writable pos + 1
        self.tenant = ""
        self.temperature = 0.0
        self.top_k = 0
        self.table = np.zeros((table_width,), np.int32)  # trash-filled
        self.blocks = None  # reservation owned while active
        self.prompt = None  # [1, T] prompt row (prefix-cache insertion)
        self.tokens = []    # delivered generation tokens (recompute replay)
        self.handle = None  # the submit() handle streaming on this lane
        self.spec = None    # LaneSpec when speculative decoding is on
        # a family whose tick holds a block of positions a lane: the masked
        # positions its block at ``length`` still holds, and for each
        # delivered token the state its block was in when it was fixed
        self.masks = 0
        self.fixed = []


class _Handle:
    """Opaque submit() handle; ``placed`` is None (pending / mid-prefill),
    _CANCELLED, or (slot, gen) once streaming."""

    __slots__ = ("prompt", "prompt_len", "max_tokens", "queue", "tenant",
                 "temperature", "top_k", "seed", "placed", "remote_kv",
                 "t_submit", "t_admit")

    def __init__(self, prompt, max_tokens, q, tenant, temperature, top_k,
                 seed):
        self.prompt = prompt
        self.prompt_len = int(prompt.shape[1])
        self.max_tokens = int(max_tokens)
        self.queue = q
        self.tenant = tenant
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self.placed = None
        # (covered_blocks, host pools, start) fetched from the fleet prefix
        # tier on the submit caller's thread (never under _cv); _admit
        # adopts whatever still beats the local trie at admission time
        self.remote_kv = None
        # time.monotonic() at submit() and at admission: the tick_trace()
        # entry of the chunk that ends the prompt carries them beside the
        # first token's completion and delivery
        self.t_submit = time.monotonic()
        self.t_admit = None


class _PrefillJob:
    __slots__ = ("handle", "slot", "blocks", "table", "plan", "chunk_idx",
                 "key", "token", "resume", "remote")

    def __init__(self, handle, slot, blocks, table, plan, key):
        self.handle = handle
        self.slot = slot
        self.blocks = blocks
        self.table = table
        self.plan = plan
        self.chunk_idx = 0
        self.key = key
        self.token = None
        # _Swapped being resumed via the recompute path (None for normal
        # admissions): activation restores its produced/remaining state
        # and the saved token/RNG carry instead of the chunk's sample
        self.resume = None
        # [lo, hi, host pools, rstart]: fleet-fetched KV content destined for
        # blocks[lo:hi]; installed by the FIRST _prefill_step (outside
        # _cv), cleared once on device — abort before install must not
        # cache those blocks as valid content
        self.remote = None


class _Swapped:
    """One preempted stream parked off-device.

    ``host`` holds the lane's written blocks, every pool's a layer
    (``KvBlockPool.read_blocks``), when
    the swap fit the host budget; None means the recompute path (replay
    prompt + delivered tokens through chunked prefill on resume).  The
    stream's queue is PAUSED — no CLOSE, no error — until resume or
    cancel."""

    __slots__ = ("handle", "queue", "tenant", "prompt", "prompt_len",
                 "produced", "remaining", "length", "limit", "temperature",
                 "top_k", "tokens", "token", "key", "host",
                 "n_blocks", "written_blocks", "cancelled", "t_swap",
                 "masks", "fixed")

    def __init__(self, lane, n_blocks, written_blocks, token, key, host):
        self.handle = lane.handle
        self.queue = lane.queue
        self.tenant = lane.tenant
        self.prompt = lane.prompt
        self.prompt_len = int(lane.prompt.shape[1])
        self.produced = lane.produced
        self.remaining = lane.remaining
        self.length = lane.length
        self.limit = lane.limit
        self.temperature = lane.temperature
        self.top_k = lane.top_k
        self.tokens = list(lane.tokens)
        self.masks = lane.masks
        self.fixed = list(lane.fixed)
        self.token = token          # the lane array's row: the next input
        self.key = key              # RNG carry at the preemption point
        self.host = host
        self.n_blocks = int(n_blocks)
        self.written_blocks = int(written_blocks)
        self.cancelled = False
        self.t_swap = time.monotonic()


class LmEngine:
    """Continuous-batching decode engine.

    ``submit(prompt_tokens, max_tokens, temperature=0, top_k=0, seed=0,
    tenant="")`` returns ``(queue, handle)``; the queue yields int token
    ids and finally :data:`CLOSE`.  ``cancel(handle)`` releases a stream
    early.  Device state (KV pool, lane arrays, scheduler thread)
    allocates lazily on the first submit so an idle engine pins no HBM.

    ``readback_depth`` is the most dispatched results the scheduler
    keeps in flight, not the depth it runs at: it reads back further
    while the priced device time queued behind the oldest result still
    covers its own next pass (module docstring), and 0 reads back
    everything every pass (serial).
    """

    CLOSE = _CLOSE

    def __init__(self, params, cfg, max_slots=8, lane_counts=None,
                 block_size=16, pool_tokens=None, prefill_chunk=None,
                 min_bucket=16, readback_depth=8, eos_id=None,
                 check_prompt=None, registry=None,
                 tenant_lane_share=0.75, scale_up_after=3,
                 scale_down_after=50, tick_log_len=8192, pass_log_len=256,
                 prefix_cache=True, min_prefix_blocks=1,
                 tenant_priority=None, swap_block_limit=None, fleet=None,
                 speculative=None):
        self.params = params
        self.cfg = cfg
        self.max_slots = int(max_slots)
        if lane_counts is None:
            lane_counts = sorted({
                max(1, self.max_slots // 4),
                max(1, self.max_slots // 2),
                self.max_slots,
            })
        self.lane_counts = tuple(sorted(set(int(c) for c in lane_counts)))
        if self.lane_counts[-1] != self.max_slots:
            raise ValueError("largest lane count must equal max_slots")
        self.depth = max(int(readback_depth), 0)
        self.eos_id = eos_id
        self.check_prompt = check_prompt  # optional prompt validator
        self.registry = registry
        # flight recorder (serve/flight.py; bound by the model binder):
        # preemptions and a wedged scheduler loop land in the server's
        # postmortem ring — a wedge also dumps it automatically
        self.flight = None
        self.tenant_lane_share = tenant_lane_share
        self.block_size = int(block_size)
        chunk = int(prefill_chunk or min(64, cfg.max_seq))
        self.buckets = geometric_buckets(
            min(min_bucket, chunk), min(chunk, cfg.max_seq)
        )
        self._table_width = -(-cfg.max_seq // self.block_size)
        self._pool_tokens = int(pool_tokens or self.max_slots * cfg.max_seq)

        self._cv = threading.Condition()
        self._closed = False
        self._lanes = [
            _Lane(self._table_width) for _ in range(self.max_slots)
        ]
        self._pending = OrderedDict()  # tenant -> deque[_Handle]
        self._rr = 0                   # round-robin cursor over tenants
        self._job = None
        self._scaler = LaneAutoscaler(
            self.lane_counts, up_after=scale_up_after,
            down_after=scale_down_after,
        )
        self._tick_log = deque(maxlen=int(tick_log_len))
        self._pass_log = deque(maxlen=int(pass_log_len))  # pass_trace()
        # dispatched, not yet read back: (tokens, lanes' (slot, gen), the
        # tick_trace() entry if these are a prompt's first token, a block
        # family's takes, the priced device time the readback completes:
        # its program's and that of any chunk dispatched since the entry
        # before it; None where a price was not known yet)
        self._inflight = deque()
        # priced device time of chunks dispatched since the last entry of
        # _inflight (None: one was unpriced); the scheduler thread's
        self._loose_s = 0.0
        # (kind, width or lanes) -> its last device times, from the
        # completion observer's thread (_cv)
        self._device_times = {}
        # the host's own seconds for each recent pass that dispatched
        # work (the scheduler thread's), and the current pass's seconds
        # blocked on the device
        self._host_s = deque(maxlen=AHEAD_COVER_OVER)
        self._waited_s = 0.0
        self._thread = None  # started lazily on the first submit
        # every tick's result is watched to completion: its instant gives
        # the tick's device time (serve/_completion.py)
        self._observer = CompletionObserver(name="lm-engine-watch")
        self._device_s = 0.0  # completed, not yet in the profiler (_cv)
        self._t_done_prev = None  # the observer's thread's: see _tick_done

        # continuous profiler (serve/prof.py): each scheduler pass is one
        # tick with schedule/dispatch/device-wait/delivery phase spans;
        # the model binder rebinds the registry and adopts this profiler
        # into the server's, so /v2/debug/prof and flight dumps cover
        # the LM engine too.  _ptick is the scheduler thread's current
        # tick — only that thread ever touches it.
        self.prof = PhaseProfiler(name="lm", registry=registry)
        self._ptick = NULL_TICK
        # the family's programs, which its configuration hands out (as it
        # does ``state_spec``): the engine knows no model.  Where its
        # lanes carry state that blocks do not rebuild, what assumes a
        # lane IS its blocks is switched off here, with the reason in the
        # stats: prefix adoption and fleet export (a block chain without
        # the state at its end is no cache) and the host swap (preemption
        # falls back to recompute-replay, which rebuilds the state).
        # Speculative decoding needs the family's verify program
        # (``make_verify``): a family without one is refused here, for its
        # own reason (a recurrent state has no pointer to rewind; another
        # family says why in ``no_verify``), so that no family can reach
        # ``_verify_for`` without a program.
        self._programs = cfg.family(cfg, self.block_size)
        # positions a lane's tick holds: one, and the tick yields that
        # position's token; or a family's block of them (``block``), whose
        # passes the family's static schedule tells apart (``advance``):
        # most deliver nothing and do not advance the lane, one delivers
        # the block's tokens at once, one advances the lane by the block.
        # A chunk's edge is then a block's edge, and so is a pool block's.
        self._block = getattr(self._programs, "block", 1)
        if any(width % self._block for width in self.buckets):
            raise ValueError(
                f"prefill widths {self.buckets} are not whole blocks of "
                f"{self._block} positions")
        self._flops_per_token = self._programs.flops_per_token
        self._recurrent = self._programs.recurrent
        self._no_verify = "" if hasattr(self._programs, "make_verify") else (
            self._recurrent or getattr(self._programs, "no_verify", "")
            or "the family has no verify program")
        if self._no_verify and speculative is not None:
            raise ValueError(
                "speculative decoding is not available for this model: "
                + self._no_verify
            )
        if self._recurrent:
            prefix_cache, swap_block_limit = False, 0

        # prefix cache + preemption state
        self._prefix_enabled = bool(prefix_cache)
        self.min_prefix_blocks = int(min_prefix_blocks)
        # tenant -> priority (callable or mapping; None/absent = 0.0) —
        # preemption triggers only for a STRICTLY higher-priority waiter
        self.tenant_priority = tenant_priority
        # host-side swap store budget in blocks (None = one pool's worth)
        self.swap_block_limit = swap_block_limit
        self._swapped = []          # paused _Swapped streams, FIFO
        self._swapped_blocks = 0    # blocks parked in the host store
        self._preempt = None        # (slot, gen) chosen by _admit
        self._preemptions = 0
        self._resume_ms = []        # swap-out -> reactivation latencies

        # fleet prefix tier (serve/fleet.py): peer lookups run on the
        # SUBMIT caller's thread and exports on the scheduler thread,
        # both strictly outside _cv (the PEER-CALL-UNDER-LOCK gate)
        self.fleet = fleet
        self._fleet_lookups = 0     # peer prefix lookups issued
        self._fleet_blocks = 0      # blocks installed from peers

        # device state allocates lazily with the thread
        self.kv = None
        self.prefix = None
        self._tokens = None
        self._keys = None
        self._prefill = self._programs.prefill  # one chunk's dispatch
        # how far a decode tick reads each lane (``_tick_reads(lengths,
        # table_width)``): to its own length where the family's tick
        # reads the blocks in place; None where every lane reads
        # ``attended_positions`` of the longest
        self._tick_reads = self._programs._tick_reads
        # a family counts more for an entry of tick_trace(): on the host
        # from the entry's own lengths (``tick_fields``), and it may on the
        # device (``counters`` names the int32 vector its programs return
        # beside their tokens, with the series each feeds).  The engine
        # knows none of the names.
        self._tick_fields = self._programs.tick_fields
        self._counters = getattr(self._programs, "counters", ())
        self._adopt = jax.jit(_adopt)
        self._tick_jits = {}

        # speculative decoding (serve/lm/spec.py; off by default): the
        # drafter + adaptive-k policy is per-model config, the verify
        # widths a fixed geometric set so the verify executable count is
        # provably <= len(_verify_widths) * len(lane_counts)
        self._spec = SpecConfig.parse(speculative)
        self._verify_widths = (
            verify_widths(self._spec.k) if self._spec is not None else ()
        )
        self._verify_jits = {}
        self._spec_proposed = 0
        self._spec_accepted = 0

    # -- executable accounting (the bounded-compile proofs) ---------------

    def prefill_executables(self):
        """Compiled prefill-chunk executable count (<= len(self.buckets)
        by construction — chunk widths come from the bucket set)."""
        size = getattr(self._programs.prefill_jit, "_cache_size", None)
        return size() if callable(size) else None

    def decode_executables(self):
        """Compiled decode-tick executable count (<= len(lane_counts))."""
        with self._cv:  # the scheduler inserts into _tick_jits mid-run
            fns = list(self._tick_jits.values())
        total = 0
        for fn in fns:
            size = getattr(fn, "_cache_size", None)
            total += size() if callable(size) else 1
        return total

    def verify_executables(self):
        """Compiled speculative-verify executable count
        (<= len(verify_widths(k)) * len(lane_counts) by construction)."""
        with self._cv:  # the scheduler inserts into _verify_jits mid-run
            fns = list(self._verify_jits.values())
        total = 0
        for fn in fns:
            size = getattr(fn, "_cache_size", None)
            total += size() if callable(size) else 1
        return total

    def spec_stats(self):
        """Speculative-decoding counters ({} when speculation is off;
        ``enabled`` False with the ``reason`` for a model that cannot have
        it)."""
        with self._cv:
            if self._no_verify:
                return {"enabled": False, "reason": self._no_verify}
            if self._spec is None:
                return {}
            prop, acc = self._spec_proposed, self._spec_accepted
            return {
                "proposed": prop,
                "accepted": acc,
                "rejected": prop - acc,
                "acceptance_rate": round(acc / max(prop, 1), 4),
            }

    def tick_trace(self):
        """Recent per-tick records, oldest first, as they stand now:
        ``kind``, ``t0``/``t1`` (around the dispatch), ``lanes``,
        ``n_lanes`` — the fairness/jitter evidence tests and ops read —
        and, once the tick's device work has completed, ``t_done`` and
        ``device_s`` (serve/_completion.py; a ``draft`` runs on the host
        and has neither).  The dispatch between ``t0`` and ``t1`` is two
        spans, ``upload_s`` (every host array of the tick made a device
        array: the ``upload`` phase) and ``call_s`` (the program's call
        alone: the ``*_dispatch`` phase), so ``upload_s + call_s <= t1 -
        t0``.  Once complete, an entry also carries ``host_pause_s``: the
        seconds, of those its ``device_s`` counts, in which the
        interpreter was held (serve/prof.py's pulse), so that a completion
        stamped late does not read as a slow device without saying so (0.0
        as a rule).  An entry the profiler's stall rule marked carries
        ``stall`` (``upload``, ``call``, ``compile``, ``host_pause`` or
        ``device``) and ``stall_s`` (the span's length, or the device
        time's excess over its kind's running median); a sound run has
        neither on any entry.  These five come from the profiler's phases
        and its pulse: with the profiler disarmed
        (``PhaseProfiler.arm(False)``) an entry has none of them.  The
        ``prefill_chunk`` that ends a prompt also
        carries the first token's wait: ``t_submit``, ``t_admit`` and,
        once the token is on its stream's queue, ``t_delivered``.  All on
        ``time.monotonic()``'s clock.  Every entry that dispatched device
        work also counts what the program met, from the engine's own
        lengths: ``context_tokens`` (the lanes' real lengths, summed: a
        decode or verify tick's before its write, a chunk's lane after
        it), ``window_tokens`` for a model with window layers (the sum of
        ``min(length, window)``), ``attended_positions`` (the cache
        positions a lane its attention read: for the decoder the table
        width that ``policy.attention_width_index`` picks for the longest
        lane, or for a chunk's last position, so its mean over decode
        ticks against ``max_seq`` is how far that bound engages; for a
        family whose tick reads each lane to its own length, the longest
        lane's read), ``attended_tokens`` (those positions summed over the
        lanes: against ``context_tokens`` on decode ticks, how much of
        what attention read was live), and on
        a ``prefill_chunk`` its bucket ``width``, the real ``tokens`` in
        it and its ``start``.  A family may add fields of its own: what
        its ``tick_fields`` counts on the host when the entry is written,
        and, once the device work has completed, what its programs counted
        on the device (its ``counters``: the vector comes to the host with
        the tokens).  The ``decode`` entry of a family whose tick holds a
        block of positions a lane (``context_tokens``: the blocks' first
        positions) also says what its passes were: ``block_rows`` (positions
        in the tick), ``denoise_lanes`` and ``commit_lanes`` (lanes by the
        pass they ran), ``masked_rows`` (positions still masked, whose
        logits the pass used) and ``tokens_out`` (tokens its readback
        delivers).  How far the host ran ahead (``_drain_ahead``): a
        ``prefill_chunk`` carries ``ahead_s``, the priced device time
        dispatched ahead of it and not yet read back when it was
        dispatched (absent while a program in that queue had no price
        yet), and a ``decode`` entry ``inflight``, the results its pass
        left dispatched and not read back."""
        with self._cv:
            return [dict(entry) for entry in self._tick_log]

    def pass_trace(self):
        """For a family whose tick holds a block of positions a lane: the
        last ``pass_log_len`` streams that ended, oldest first, each
        ``{"prompt": ids (an array), "tokens": delivered ids, "fixed_at":
        for each delivered token, how many of its block's positions were
        unmasked when the pass ran that fixed it}``: tokens with one
        ``fixed_at`` in one block were fixed by one pass, and a block's state
        at any pass follows.  Empty for every other family."""
        with self._cv:
            return [dict(entry) for entry in self._pass_log]

    def prefix_stats(self):
        """Prefix-cache counters ({} when the cache is disabled or the
        engine never started; ``enabled`` False with the ``reason`` for a
        model whose lanes cannot adopt blocks)."""
        with self._cv:
            if self._recurrent:
                return {"enabled": False, "reason": self._recurrent}
            return {} if self.prefix is None else self.prefix.stats()

    def preempt_stats(self):
        """Preemption/swap counters: preemptions, completed resumes with
        their swap-out -> reactivation latencies, streams still parked."""
        with self._cv:
            stats = {
                "preemptions": self._preemptions,
                "resumes": len(self._resume_ms),
                "resume_ms": list(self._resume_ms),
                "swapped_streams": len(self._swapped),
                "swapped_blocks": self._swapped_blocks,
            }
            if self._recurrent:
                # no host swap: a preempted lane resumes by recompute
                stats["swap"] = "off, recompute only: " + self._recurrent
            return stats

    def set_registry(self, registry):
        """Late-bind the serving metrics registry (add_model wiring)."""
        with self._cv:
            self.registry = registry
            if self.prefix is not None:
                self.prefix.registry = registry
            kv = self.kv
        if kv is not None:
            kv.set_registry(registry)
        self.prof.set_registry(registry)

    def set_fleet(self, fleet):
        """Late-bind the cross-replica prefix tier (add_model wiring):
        submit consults it on local-trie shortfall, prefill completion
        exports into it, drain migrates parked streams through it."""
        with self._cv:
            self.fleet = fleet

    def fleet_stats(self):
        """Fleet prefix-tier counters: peer lookups issued at submit and
        KV blocks installed from peers (zeros when no tier is bound)."""
        with self._cv:
            stats = {
                "remote_lookups": self._fleet_lookups,
                "remote_blocks": self._fleet_blocks,
            }
            if self._recurrent:
                stats["prefix_export"] = "off: " + self._recurrent
            return stats

    def pressure(self):
        """Autoscaling signal: queued submissions + parked (swapped)
        streams + active lanes — the LM half of the per-replica
        queue-depth gauge the fleet tier gossips on probes — plus
        paged-KV occupancy (block exhaustion is the earliest scale-up
        signal for LM workloads)."""
        with self._cv:
            pending = sum(len(dq) for dq in self._pending.values())
            active = sum(1 for lane in self._lanes if lane.active)
            kv = self.kv
        # KV accounting outside the condition lock: the pool has its own
        # synchronization and holding _cv across it invites lock nesting
        kv_fraction = 0.0
        if kv is not None:
            used = kv.used_blocks
            total = used + kv.free_blocks
            kv_fraction = round(used / total, 4) if total > 0 else 0.0
        return {
            "queue_depth": pending + len(self._swapped),
            "inflight": active,
            "kv_used_fraction": kv_fraction,
        }

    # -- request side ------------------------------------------------------

    def submit(self, prompt_tokens, max_tokens, temperature=0.0, top_k=0,
               seed=0, tenant=""):
        """Returns (token_queue, handle); the queue ends with CLOSE."""
        prompt = np.asarray(prompt_tokens, np.int32).reshape(1, -1)
        max_tokens = min(int(max_tokens),
                         self.cfg.max_seq - prompt.shape[1])
        q = queue.Queue()
        if max_tokens <= 0:
            q.put(_CLOSE)
            return q, None
        handle = _Handle(prompt, max_tokens, q, str(tenant or ""),
                         temperature, top_k, seed)
        fleet = self.fleet
        if fleet is not None and self._prefix_enabled:
            shareable = (handle.prompt_len - 1) // self.block_size
            if shareable > 0:
                with self._cv:
                    if self._closed:
                        q.put(_CLOSE)
                        return q, None
                    self._ensure_thread_locked()
                    local = len(
                        self.prefix.match(handle.prompt[0], shareable)[0]
                    )
                    if local < shareable:
                        self._fleet_lookups += 1
                if local < shareable:
                    # peer RPC on the CALLER's thread with no engine lock
                    # held: a slow/dead peer delays only this submit, by
                    # at most the tier's bounded fan-out x timeout — the
                    # scheduler keeps ticking throughout.  Only the tail
                    # past the local match travels (start_blocks).
                    got = fleet.prefix_lookup(
                        handle.prompt[0], self.block_size, shareable,
                        start_blocks=local,
                    )
                    if got is not None and got[0] > local:
                        handle.remote_kv = got
        with self._cv:
            if self._closed:
                q.put(_CLOSE)
                return q, None
            self._ensure_thread_locked()
            self._pending.setdefault(handle.tenant, deque()).append(handle)
            self._cv.notify_all()
        return q, handle

    def cancel(self, handle):
        """Release a stream early (consumer went away)."""
        if handle is None:
            return
        with self._cv:
            lane_q = self._pending.get(handle.tenant)
            if lane_q is not None:
                for i, entry in enumerate(lane_q):
                    if entry is handle:
                        entry.queue.put(_CLOSE)
                        del lane_q[i]
                        if not lane_q:
                            del self._pending[handle.tenant]
                        return
            placed = handle.placed
            if placed is None:
                # popped from pending but not yet streaming: the prefill
                # job is mid-dispatch outside _cv.  Mark the handle; the
                # job step aborts and closes the queue.
                handle.placed = _CANCELLED
                return
            if placed is _CANCELLED:
                return
            if isinstance(placed, _Swapped):
                # preempted and parked: its blocks were already released
                # at swap-out, so cancel just closes the paused stream
                # and drops the host copies (a resume job in flight for
                # it sees .cancelled and aborts)
                if not placed.cancelled:
                    placed.cancelled = True
                    placed.queue.put(_CLOSE)
                    if placed in self._swapped:
                        self._swapped.remove(placed)
                        if placed.host is not None:
                            self._swapped_blocks -= placed.written_blocks
                            self._swap_gauge_locked()
                    placed.host = None
                return
            slot_idx, gen = placed
            lane = self._lanes[slot_idx]
            if lane.active and lane.gen == gen:
                self._retire_lane_locked(lane)

    def close(self):
        with self._cv:
            self._closed = True
            self._release_all_locked()
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
        self._observer.close()

    # -- locked helpers ----------------------------------------------------

    def _ensure_thread_locked(self):
        if self._thread is not None:
            return
        self.kv = KvBlockPool(
            self.cfg,
            n_blocks=max(
                self._pool_tokens // self.block_size, self._table_width
            ),
            block_size=self.block_size,
            registry=self.registry,
            lanes=self.max_slots,
        )
        if self._prefix_enabled:
            self.prefix = PrefixCache(
                self.kv, registry=self.registry,
                min_prefix_blocks=self.min_prefix_blocks,
            )
        if self.swap_block_limit is None:
            self.swap_block_limit = self.kv.n_blocks
        # the lane array: a lane's next input token, or its block's state
        self._tokens = (
            jnp.zeros((self.max_slots,), jnp.int32) if self._block == 1
            else self._programs.lane_state(self.max_slots))
        self._keys = jnp.zeros((self.max_slots, 2), jnp.uint32)
        self._thread = threading.Thread(
            target=self._loop, name="lm-engine", daemon=True
        )
        self._thread.start()

    def _retire_lane_locked(self, lane, close_queue=True):
        """Release a lane and return its KV reservation (full prompt
        blocks go to the prefix cache; the rest free).  ``close_queue``
        False is the preemption path: the stream pauses, it does not
        end."""
        lane.active = False
        lane.gen += 1  # in-flight ticks for this lane drop on drain
        if close_queue:
            lane.queue.put(_CLOSE)
            if self._block > 1 and lane.tokens:
                self._pass_log.append({
                    "prompt": lane.prompt[0],
                    "tokens": list(lane.tokens),
                    "fixed_at": lane.fixed[:len(lane.tokens)],
                })
        lane.table[:] = KvBlockPool.TRASH
        written, lane.length = lane.length, 0
        prompt, lane.prompt = lane.prompt, None
        lane.tokens, lane.fixed = [], []
        lane.handle = None
        lane.spec = None
        blocks, lane.blocks = lane.blocks, None
        if blocks:
            self._release_blocks_locked(prompt, written, blocks)

    def _release_blocks_locked(self, prompt, written_tokens, blocks):
        """Return one reservation: fully written FULL prompt blocks are
        offered to the prefix cache (the holder's reference transfers or
        drops — see PrefixCache.give_back), everything else frees."""
        if self.prefix is None or prompt is None:
            self.kv.release(blocks)
            return
        prompt_row = prompt[0]
        cacheable = (
            min(int(written_tokens), prompt_row.shape[0]) // self.block_size
        )
        self.prefix.give_back(prompt_row, cacheable, blocks)

    def _release_all_locked(self):
        """Close every pending/active/in-prefill/swapped stream and drop
        the warm cache (caller holds _cv)."""
        for lane_q in self._pending.values():
            for entry in lane_q:
                entry.queue.put(_CLOSE)
        self._pending.clear()
        for lane in self._lanes:
            if lane.active:
                self._retire_lane_locked(lane)
        job, self._job = self._job, None
        if job is not None:
            self._abort_job_locked(job)
        swapped, self._swapped = self._swapped, []
        for entry in swapped:
            if not entry.cancelled:
                entry.cancelled = True
                entry.queue.put(_CLOSE)
        self._swapped_blocks = 0
        self._preempt = None
        if self.prefix is not None:
            # AFTER every give_back above: the pool must end fully free
            self.prefix.clear()

    def _abort_job_locked(self, job):
        blocks, job.blocks = job.blocks, None
        if blocks:
            # chunks already dispatched cover positions up to the next
            # chunk's start; those full prompt blocks are valid cache
            # content even though the request died mid-prefill
            written = (
                job.plan[job.chunk_idx][0]
                if job.chunk_idx < len(job.plan)
                else job.handle.prompt_len
            )
            if job.remote is not None:
                # fleet-fetched blocks were never installed on device:
                # only the locally adopted prefix below them is real
                # content — caching the uninstalled range would poison
                # the trie with garbage KV
                written = min(written, job.remote[0] * self.block_size)
            self._release_blocks_locked(job.handle.prompt, written, blocks)
        if job.resume is not None:
            if not job.resume.cancelled:
                job.resume.cancelled = True
                job.resume.queue.put(_CLOSE)
        else:
            job.handle.queue.put(_CLOSE)

    def _tenant_lanes_locked(self, tenant):
        held = sum(
            1 for lane in self._lanes if lane.active and lane.tenant == tenant
        )
        if self._job is not None and self._job.handle.tenant == tenant:
            held += 1
        return held

    def _tenant_quota_locked(self, tenant, n_lanes, others_pending):
        """Max lanes *tenant* may hold right now.  Work-conserving: the
        quota binds only while another tenant is waiting."""
        if not others_pending:
            return n_lanes
        share = self.tenant_lane_share
        if callable(share):
            share = share(tenant)
        if share is None:
            share = 1.0
        return max(1, min(n_lanes, int(np.ceil(float(share) * n_lanes))))

    def _pick_pending_locked(self, n_lanes):
        """Pop the next admissible pending handle: strict priority-class
        order first (a gold request queued behind a backpressured bronze
        one must be picked — and preempt — FIRST, or pool exhaustion
        re-picks the bronze head forever and preemption never fires),
        round-robin-fair within a class (the only order when no
        priorities are configured); tenants at their lane quota are
        skipped while others wait."""
        tenants = [t for t, dq in self._pending.items() if dq]
        if not tenants:
            return None
        rotated = tenants[self._rr % len(tenants):] + \
            tenants[:self._rr % len(tenants)]
        # stable sort: equal classes keep their rotated (rr) order
        order = sorted(rotated, key=lambda t: -self._priority_of(t))
        for tenant in order:
            others = any(t != tenant and dq for t, dq in
                         self._pending.items() if dq)
            quota = self._tenant_quota_locked(tenant, n_lanes, others)
            if self._tenant_lanes_locked(tenant) >= quota:
                continue
            self._rr += 1
            lane_q = self._pending[tenant]
            handle = lane_q.popleft()
            if not lane_q:
                # a drained tenant's entry is evicted: client-minted
                # tenant ids must not grow the map (or the per-pass
                # scan) without bound
                del self._pending[tenant]
            return handle
        return None

    def _max_active_locked(self):
        top = -1
        for i, lane in enumerate(self._lanes):
            if lane.active:
                top = i
        if self._job is not None:
            top = max(top, self._job.slot)
        return top

    def _queued_locked(self):
        return any(dq for dq in self._pending.values())

    def _has_pending_locked(self):
        # swapped streams count as pending pressure: they need a lane and
        # blocks to resume, so the autoscaler must not scale down past them
        return self._queued_locked() or bool(self._swapped)

    def _priority_of(self, tenant):
        """Priority class of *tenant* (higher preempts lower; default 0)."""
        source = self.tenant_priority
        if source is None:
            return 0.0
        value = source(tenant) if callable(source) else source.get(tenant)
        return 0.0 if value is None else float(value)

    def _pick_preempt_victim_locked(self, tenant):
        """Lowest-priority active lane STRICTLY below *tenant*'s class
        (ties broken toward the shortest sequence — least KV to swap);
        None when nothing qualifies."""
        want = self._priority_of(tenant)
        victim = None
        victim_key = None
        for i, lane in enumerate(self._lanes):
            if not lane.active:
                continue
            pri = self._priority_of(lane.tenant)
            if pri >= want:
                continue
            key = (pri, lane.length)
            if victim_key is None or key < victim_key:
                victim, victim_key = i, key
        return victim

    def _restore_lane_locked(self, lane, entry, slot):
        """Install a parked _Swapped stream's saved counters/identity on
        a lane and stamp the resume latency.  The caller owns gen/active
        and the table/blocks install — those differ between the swap-in
        and recompute-replay paths."""
        lane.queue = entry.queue
        lane.remaining = entry.remaining
        lane.produced = entry.produced
        lane.length = entry.length
        lane.limit = entry.limit
        lane.tenant = entry.tenant
        lane.temperature = entry.temperature
        lane.top_k = entry.top_k
        lane.prompt = entry.prompt
        lane.tokens = list(entry.tokens)
        lane.masks, lane.fixed = entry.masks, list(entry.fixed)
        lane.handle = entry.handle
        # drafter state rebuilds from the prompt; the adaptive-k window
        # restarts (a resume is rare — one extra window to re-disable an
        # adversarial lane is noise)
        lane.spec = (
            LaneSpec(self._spec, entry.prompt[0])
            if self._spec is not None else None
        )
        if entry.handle is not None:
            entry.handle.placed = (slot, lane.gen)
        self._resume_ms.append((time.monotonic() - entry.t_swap) * 1e3)

    def _swap_gauge_locked(self):
        if self.registry is not None:
            self.registry.set(
                "ctpu_lm_swapped_blocks", None, self._swapped_blocks,
                help_=LM_PREFIX_HELP["ctpu_lm_swapped_blocks"],
            )

    def _lane_gauges_locked(self, active_count=None):
        if self.registry is None:
            return
        self.registry.set("ctpu_lm_lanes", None, self._scaler.n_lanes,
                          help_=_LANE_HELP["ctpu_lm_lanes"])
        if active_count is None:
            active_count = sum(1 for lane in self._lanes if lane.active)
        self.registry.set("ctpu_lm_active_lanes", None, active_count,
                          help_=_LANE_HELP["ctpu_lm_active_lanes"])

    # -- scheduler loop ----------------------------------------------------

    def _reserve_locked(self, needed, matched_blocks):
        """Allocate ``needed - len(matched)`` fresh blocks, evicting warm
        cache blocks under pressure.  Matched blocks must already be
        adopted (refcount >= 2) so eviction can never steal them.
        Returns the fresh list or None."""
        short = needed - len(matched_blocks)
        fresh = self.kv.alloc(short)
        if fresh is None and self.prefix is not None:
            missing = short - self.kv.free_blocks
            if self.prefix.evict(missing) >= missing:
                fresh = self.kv.alloc(short)
        return fresh

    def _admit(self):
        """Move one pending request into a prefill job (bookkeeping under
        _cv; every chunk dispatch happens later, outside the lock).
        Prefix-cache adoption happens here: matched prompt blocks are
        retained by reference and the chunk plan starts at the first
        miss."""
        with self._cv:
            if (self._closed or self._job is not None
                    or self._preempt is not None):
                return
            n_lanes = self._scaler.n_lanes
            slot = next(
                (i for i in range(n_lanes) if not self._lanes[i].active),
                None,
            )
            if slot is None:
                # every lane busy: ANY pending work is starvation —
                # sustained starvation steps the lane count up.  (Checked
                # before the quota-aware pick: a tenant at its lane quota
                # with zero free lanes must still register pressure.)
                if self._has_pending_locked():
                    if self._scaler.note_starved():
                        self._lane_gauges_locked()
                else:
                    self._scaler.note_ok(False, self._max_active_locked())
                return
            handle = self._pick_pending_locked(n_lanes)
            if handle is None:
                # nothing admissible: idle, or every pending tenant is at
                # its quota while a lane sits free (note_ok with pending
                # True so the free lane cannot drive a scale-down under a
                # quota-capped backlog)
                self._scaler.note_ok(
                    self._has_pending_locked(), self._max_active_locked()
                )
                self._lane_gauges_locked()
                return
            handle.t_admit = time.monotonic()
            needed = self.kv.blocks_for(
                handle.prompt_len + handle.max_tokens
            )
            matched_blocks, matched_nodes = [], []
            shareable = (handle.prompt_len - 1) // self.block_size
            if self.prefix is not None and shareable:
                # cap at (prompt_len - 1): the final prompt position must
                # always prefill — its logits seed the first new token
                matched_blocks, matched_nodes = self.prefix.match(
                    handle.prompt[0], shareable
                )
                # adopt BEFORE the allocation attempt: refcount 2 pins the
                # matched chain against the eviction pass below
                self.prefix.adopt(matched_nodes)
            fresh = self._reserve_locked(needed, matched_blocks)
            if fresh is None:
                # pool exhausted even after cache eviction: drop the
                # adoption, then either preempt a strictly lower-priority
                # lane for a higher-priority waiter or backpressure until
                # completions free blocks.  (The pick may have evicted the
                # tenant's drained entry — recreate it.)
                if matched_blocks:
                    self.kv.release(matched_blocks)
                victim = self._pick_preempt_victim_locked(handle.tenant)
                if victim is not None:
                    self._preempt = (victim, self._lanes[victim].gen)
                self._pending.setdefault(
                    handle.tenant, deque()
                ).appendleft(handle)
                self._rr -= 1
                return
            blocks = matched_blocks + fresh
            table = np.full(
                (self._table_width,), KvBlockPool.TRASH, np.int32
            )
            table[:len(blocks)] = blocks
            start = len(matched_blocks) * self.block_size
            job_remote = None
            if handle.remote_kv is not None:
                # fleet-tier adoption beyond the local trie: blocks
                # [local..covered) are FRESH pool blocks whose content the
                # first _prefill_step installs from the peer's host arrays
                # (outside _cv); the chunk plan starts past them.  The
                # fetched arrays cover blocks [rstart, covered) — if the
                # trie shrank below rstart since the submit-time probe
                # (eviction under pressure), the fetch cannot bridge the
                # gap and is dropped: prefill is always correct, just
                # slower.
                covered = min(int(handle.remote_kv[0]), shareable)
                rstart = handle.remote_kv[2]
                if rstart <= len(matched_blocks) < covered:
                    job_remote = [
                        len(matched_blocks), covered, handle.remote_kv[1],
                        rstart,
                    ]
                    start = covered * self.block_size
                    self._fleet_blocks += covered - len(matched_blocks)
                    if self.registry is not None:
                        self.registry.inc(
                            "ctpu_fleet_prefix_blocks_total", None,
                            value=covered - len(matched_blocks),
                            help_=FLEET_HELP[
                                "ctpu_fleet_prefix_blocks_total"],
                        )
                        self.registry.inc(
                            "ctpu_fleet_prefix_tokens_saved_total", None,
                            value=(covered - len(matched_blocks))
                            * self.block_size,
                            help_=FLEET_HELP[
                                "ctpu_fleet_prefix_tokens_saved_total"],
                        )
            if self.prefix is not None and shareable:
                self.prefix.note_lookup(
                    len(matched_blocks), shareable - len(matched_blocks)
                )
            if self.registry is not None and start:
                self.registry.inc(
                    "ctpu_lm_prefill_tokens_saved_total", None, value=start,
                    help_=LM_PREFIX_HELP["ctpu_lm_prefill_tokens_saved_total"],
                )
            # key=None: PRNGKey is itself a (jitted) device dispatch and
            # must not run under _cv — the first _prefill_step builds it
            self._job = _PrefillJob(
                handle, slot, blocks, table,
                chunk_plan(handle.prompt_len, self.buckets, start=start),
                None,
            )
            self._job.remote = job_remote
            self._scaler.note_ok(False, self._max_active_locked())

    def _job_cancelled_locked(self, job):
        """True when the stream this job serves went away: a normal
        admission's handle was cancelled, or a recompute-resume's
        swapped stream was."""
        if job.resume is not None:
            return job.resume.cancelled
        return job.handle.placed is _CANCELLED

    def _prefill_step(self, ptick):
        """Dispatch ONE chunk of the current prefill job (outside _cv);
        the final chunk activates the lane.  The pass's phases: ``build``
        (the job read, the chunk padded), ``upload``, ``prefill_dispatch``
        (the program's call) and ``record``."""
        with ptick.phase("build"):
            chunk = self._build_chunk()
        if chunk is None:
            return
        job, handle, start, width, chunk = chunk
        t0 = time.monotonic()
        fresh = job.chunk_idx == 0
        with ptick.phase("upload", held=True) as upload:
            # a family whose lanes carry state takes the lane's slot and
            # whether this chunk starts it from zero on the device too
            slot = job.slot
            if self._recurrent:
                slot, fresh = jnp.int32(slot), jnp.bool_(fresh)
            args = (
                jnp.asarray(chunk), jnp.asarray(job.table), slot,
                jnp.int32(start), jnp.int32(handle.prompt_len), fresh,
                job.key, jnp.float32(handle.temperature),
                jnp.int32(handle.top_k),
            )
        with ptick.phase("prefill_dispatch", held=True) as call:
            tok, job.key, *counted = self._prefill(self.params, self.kv,
                                                   *args)
        with ptick.phase("record"):
            self._chunk_dispatched(job, handle, start, width, t0, tok,
                                   counted, (upload, call))

    def _build_chunk(self):
        """``(job, handle, start, width, padded chunk)`` of the chunk to
        dispatch now, or None where the job has gone."""
        with self._cv:
            # re-read under the lock: a concurrent close() may have
            # aborted and cleared the job since the caller's check
            job = self._job
            if job is None:
                return
            if self._closed or self._job_cancelled_locked(job):
                self._abort_job_locked(job)
                self._job = None
                return
            # snapshot the remote-install plan under the lock: a close()
            # racing this step nulls job.blocks in _abort_job_locked, and
            # the consumed job.remote marks the blocks as real content
            # for the eventual give_back
            remote, job.remote = job.remote, None
            remote_blocks = (
                list(job.blocks[remote[0]:remote[1]])
                if remote is not None else None
            )
        handle = job.handle
        if remote is not None:
            # install the fleet-fetched KV content into the reservation's
            # fresh blocks (scheduler thread, outside _cv — the scatter
            # orders before this job's chunk dispatches below, so the
            # chunk's attention reads the peer-computed content).  The
            # host arrays cover chain blocks [rstart, covered); the
            # destination is blocks [lo, hi) of the reservation.
            lo, _hi, host, rstart = remote
            self.kv.write_blocks(remote_blocks, host, first=lo - rstart)
        if job.key is None:  # deferred out of _admit: dispatch-free lock
            job.key = jax.random.PRNGKey(handle.seed)
        start, width = job.plan[job.chunk_idx]
        chunk = pad_prompt(
            handle.prompt[:, start:start + width], width,
            pad_id=0,
        )
        return job, handle, start, width, chunk

    def _chunk_dispatched(self, job, handle, start, width, t0, tok, counted,
                          spans):
        """The chunk's entry; after a prompt's last, its lane activated
        and its first token on the way to the host."""
        job.chunk_idx += 1
        # every chunk samples a token, which nothing donates onward: the
        # chunk's device work is complete when it is
        tokens = min(start + width, handle.prompt_len) - start
        entry = self._log_tick(
            "prefill_chunk", t0, (job.slot,), tok, [start + tokens],
            start + width - 1, counted=counted, spans=spans, width=width,
            tokens=tokens, start=start,
        )
        self._price(entry)
        if self.registry is not None:
            self.registry.inc(
                "ctpu_lm_prefill_chunks_total",
                help_="Prefill chunks dispatched between decode ticks",
            )
            # real (non-pad) prompt tokens this chunk computed — the
            # denominator side of the prefix-cache savings accounting
            self.registry.inc(
                "ctpu_lm_prefill_tokens_total", None, value=tokens,
                help_=LM_PREFIX_HELP["ctpu_lm_prefill_tokens_total"],
            )
        if job.chunk_idx < len(job.plan):
            return
        export = None
        with self._cv:
            self._job = None
            if self._closed or self._job_cancelled_locked(job):
                self._abort_job_locked(job)
                return
            lane = self._lanes[job.slot]
            resume = job.resume
            lane.gen += 1
            lane.active = True
            lane.table[:] = job.table
            lane.blocks, job.blocks = job.blocks, None
            if (resume is None and self.fleet is not None
                    and not self._recurrent):
                nfull = handle.prompt_len // self.block_size
                if nfull:
                    export = (
                        handle.prompt[0],
                        [int(b) for b in lane.blocks[:nfull]],
                        nfull,
                    )
            if resume is None:
                lane.queue = handle.queue
                lane.remaining = handle.max_tokens
                lane.produced = 0
                lane.length = handle.prompt_len
                lane.limit = handle.prompt_len + handle.max_tokens
                lane.tenant = handle.tenant
                lane.temperature = handle.temperature
                lane.top_k = handle.top_k
                lane.prompt = handle.prompt
                lane.tokens, lane.fixed = [], []
                if self._block > 1:
                    # the prefill stored the prompt's whole blocks; its
                    # tail is the known head of the first generated block
                    lane.length = self._programs.stored(handle.prompt_len)
                    lane.masks = self._programs.masks(
                        lane.length, handle.prompt_len)
                lane.handle = handle
                lane.spec = (
                    LaneSpec(self._spec, handle.prompt[0])
                    if self._spec is not None else None
                )
                handle.placed = (job.slot, lane.gen)
                if self.prefix is not None:
                    # the prompt's full blocks are fully written as of
                    # this chunk: publish them so a same-prefix burst
                    # shares from the first finished prefill
                    self.prefix.publish(
                        handle.prompt[0],
                        handle.prompt_len // self.block_size,
                        lane.blocks,
                    )
            else:
                # recompute-resume: the replayed prefill rebuilt the KV
                # for prompt + delivered tokens; streaming continues from
                # the SAVED counters, token and RNG carry — the chunk's
                # sampled token is discarded (that position's token was
                # already delivered before preemption)
                self._restore_lane_locked(lane, resume, job.slot)
            snapshot = ((job.slot, lane.gen),)
            self._lane_gauges_locked()
        if export is not None:
            self._export_prefix(export)
        if resume is not None:
            # install the saved next-tick input token + RNG carry; nothing
            # streams (everything up to `produced` was already delivered)
            self._tokens, self._keys = self._adopt(
                self._tokens, self._keys, jnp.int32(job.slot),
                jnp.asarray(resume.token), jnp.asarray(resume.key),
            )
            return
        # install the first token + RNG carry into the lane arrays and
        # stream the token through the readback pipeline (single-lane
        # entry, exactly like a full tick's vector)
        self._tokens, self._keys = self._adopt(
            self._tokens, self._keys, jnp.int32(job.slot), tok, job.key
        )
        with self._cv:
            entry["t_submit"] = handle.t_submit
            entry["t_admit"] = handle.t_admit
        if self._block > 1:
            return  # what the chunk yields is the lane's first block: no token
        tok.copy_to_host_async()
        self._enqueue(tok, snapshot, entry, None)

    def _export_prefix(self, export):
        """Publish freshly prefilled full prompt blocks into the fleet
        tier's host store (scheduler thread, OUTSIDE _cv: the gather is
        a device read ordered after this job's chunk writes, and the
        store insert is host-side only).  One device->host copy per
        prefill — the price of making the prefix fleet-visible, paid
        only while a tier is attached."""
        row, blocks, nfull = export
        self.fleet.export_prefix(
            row, nfull, self.block_size, self.kv.read_blocks(blocks)
        )

    def drain(self):
        """Planned retire: migrate what can migrate, then close.

        Active lanes' prompt prefixes were already exported to the fleet
        tier at prefill completion, so a client replaying
        prompt + delivered tokens on a surviving replica resumes
        byte-exact with its prefill largely served from the tier.
        Parked (preempted) streams are the case with otherwise-stranded
        state: their host-swapped KV chains — prompt AND generated-token
        blocks — are exported here, and the swap store drops with the
        close (audited: no leaked blocks).  Returns the number of parked
        streams exported."""
        exports = []
        with self._cv:
            fleet = self.fleet if self.prefix is not None else None
            if fleet is not None:
                for entry in self._swapped:
                    if entry.cancelled or entry.host is None:
                        continue
                    nfull = entry.length // self.block_size
                    if not nfull:
                        continue
                    row = self._written_row(entry)
                    exports.append((row, nfull, entry.host))
        for row, nfull, host in exports:
            fleet.export_prefix(
                row, nfull, self.block_size,
                {name: [a[:nfull] for a in layers]
                 for name, layers in host.items()},
            )
        if exports and self.registry is not None:
            self.registry.inc(
                "ctpu_fleet_sessions_migrated_total", None,
                value=len(exports),
                help_=FLEET_HELP["ctpu_fleet_sessions_migrated_total"],
            )
        self.close()
        return len(exports)

    @staticmethod
    def _written_row(entry):
        """The ids whose K/V a parked stream had written: the prompt and
        the delivered tokens up to its ``length`` (every delivered token
        but the last, which is the NEXT tick's input; of a family whose
        tick holds a block, the blocks that were committed: none yet of a
        prompt shorter than a block, whose replay then holds one id and
        stores nothing)."""
        return np.concatenate([
            entry.prompt[0], np.asarray(entry.tokens, np.int32),
        ])[:max(entry.length, 1)]

    def _tick_for(self, n):
        # memoized under _cv: decode_executables() iterates this dict
        # from the caller thread while the scheduler inserts — jax.jit
        # here only CONSTRUCTS the callable (tracing happens at the
        # dispatch site, outside the lock), so the critical section
        # stays cheap
        with self._cv:
            fn = self._tick_jits.get(n)
            if fn is None:
                fn = self._tick_jits[n] = self._programs.make_tick(n)
        return fn

    def _decode_pass(self, ptick):
        """One batched decode tick over the active lanes (dispatch
        outside _cv).  Returns the tick's tick_trace() entry, or None where
        no tick ran.  The pass's phases:
        ``build`` (the tick's arrays, on the host, under _cv),
        ``upload`` (all of them to the device), ``decode_dispatch`` (the
        program's call alone) and ``record`` (the tick's entry)."""
        with ptick.phase("build"):
            built = self._build_tick()
        if built is None:
            return None
        n, active, tables, lens, live, temps, topks, takes, passes = built
        t0 = time.monotonic()
        with ptick.phase("upload", held=True) as upload:
            args = (
                jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(live),
                jnp.asarray(temps), jnp.asarray(topks),
            )
        with ptick.phase("decode_dispatch", held=True) as call:
            self._tokens, self._keys, *counted = self._programs.tick(
                self._tick_for(n), self.params, self.kv, self._tokens,
                *args, self._keys,
            )
        with ptick.phase("record"):
            self._tokens.copy_to_host_async()
            entry = self._log_tick(
                "decode", t0, tuple(i for i, _ in active), self._tokens,
                lens[live], int(lens.max()) + self._block - 1,
                self._tick_reads(lens[live], self._table_width),
                counted=counted, spans=(upload, call), **passes,
            )
            self._price(entry)
            self._enqueue(self._tokens, tuple(active), None, takes)
        return entry

    def _build_tick(self):
        """A decode tick's arrays, on the host: ``(n, active, tables,
        lens, live, temps, topks, takes, passes)``, or None with no lane to
        tick.  ``takes`` and ``passes`` are a block family's (None and
        empty otherwise): for each lane of ``active`` the first position of
        its block whose token the tick's readback delivers (the block's
        length: none), and what the tick's entry says of its passes."""
        with self._cv:
            if self._closed:
                return None
            n = self._scaler.n_lanes
            # a lane drops out of the tick batch once it has dispatched
            # its full token budget (readback may still be in flight) —
            # dispatch-ahead must never write past the lane's block
            # reservation
            active = [
                (i, self._lanes[i].gen)
                for i in range(n)
                if self._lanes[i].active
                and self._unspent_locked(self._lanes[i])
            ]
            if not active:
                return None
            # lanes outside the batch (idle, or at-budget awaiting drain)
            # get a trash table + position 0: their scatter lands in the
            # trash block and their garbage token is never delivered
            included = {i for i, _ in active}
            trash_row = np.zeros((self._table_width,), np.int32)
            tables = np.stack([
                self._lanes[i].table if i in included else trash_row
                for i in range(n)
            ])
            lens = np.array(
                [self._lanes[i].length if i in included else 0
                 for i in range(n)], np.int32,
            )
            temps = np.array(
                [self._lanes[i].temperature for i in range(n)], np.float32
            )
            topks = np.array(
                [self._lanes[i].top_k for i in range(n)], np.int32
            )
            takes, passes = None, {}
            if self._block == 1:
                for i, _ in active:
                    self._lanes[i].length += 1  # the tick writes position len
            else:
                takes, passes = self._advance_blocks_locked(active)
            self._lane_gauges_locked(active_count=len(active))
        # ``live``: a lane outside the batch keeps its fixed state as it is
        # (one mid-prefill carries it from chunk to chunk)
        live = np.array([i in included for i in range(n)])
        return n, active, tables, lens, live, temps, topks, takes, passes

    def _unspent_locked(self, lane):
        """Whether a lane has a tick left to dispatch inside its budget:
        dispatch-ahead must never write past the lane's block reservation.
        A lane of one position a tick: until it has written its last.  A
        lane of a block a tick: while its block holds a mask, and for the
        commit only if a block follows inside the budget."""
        if self._block == 1:
            return lane.length < lane.limit
        return lane.masks > 0 or lane.length + self._block < lane.limit

    def _advance_blocks_locked(self, active):
        """A block family's lanes through the pass this tick runs for each
        (the family's static schedule, ``advance``): ``(takes, passes)`` as
        ``_build_tick`` returns them."""
        takes = []
        passes = {"block_rows": self._block * len(active), "denoise_lanes": 0,
                  "commit_lanes": 0, "masked_rows": 0, "tokens_out": 0}
        for i, _ in active:
            lane = self._lanes[i]
            start, masked = lane.length, lane.masks
            kind, lane.length, lane.masks, delivers = self._programs.advance(
                start, masked)
            passes[kind + "_lanes"] += 1
            passes["masked_rows"] += masked
            # the block's positions under the prompt's end were known
            first = self._block - self._programs.masks(
                start, lane.prompt.shape[1])
            takes.append(first if delivers else self._block)
            if delivers:
                passes["tokens_out"] += (
                    min(start + self._block, lane.limit) - start - first)
            if self.registry is not None:
                self.registry.inc(
                    "ctpu_lm_block_passes_total", {"kind": kind},
                    help_="Passes over a lane's diffusion block, by kind "
                          "(denoise: fixes masked positions; commit: stores "
                          "the finished block's keys and values)")
        return takes, passes

    def _verify_for(self, n, w):
        # memoized under _cv exactly like _tick_for: jit here only
        # CONSTRUCTS the callable, tracing happens at dispatch outside
        # the lock
        with self._cv:
            fn = self._verify_jits.get((n, w))
            if fn is None:
                fn = self._verify_jits[(n, w)] = self._programs.make_verify(
                    n, w)
        return fn

    def _spec_pass(self, ptick):
        """One speculative draft + verify pass over the active lanes;
        True when a verify tick ran, False to fall through to the plain
        decode tick.

        The fall-through IS the never-slower path: a lane whose adaptive
        k backed off to 0 skips drafting, and when NO lane drafts the
        pass returns before touching the readback pipeline — the engine
        then runs the exact plain-decode code (dispatch-ahead included),
        paying only this method's host-side enabled check.

        The verify tick is SYNCHRONOUS (no dispatch-ahead): how far a
        lane advances depends on its accepted count, which the host
        learns only at readback.  The in-flight pipeline is drained
        before drafting so each lane's host history is complete
        (``lane.tokens[-1]`` == the device-side next input token — the
        same consistency point ``_preempt_step`` establishes), and
        because verify never spans a pass boundary, a preemption, swap
        or cancel can never observe a half-applied verify: the
        swap/recompute byte-exactness argument is unchanged.
        """
        with self._cv:
            if self._closed:
                return False
            n = self._scaler.n_lanes
            want = False
            for i in range(n):
                lane = self._lanes[i]
                if (not lane.active or lane.spec is None
                        or lane.length >= lane.limit):
                    continue
                room = min(lane.limit - 1 - lane.length,
                           lane.remaining - lane.produced - 1)
                if lane.spec.k > 0 and room > 0:
                    want = True
                else:
                    lane.spec.note_plain()  # re-probe timer while k == 0
            if not want:
                return False
        while self._inflight:
            self._drain_one(ptick)
        cands = []
        with self._cv:
            if self._closed:
                return False
            for i in range(n):
                lane = self._lanes[i]
                if (not lane.active or lane.spec is None
                        or lane.length >= lane.limit or not lane.tokens):
                    continue
                room = min(lane.limit - 1 - lane.length,
                           lane.remaining - lane.produced - 1)
                if lane.spec.k <= 0 or room <= 0:
                    continue
                hist = np.concatenate([
                    lane.prompt[0], np.asarray(lane.tokens, np.int32),
                ])
                cands.append((i, lane.gen, lane.spec, hist, room))
        if not cands:
            return False
        # drafting is pure host work, outside the lock; its own phase and
        # tick_trace() entry, so profview prices it against verify/decode
        t_draft = time.monotonic()
        proposals = {}
        with ptick.phase("draft"):
            for i, gen, lane_spec, hist, room in cands:
                toks = lane_spec.draft(hist)[:room]
                if toks:
                    proposals[i] = (gen, toks)
        if not proposals:
            return False
        self._log_tick("draft", t_draft, tuple(sorted(proposals)))
        with ptick.phase("build"):
            built = self._build_verify(n, proposals)
        if built is None:
            return False
        active, w, tables, lens, temps, topks, props, counts = built
        fn = self._verify_for(n, w)
        t0 = time.monotonic()
        with ptick.phase("upload", held=True) as upload:
            args = (
                jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(temps),
                jnp.asarray(topks), self._keys, jnp.asarray(props),
                jnp.asarray(counts),
            )
        with ptick.phase("verify_dispatch", held=True) as call:
            out, self._tokens, self._keys = self._programs.verify(
                fn, self.params, self.kv, self._tokens, *args)
        with ptick.phase("record"):
            self._log_tick(
                "verify", t0, tuple(i for i, _ in active), out,
                [lens[i] for i, _ in active], int(lens.max()) + w - 1,
                spans=(upload, call))
        with ptick.phase("device_wait"):
            t_wait = time.monotonic()
            vals = np.asarray(out)  # [2, n]: accepted count, correction
            self._waited_s += time.monotonic() - t_wait
        self._loose_s = 0.0  # all that was dispatched has completed
        self._deliver_verified(ptick, active, vals, props, counts)
        return True

    def _build_verify(self, n, proposals):
        """A verify tick's arrays, on the host: ``(active, width, tables,
        lens, temps, topks, props, counts)``, or None where no draft is
        left to verify."""
        with self._cv:
            if self._closed:
                return None
            active = [
                (i, self._lanes[i].gen)
                for i in range(n)
                if self._lanes[i].active
                and self._lanes[i].length < self._lanes[i].limit
            ]
            if not active:
                return None
            included = {i for i, _ in active}
            # gen-checked: a lane cancelled while drafting drops its
            # proposal; other active lanes ride the tick as plain decode
            # (count 0 — they deliver exactly one token)
            drafts = {
                i: toks for i, (gen, toks) in proposals.items()
                if i in included and self._lanes[i].gen == gen
            }
            if not drafts:
                return None
            max_d = max(len(toks) for toks in drafts.values())
            w = bucket_for(max_d + 1, self._verify_widths)
            props = np.zeros((n, w - 1), np.int32)
            counts = np.zeros((n,), np.int32)
            for i, toks in drafts.items():
                d = min(len(toks), w - 1)
                props[i, :d] = toks[:d]
                counts[i] = d
            trash_row = np.zeros((self._table_width,), np.int32)
            tables = np.stack([
                self._lanes[i].table if i in included else trash_row
                for i in range(n)
            ])
            lens = np.array(
                [self._lanes[i].length if i in included else 0
                 for i in range(n)], np.int32,
            )
            temps = np.array(
                [self._lanes[i].temperature for i in range(n)], np.float32
            )
            topks = np.array(
                [self._lanes[i].top_k for i in range(n)], np.int32
            )
            self._lane_gauges_locked(active_count=len(active))
        return active, w, tables, lens, temps, topks, props, counts

    def _deliver_verified(self, ptick, active, vals, props, counts):
        """Stream one verify tick's accepted drafts + correction token
        per lane and advance the per-lane length/budget/adaptive-k
        bookkeeping (under _cv; the tick already completed on device)."""
        delivered = 0
        proposed = accepted = 0
        with ptick.phase("deliver"), self._cv:
            for slot_idx, gen in active:
                lane = self._lanes[slot_idx]
                if not lane.active or lane.gen != gen:
                    continue  # cancelled since dispatch: stale tick
                d = int(counts[slot_idx])
                acc = min(int(vals[0, slot_idx]), d)
                toks = [int(t) for t in props[slot_idx, :acc]]
                toks.append(int(vals[1, slot_idx]))
                if lane.spec is not None:
                    if d:
                        lane.spec.note(d, acc)
                    else:
                        lane.spec.note_plain()
                proposed += d
                accepted += acc
                for token in toks:
                    lane.queue.put(token)
                    lane.produced += 1
                    lane.tokens.append(token)
                    # the tick wrote K/V for this token's position; the
                    # first garbage (rejected) position becomes the next
                    # tick's write position — the rewind is this pointer
                    lane.length += 1
                    delivered += 1
                    if self.registry is not None:
                        self.registry.inc(
                            "ctpu_lm_tokens_total",
                            help_="Tokens streamed by the LM engine",
                        )
                    if (lane.produced >= lane.remaining
                            or (self.eos_id is not None
                                and token == self.eos_id)):
                        self._retire_lane_locked(lane)
                        break
            self._spec_proposed += proposed
            self._spec_accepted += accepted
            if proposed and self.registry is not None:
                self.registry.inc(
                    "ctpu_lm_spec_proposed_tokens_total", None,
                    value=proposed,
                    help_=LM_SPEC_HELP[
                        "ctpu_lm_spec_proposed_tokens_total"],
                )
                if accepted:
                    self.registry.inc(
                        "ctpu_lm_spec_accepted_tokens_total", None,
                        value=accepted,
                        help_=LM_SPEC_HELP[
                            "ctpu_lm_spec_accepted_tokens_total"],
                    )
                if proposed - accepted:
                    self.registry.inc(
                        "ctpu_lm_spec_rejected_tokens_total", None,
                        value=proposed - accepted,
                        help_=LM_SPEC_HELP[
                            "ctpu_lm_spec_rejected_tokens_total"],
                    )
                self.registry.set(
                    "ctpu_lm_spec_acceptance_rate", None,
                    round(
                        self._spec_accepted
                        / max(self._spec_proposed, 1), 4,
                    ),
                    help_=LM_SPEC_HELP["ctpu_lm_spec_acceptance_rate"],
                )
        self._account(ptick, delivered)

    def _account(self, ptick, delivered):
        """Fold the tokens a pass delivered, and the device time that
        completed since the last call, into the pass's profiler tick."""
        with self._cv:
            device_s, self._device_s = self._device_s, 0.0
        if delivered or device_s:
            ptick.compute("lm", delivered, self._flops_per_token,
                          device_s=device_s)

    def _log_tick(self, kind, t0, slots, result=None, lengths=None,
                  max_pos=None, reads=None, counted=(),
                  spans=(NULL_PHASE, NULL_PHASE), **fields):
        """Append one tick_trace() entry and return it.  *result* is an
        output of the program the tick dispatched at ``t0``: the
        completion observer fills in ``t_done`` and ``device_s`` when it
        lands.  *spans* are the dispatch's two closed phases, the upload
        and the program's call: their seconds are the entry's ``upload_s``
        and ``call_s`` (none from a disarmed profiler), and ``_tick_done``
        hands them to the profiler's stall rule.  *lengths* are the real
        lengths of the lanes the program
        worked on (the engine's own count): the entry carries their sum
        as ``context_tokens`` and, for a model with window layers, what
        of it a window holds as ``window_tokens``.  *max_pos* is the
        largest position the program asked from: the entry carries the
        width its attention read for it as ``attended_positions`` and that
        width over the lanes as ``attended_tokens``, or, from a family
        whose tick reads each lane to its own length, the largest and the
        sum of those *reads*.  *counted* holds, from a family whose programs
        count on the device, the vector they returned: it starts for the
        host here, beside the tokens, and ``_tick_done`` writes it into the
        entry under the family's names."""
        entry = {
            "kind": kind, "t0": t0, "t1": time.monotonic(), "lanes": slots,
            **fields,
        }
        if spans[0].seconds is not None:
            entry["upload_s"] = spans[0].seconds
            entry["call_s"] = spans[1].seconds
        for vector in counted:
            vector.copy_to_host_async()
        if lengths is not None:
            entry.update(self._tick_fields(kind, lengths, **fields))
            entry["context_tokens"] = int(sum(lengths))
            window = self._programs.window
            if window is not None:
                entry["window_tokens"] = int(
                    sum(min(int(n), window) for n in lengths)
                )
        if max_pos is not None:
            if not reads:
                reads = [self._programs.attended_positions(
                    max_pos, self._table_width)] * len(slots)
            entry["attended_positions"] = max(reads)
            entry["attended_tokens"] = sum(reads)
        with self._cv:
            entry["n_lanes"] = self._scaler.n_lanes
            self._tick_log.append(entry)
            if max_pos is not None and self.registry is not None:
                self.registry.set(
                    "ctpu_lm_attended_positions", None,
                    entry["attended_positions"],
                    help_=_LANE_HELP["ctpu_lm_attended_positions"],
                )
        if result is not None:
            self._observer.watch(
                result,
                functools.partial(self._tick_done, entry, counted, spans),
                t_dispatch_ns=int(t0 * 1e9),
            )
        return entry

    def _tick_done(self, entry, counted, spans, t_done_ns, device_ns,
                   _queue_ns):
        """Observer callback: the tick's device work has completed.  The
        scheduler's own read-back of a first token may have seen that
        before the observer's thread was given the interpreter: then the
        delivery bounds the completion.  What the program counted on the
        device is on the host by now (it set out with the tokens): into the
        entry, and into the series the family names.  Then the profiler's
        stall rule (``PhaseProfiler.settle``): ``host_pause_s``, the
        seconds of the device time in which the interpreter was held (this
        thread stamps the completion only once it is given the
        interpreter), and on a marked entry ``stall`` and ``stall_s``."""
        t_done, device_s = t_done_ns / 1e9, device_ns / 1e9
        try:
            values = [int(v) for vector in counted for v in np.asarray(vector)]
        except Exception:  # noqa: BLE001 - the device work failed (the
            values = []    # observer has logged it): times, and no counts
        with self._cv:
            for (field, series, kind, help_), value in zip(self._counters,
                                                           values):
                entry[field] = value
                if series is None or self.registry is None:
                    continue
                if kind == "counter":
                    self.registry.inc(series, None, value=value, help_=help_)
                else:
                    self.registry.set(series, None, value, help_=help_)
            late_s = max(t_done - entry.get("t_delivered", t_done), 0.0)
            entry["t_done"] = t_done = t_done - late_s
            entry["device_s"] = device_s = max(device_s - late_s, 0.0)
            self._device_s += device_s
            key = _price_key(entry)
            times = self._device_times.get(key)
            if times is None:
                times = self._device_times[key] = deque(
                    maxlen=AHEAD_PRICE_OVER)
            times.append(device_s)
        # completions land one at a time, on the observer's thread: the
        # one before this is this thread's to keep
        t_prev, self._t_done_prev = self._t_done_prev, t_done
        marks = self.prof.settle(entry, t_done, device_s, t_prev, *spans,
                                 inflight=len(self._inflight))
        if marks:
            with self._cv:
                entry.update(marks)

    def _price(self, entry):
        """A tick or chunk was dispatched: price its device time, which
        the next entry of _inflight completes, and write on a chunk's
        entry ``ahead_s``, the priced device time queued ahead of it."""
        queued = self._queued_s()
        with self._cv:
            times = self._device_times.get(_price_key(entry), ())
            price = (statistics.median(times)
                     if len(times) >= AHEAD_PRICE_NEEDS else None)
            if entry["kind"] == "prefill_chunk" and queued is not None:
                entry["ahead_s"] = queued
        if price is None or self._loose_s is None:
            self._loose_s = None
        else:
            self._loose_s += price

    def _enqueue(self, tokens, snapshot, first, takes):
        """A dispatched program's readback joins _inflight, with the
        priced device time that its completion completes."""
        self._inflight.append((tokens, snapshot, first, takes,
                               self._loose_s))
        self._loose_s = 0.0

    def _queued_s(self):
        """Priced device time dispatched and not yet read back, or None
        while a program in it has no price yet."""
        queued = self._loose_s
        for *_, price in self._inflight:
            if queued is None or price is None:
                return None
            queued += price
        return queued

    def _drain_ahead(self, ptick, ticked):
        """Read back after a pass: every result where no tick ran, down
        to ``readback_depth`` results, and then on while what would stay
        queued behind the oldest result is more device time than the
        host's cover: ``AHEAD_MARGIN`` times the ``AHEAD_COVER_AT``
        quantile of its last ``AHEAD_COVER_OVER`` passes.  What is left
        queued when the host leaves is thus more than the cover where
        enough was dispatched, and ahead of what the next pass
        dispatches at most the cover and one result."""
        cap = self.depth if ticked else 0
        while len(self._inflight) > cap:
            self._drain_one(ptick)
        queued = self._queued_s() if ticked else None
        if queued is None or len(self._host_s) < AHEAD_COVER_NEEDS:
            return  # no price or no cover yet: the cap alone
        passes = sorted(self._host_s)
        cover = AHEAD_MARGIN * passes[int(AHEAD_COVER_AT * (len(passes) - 1))]
        drained = False
        while self._inflight and queued - self._inflight[0][-1] > cover:
            queued -= self._inflight[0][-1]
            self._drain_one(ptick)
            drained = True
        if self.registry is not None:
            if drained:
                self.registry.inc(
                    "ctpu_lm_ahead_drains_total",
                    help_=_LANE_HELP["ctpu_lm_ahead_drains_total"])
            self.registry.set("ctpu_lm_ahead_seconds", None, queued,
                              help_=_LANE_HELP["ctpu_lm_ahead_seconds"])

    def _drain_one(self, ptick=NULL_TICK):
        tokens_dev, snapshot, first, takes, _ = self._inflight.popleft()
        with ptick.phase("device_wait"):
            # the host-side materialization is where async dispatch pays:
            # this np.asarray blocks until the tick's device work lands
            t_wait = time.monotonic()
            vals = np.asarray(tokens_dev)
            self._waited_s += time.monotonic() - t_wait
        delivered = 0
        with ptick.phase("deliver"), self._cv:
            for k, (slot_idx, gen) in enumerate(snapshot):
                lane = self._lanes[slot_idx]
                if not lane.active or lane.gen != gen:
                    continue  # cancelled/finished lane: stale tick token
                if takes is None:
                    # full ticks carry one token PER LANE (index by slot);
                    # single-lane prefill entries carry exactly one value
                    tokens = (int(vals[slot_idx]) if vals.size > 1
                              else int(vals.reshape(-1)[0]),)
                else:
                    # a block's tokens, in position order, from the tick
                    # that removed its last mask; none from any other
                    tokens, fixed = self._programs.delivered(
                        vals[slot_idx], takes[k])
                    lane.fixed.extend(fixed)
                for token in tokens:
                    if first is not None:
                        # stamped before the put: the consumer may run, and
                        # send the token off, before this thread runs again
                        first["t_delivered"] = time.monotonic()
                    lane.queue.put(token)
                    lane.produced += 1
                    lane.tokens.append(token)  # recompute-replay history
                    delivered += 1
                    if self.registry is not None:
                        self.registry.inc(
                            "ctpu_lm_tokens_total",
                            help_="Tokens streamed by the LM engine",
                        )
                    if (lane.produced >= lane.remaining
                            or (self.eos_id is not None
                                and token == self.eos_id)):
                        self._retire_lane_locked(lane)
                        break  # what the block holds past the budget drops
        self._account(ptick, delivered)

    # -- preemption / swap -------------------------------------------------

    def _preempt_step(self):
        """Swap the victim _admit chose out to the host store (or drop
        its KV for recompute when the store is full).  Scheduler thread;
        every device copy runs OUTSIDE _cv."""
        # deliver every dispatched token first so the swap record's
        # counters (produced/length) and the lane arrays' token/RNG carry
        # describe one consistent preemption point
        while self._inflight:
            self._drain_one()
        with self._cv:
            decision, self._preempt = self._preempt, None
            if decision is None or self._closed:
                return
            slot, gen = decision
            lane = self._lanes[slot]
            if not lane.active or lane.gen != gen:
                return  # completed or cancelled since the decision
            written_blocks = -(-lane.length // self.block_size)
            blocks = [int(b) for b in lane.table[:written_blocks]]
            n_blocks = len(lane.blocks)
            use_swap = (
                self._swapped_blocks + written_blocks
                <= self.swap_block_limit
            )
        # device -> host gather outside the lock: scheduler-thread
        # dispatch order guarantees every write to these blocks was
        # issued before this read, and nobody re-allocates them until
        # the release below
        host = self.kv.read_blocks(blocks) if use_swap else None
        token = np.asarray(self._tokens)[slot].copy()
        key = np.asarray(self._keys)[slot].copy()
        with self._cv:
            lane = self._lanes[slot]
            if self._closed or not lane.active or lane.gen != gen:
                return  # raced with cancel/close: drop the copies
            entry = _Swapped(
                lane, n_blocks, written_blocks, token, key, host
            )
            self._swapped.append(entry)
            if entry.handle is not None:
                entry.handle.placed = entry
            if use_swap:
                self._swapped_blocks += written_blocks
            self._preemptions += 1
            if self.registry is not None:
                self.registry.inc(
                    "ctpu_lm_preemptions_total", None,
                    help_=LM_PREFIX_HELP["ctpu_lm_preemptions_total"],
                )
            if self.flight is not None:
                self.flight.note(
                    "lm_preemption", slot=slot, tenant=lane.tenant,
                    swapped=bool(use_swap), blocks=written_blocks,
                )
            self._swap_gauge_locked()
            # pause, don't end: the stream's queue stays open
            self._retire_lane_locked(lane, close_queue=False)

    def _resume_step(self):
        """Swap one parked stream back in when a free lane + blocks
        exist and no queued request outranks it (otherwise the blocks a
        preemption just freed would thrash straight back to the stream
        it preempted)."""
        plan = None
        with self._cv:
            if (self._closed or not self._swapped or self._job is not None
                    or self._preempt is not None):
                return
            n_lanes = self._scaler.n_lanes
            slot = next(
                (i for i in range(n_lanes) if not self._lanes[i].active),
                None,
            )
            if slot is None:
                return
            queued_pri = None
            for tenant, dq in self._pending.items():
                if dq:
                    pri = self._priority_of(tenant)
                    queued_pri = (
                        pri if queued_pri is None else max(queued_pri, pri)
                    )
            order = sorted(
                range(len(self._swapped)),
                key=lambda i: (
                    -self._priority_of(self._swapped[i].tenant), i,
                ),
            )
            for i in order:
                entry = self._swapped[i]
                if entry.cancelled:
                    continue  # cancel() removes eagerly; belt and braces
                if (queued_pri is not None
                        and self._priority_of(entry.tenant) < queued_pri):
                    continue
                if entry.host is not None:
                    row = entry.prompt[0]
                    cap = min(entry.prompt_len // self.block_size,
                              entry.written_blocks)
                else:
                    # recompute: the replay chain is prompt + delivered
                    # tokens, so cached generated-token blocks match too
                    row = self._written_row(entry)
                    cap = (len(row) - 1) // self.block_size
                matched_blocks, matched_nodes = [], []
                if self.prefix is not None and cap:
                    matched_blocks, matched_nodes = self.prefix.match(
                        row, cap
                    )
                    self.prefix.adopt(matched_nodes)
                fresh = self._reserve_locked(entry.n_blocks, matched_blocks)
                if fresh is None:
                    if matched_blocks:
                        self.kv.release(matched_blocks)
                    continue
                self._swapped.pop(i)
                blocks = matched_blocks + fresh
                table = np.full(
                    (self._table_width,), KvBlockPool.TRASH, np.int32
                )
                table[:len(blocks)] = blocks
                if entry.host is None:
                    pseudo = row[None, :].astype(np.int32)
                    handle = _Handle(
                        pseudo, entry.limit - entry.length, entry.queue,
                        entry.tenant, entry.temperature, entry.top_k, 0,
                    )
                    job = _PrefillJob(
                        handle, slot, blocks, table,
                        chunk_plan(
                            len(row), self.buckets,
                            start=len(matched_blocks) * self.block_size,
                        ),
                        None,
                    )
                    job.resume = entry
                    self._job = job  # _prefill_step replays from here
                    return
                plan = (entry, slot, blocks, len(matched_blocks), table,
                        entry.host)
                break
        if plan is None:
            return
        entry, slot, blocks, n_matched, table, host = plan
        # restore the written, non-adopted blocks from the host store
        dst = blocks[n_matched:entry.written_blocks]
        if dst:
            self.kv.write_blocks(dst, host, first=n_matched)
        with self._cv:
            if self._closed or entry.cancelled:
                # the stream died while restoring: unwind the reservation.
                # host is the plan-local reference — cancel may have
                # nulled the entry's.
                if self._closed:
                    # _release_all_locked already zeroed _swapped_blocks
                    # (and cleared the cache), so no gauge decrement here.
                    # The entry was popped from _swapped BEFORE close ran,
                    # so close's sweep missed its queue: close it here or
                    # the consumer blocks on q.get() forever.
                    if not entry.cancelled:
                        entry.cancelled = True
                        entry.queue.put(_CLOSE)
                    self.kv.release(blocks)
                else:
                    self._release_blocks_locked(
                        entry.prompt, entry.length, blocks
                    )
                    self._swapped_blocks -= entry.written_blocks
                    self._swap_gauge_locked()
                return
            lane = self._lanes[slot]
            lane.gen += 1
            lane.active = True
            lane.table[:] = table
            lane.blocks = blocks
            self._restore_lane_locked(lane, entry, slot)
            self._swapped_blocks -= entry.written_blocks
            self._swap_gauge_locked()
            self._lane_gauges_locked()
        # install the saved next-tick input token + RNG carry (scheduler
        # thread: the next decode pass dispatches strictly after this)
        self._tokens, self._keys = self._adopt(
            self._tokens, self._keys, jnp.int32(slot),
            jnp.asarray(entry.token), jnp.asarray(entry.key),
        )

    def _loop(self):
        try:
            self._loop_inner()
        except Exception as exc:
            # a dying scheduler must never strand consumers on q.get()
            with self._cv:
                self._release_all_locked()
                self._closed = True
            # an engine wedge is the flagship flight-recorder anomaly:
            # capture the ring (recent ticks, spans, preemptions) NOW —
            # the postmortem must not depend on tracing having been on
            flight = self.flight
            if flight is not None:
                flight.note("lm_engine_wedge", error=repr(exc))
                flight.dump("lm_engine_wedge")
            raise

    def _loop_inner(self):
        while True:
            # every pass is one profiler tick; finish-in-finally is the
            # bracket shape the SPAN-LEAK lint demands, so a pass that
            # dies still commits the phases it measured before wedging
            tick = self.prof.start_tick("sched")
            self._ptick = tick
            try:
                alive = self._loop_pass(tick)
            finally:
                self._ptick = NULL_TICK
                self.prof.finish(tick)
            if not alive:
                break
        # shutdown: drop the in-flight tail (queues already closed)
        self._inflight.clear()

    def _loop_pass(self, ptick):
        """One scheduler pass (the former _loop_inner body); returns
        False when the engine closed and the loop must stop."""
        t_pass, self._waited_s = time.monotonic(), 0.0
        if self._preempt is not None:
            with ptick.phase("preempt"):
                self._preempt_step()  # device copies outside _cv
        if self._swapped:
            with ptick.phase("resume"):
                self._resume_step()
        with ptick.phase("schedule"):
            self._admit()  # takes/releases _cv itself; no dispatch inside
        worked = False
        if self._job is not None:
            # brackets its own phases, as _decode_pass and _spec_pass do:
            # build / upload / the program's call / record
            self._prefill_step(ptick)  # ONE chunk, outside _cv
            ptick.relabel("prefill")
            worked = True
        verified = False
        if self._spec is not None:
            # _spec_pass brackets its own phases (draft / verify_dispatch
            # / device_wait / deliver); False falls through to the plain
            # decode tick — the never-slower path
            verified = self._spec_pass(ptick)
        tick = None
        if not verified:
            tick = self._decode_pass(ptick)  # ONE decode tick, outside _cv
        ticked = verified or tick is not None
        if ticked:
            ptick.relabel("verify" if verified else "decode")
        worked = worked or ticked
        with self._cv:
            if self._closed:
                return False
        self._drain_ahead(ptick, ticked)
        if tick is not None:
            with self._cv:
                tick["inflight"] = len(self._inflight)
        if worked:
            self._host_s.append(
                time.monotonic() - t_pass - self._waited_s)
        if not worked and not self._inflight:
            with self._cv:
                if self._closed:
                    return False
                # swapped streams deliberately DON'T block the wait:
                # an unresumable one (blocks pinned) retries on the
                # 50ms tick instead of busy-spinning the loop
                if (not self._queued_locked()
                        and self._job is None
                        and not any(l.active for l in self._lanes)):
                    ptick.relabel("idle")
                    with ptick.phase("idle"):
                        self._cv.wait_for(
                            lambda: (self._closed
                                     or self._job is not None
                                     or self._queued_locked()
                                     or any(l.active
                                            for l in self._lanes)),
                            timeout=0.05,
                        )
        return True
