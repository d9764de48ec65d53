"""Cross-request dynamic micro-batching for stateless batchable models.

TPU-first rationale: the MXU wants large batched matmuls/convs, and every
device round trip (H2D, dispatch, D2H) carries fixed latency — per-request
execution pays that latency per request, a batcher pays it per *batch*.  This
is the server-side analog of the dynamic batcher in the reference's server
ecosystem (the client-side reference exposes it via model config
``dynamic_batching``; model_parser.h:59-193 normalizes scheduler kinds), built
the XLA way: batches are padded to power-of-two buckets so every batch size
hits an already-compiled executable instead of triggering a retrace.

Eligibility: stateless, non-decoupled models with ``max_batch_size > 1`` and
host-resident (wire) inputs.  Shared-memory requests keep the direct
zero-copy path — batching them would force device→host materialization.
"""

import functools
import sys
import threading
import time
from collections import deque

import numpy as np

from client_tpu.serve._completion import CompletionObserver
from client_tpu.serve.prof import annotation
from client_tpu.utils import InferenceServerException


def _bucket(n, cap):
    """Smallest bucket >= n from {2^k, 3*2^k}, capped at cap.

    The 1.5x intermediate sizes keep worst-case padding waste to 33% instead
    of 100% while the bucket count (and so the compile count) stays O(log n).
    """
    b = 1
    while b < n:
        if b * 3 // 2 >= n and b >= 2:
            b = b * 3 // 2
            break
        b *= 2
    return min(b, cap)


def _buckets_up_to(cap):
    """All bucket sizes warmup must cover, ending exactly at cap."""
    out = []
    b = 1
    while b < cap:
        out.append(b)
        if b >= 2 and b * 3 // 2 < cap:
            out.append(b * 3 // 2)
        b *= 2
    out.append(cap)
    return sorted(set(out))


def _is_device_array(arr):
    """jax.Array check without importing jax on the host-only path."""
    jax = sys.modules.get("jax")
    return jax is not None and isinstance(arr, jax.Array)


def _device_batch(parts, padded):
    """Assemble a padded device batch with a *bounded executable set*.

    ``jnp.concatenate`` over a variable part count compiles one executable per
    (arity, row-split) combination — exactly the rows-vary-per-window
    pathology dynamic batching creates, and a full-size model's executable
    costs tens of seconds to compile (resnet50 on a v5e: chip_smoke.py
    reports it).  Instead: allocate the pre-zeroed bucket buffer (one executable
    per bucket; the zero fill doubles as padding) and lay each part in with
    ``dynamic_update_slice`` at a *runtime* offset — one executable per
    (bucket, part-row-count), independent of group composition, all covered
    by warmup.
    """
    from jax import lax

    import jax.numpy as jnp

    buf = jnp.zeros((padded,) + tuple(parts[0].shape[1:]), parts[0].dtype)
    zero_tail = (0,) * (parts[0].ndim - 1)
    offset = 0
    for p in parts:
        buf = lax.dynamic_update_slice(buf, p, (offset,) + zero_tail)
        offset += int(p.shape[0])
    return buf


def _fused_group_fn(model_fn):
    """One jitted callable serving every device-group composition: concat the
    parts, run the forward, split the outputs back per part — inside a single
    XLA program, so a K-request group costs exactly ONE dispatch and zero
    per-request eager ops.  jax.jit retraces per (arity, row-split) pytree —
    single-row parts (the perf-client shape) dominate, so the executable set
    stays tiny and warmup covers it.  Requires a jax-pure model fn
    (``Model.fused_batching``)."""
    import jax

    def fused(parts):
        import jax.numpy as jnp

        batched = {
            name: jnp.concatenate(list(ps), axis=0) if len(ps) > 1 else ps[0]
            for name, ps in parts.items()
        }
        out = model_fn(batched, {}, None)
        # reserved response-params key: a traced fn's dict would be a
        # trace-time constant (stale across calls) and jnp.split chokes on
        # it — fused models cannot set per-response parameters; drop it
        if isinstance(out, dict):
            out.pop("__parameters__", None)
        sizes = [int(p.shape[0]) for p in next(iter(parts.values()))]
        offs = list(np.cumsum(sizes[:-1]))
        return {
            name: tuple(jnp.split(arr, offs, axis=0)) if offs else (arr,)
            for name, arr in out.items()
        }

    return jax.jit(fused)


def _device_split(arr, offset, rows):
    """One request's row slice, executable set bounded per (shape, rows):
    ``dynamic_slice`` with a runtime offset — basic ``arr[a:b]`` slicing
    would compile one executable per distinct offset."""
    from jax import lax

    sizes = (rows,) + tuple(arr.shape[1:])
    return lax.dynamic_slice(arr, (offset,) + (0,) * (arr.ndim - 1), sizes)


class _Pending:
    __slots__ = ("inputs", "rows", "signature", "event", "result", "error",
                 "t_enq", "trace", "tenant", "weight", "vfinish")

    def __init__(self, inputs, rows, signature, trace=None, tenant="",
                 weight=1.0):
        self.inputs = inputs
        self.rows = rows
        self.signature = signature
        self.event = threading.Event()
        self.result = None
        self.error = None
        self.t_enq = time.monotonic_ns()
        self.trace = trace  # optional RequestTrace (queue/compute events)
        self.tenant = tenant  # fair-queue lane (see _FairQueue)
        self.weight = max(float(weight), 1e-3)
        self.vfinish = 0.0  # virtual finish time, stamped at push


class _FairQueue:
    """Weighted-fair queue over per-tenant FIFO lanes.

    The batcher's old single FIFO serves a flooding tenant's backlog ahead
    of everyone who arrived later — arrival order IS the schedule.  Here
    each request is stamped a *virtual finish time* on push
    (``max(vclock, lane_last_finish) + rows / weight``, the classic
    start-time fair queueing recurrence) and :meth:`pop` always takes the
    earliest stamp across lane heads: a tenant's burst deepens only its
    own lane, and service converges to the weight ratio regardless of
    arrival order.  Within one lane order stays FIFO.

    Not internally locked — the batcher's ``_cond`` guards every call.
    """

    __slots__ = ("_lanes", "_last_vfinish", "_vclock", "_len")

    def __init__(self):
        self._lanes = {}  # tenant -> deque of _Pending
        self._last_vfinish = {}  # tenant -> last stamped vfinish
        self._vclock = 0.0
        self._len = 0

    def __len__(self):
        return self._len

    def push(self, pending):
        lane = self._lanes.get(pending.tenant)
        if lane is None:
            lane = deque()
            self._lanes[pending.tenant] = lane
        start = max(
            self._vclock, self._last_vfinish.get(pending.tenant, 0.0)
        )
        pending.vfinish = start + max(pending.rows, 1) / pending.weight
        self._last_vfinish[pending.tenant] = pending.vfinish
        lane.append(pending)
        self._len += 1

    def pop(self):
        """Remove and return the entry with the earliest virtual finish
        time (caller guarantees non-empty)."""
        best_tenant, best = None, None
        for tenant, lane in self._lanes.items():
            head = lane[0]
            if best is None or head.vfinish < best.vfinish:
                best_tenant, best = tenant, head
        self._remove(best_tenant, 0)
        self._vclock = max(self._vclock, best.vfinish)
        return best

    def take_first(self, pred):
        """Remove and return the fair-order-first entry matching *pred*
        (the batch fold-in scan), or None.  Per lane only the earliest
        match is a candidate — lane order stays FIFO."""
        best_tenant, best_i, best = None, None, None
        for tenant, lane in self._lanes.items():
            for i, pending in enumerate(lane):
                if pred(pending):
                    if best is None or pending.vfinish < best.vfinish:
                        best_tenant, best_i, best = tenant, i, pending
                    break
        if best is None:
            return None
        self._remove(best_tenant, best_i)
        return best

    def _remove(self, tenant, index):
        lane = self._lanes[tenant]
        del lane[index]
        self._len -= 1
        if not lane:
            del self._lanes[tenant]
        if not self._lanes:
            # busy period over: forget per-tenant stamps so the map cannot
            # grow without bound across tenant churn (vclock memory only
            # matters while requests are queued)
            self._last_vfinish.clear()
            self._vclock = 0.0

    def depths(self):
        """{tenant: queued count} (/metrics per-tenant queue gauge)."""
        return {tenant: len(lane) for tenant, lane in self._lanes.items()}

    def drain(self):
        """Remove and return every queued entry (shutdown/failure paths)."""
        out = [p for lane in self._lanes.values() for p in lane]
        self._lanes.clear()
        self._last_vfinish.clear()
        self._vclock = 0.0
        self._len = 0
        return out


class ModelBatcher:
    """One background batcher per model: gathers concurrent requests into a
    single padded forward pass and splits the host-materialized outputs."""

    def __init__(self, model, stats, max_queue_delay_s=0.003, busy=None,
                 pipeline_depth=4, max_queue_depth=None, registry=None,
                 prof=None):
        self.model = model
        self.stats = stats
        self._busy = busy  # engine BusyTracker (duty-cycle metric), optional
        self._registry = registry  # engine metrics Registry (shed counters)
        self.prof = prof  # engine PhaseProfiler: one "batch" tick per group
        # the loop's brackets: with a profiler they are phases its pulse
        # watches (one open past prof.STALL_S is a stall record, but the
        # gather, which waits by design), without one bare annotations
        self._span = prof.span if prof is not None else annotation
        self.max_batch = max(int(model.max_batch_size), 1)
        self.max_queue_delay_s = max_queue_delay_s
        # Admission control: requests beyond this queue depth are shed with
        # a retryable 503 instead of growing the queue (and the tail
        # latency) without bound.  None = unbounded.
        self.max_queue_depth = max_queue_depth
        # Device groups with a jax-pure fn fuse concat+forward+split into ONE
        # jitted dispatch (see _fused_jit); arity is capped so the executable
        # set stays warmable.
        self._fused = None
        self.max_fused_arity = int(
            getattr(model, "max_fused_arity", 8) or 8
        )
        # Dispatch/completion are decoupled: the batcher thread only gathers
        # and issues batches; completion waits run off the dispatch path, so
        # the next batch's gather and H2D overlap the current batch's device
        # time instead of queueing behind its completion wait.  (The depths
        # were tuned against a device behind a ~100 ms link; not re-measured
        # on a local chip.)  Two populations, two backpressure regimes:
        #  - HOST (wire) groups hold full tensor copies host-side and end in
        #    a real batch-wide D2H, so a small completion pool + semaphore
        #    (pipeline_depth) bounds memory while keeping transfers streaming.
        #  - DEVICE (TPU-shm) groups hold only HBM references; acks are
        #    dispatch-time by contract, so throttling dispatch to the
        #    completion-OBSERVATION rate would cap throughput at
        #    depth / observation latency.  They get a deep semaphore purely
        #    as a runaway bound, and one FIFO watcher thread that collapses a
        #    completion backlog into a single block_until_ready (a device
        #    stream executes dispatches in order, so the newest result
        #    completing implies every older one did).
        self.pipeline_depth = max(int(pipeline_depth), 1)
        self.device_pipeline_depth = max(self.pipeline_depth, 64)
        self._sem = threading.Semaphore(self.pipeline_depth)
        self._sem_device = threading.Semaphore(self.device_pipeline_depth)
        self._observer = CompletionObserver(
            name=f"batcher-{model.name}-watch"
        )
        # Host completions run real work (batch D2H + row split) on daemon
        # worker threads consuming _host_q; daemon so a wedged device call
        # can never hang interpreter exit, bounded-waited in close().
        self._host_cv = threading.Condition()
        self._host_q = deque()
        self._host_threads = []
        self._host_outstanding = 0
        # Workers exit on _host_closed, set only AFTER the batcher thread is
        # joined: the batcher keeps dispatching its remaining queue after
        # _closed, and a worker exiting early on a momentarily-empty queue
        # would strand those late batches (clients blocked forever).
        self._host_closed = False
        self._inflight = 0  # dispatched, completion pending (under _cond)
        self._cond = threading.Condition()
        # Weighted-fair queue across tenant lanes (one lane per tenant;
        # submit() stamps tenant + weight) — replaces the single FIFO so a
        # flooding tenant's backlog cannot schedule ahead of everyone else.
        self._queue = _FairQueue()
        # Requests popped off the queue but not yet completed/failed (gathered
        # group + the in-flight pipelined batch).  Tracked so the _loop
        # BaseException handler can fail them too — otherwise a KeyboardInterrupt
        # /MemoryError between _gather and _fail strands those waiters forever.
        self._active = set()
        self._closed = False
        self._thread = threading.Thread(
            target=self._loop, name=f"batcher-{model.name}", daemon=True
        )
        self._thread.start()

    def _use_fused(self):
        return bool(getattr(self.model, "fused_batching", False))

    def _fused_jit(self):
        # memoized under _cond: warmup (caller thread) and the batcher
        # loop both reach this — an unguarded rebind races them
        with self._cond:
            if self._fused is None:
                self._fused = _fused_group_fn(self.model.fn)
            return self._fused

    def warmup(self, input_specs):
        """Pre-compile every padded bucket (the reference's ``model_warmup``
        analog) so no client request ever pays a compile.  Covers both group
        populations: the wire path (host-array forward per bucket) and the
        device/TPU-shm path (bucket-buffer assembly from single-row parts,
        forward, and per-request output split) — an unwarmed executable
        costs its whole compile at request time.  Skipped for models with
        dynamic non-batch dims."""
        from client_tpu.utils import triton_to_np_dtype

        shapes = {}
        for spec in input_specs:
            dims = list(spec.dims)
            if any(d < 0 for d in dims[1:]):
                return
            np_dtype = triton_to_np_dtype(spec.datatype)
            if np_dtype is None or np_dtype == np.object_:
                return
            shapes[spec.name] = (dims[1:], np_dtype)
        buckets = _buckets_up_to(self.max_batch)
        import jax

        for b in buckets:
            zeros = {
                name: np.zeros([b] + dims, dtype=np_dtype)
                for name, (dims, np_dtype) in shapes.items()
            }
            jax.device_get(self.model.fn(zeros, {}, None))
        if not getattr(self.model, "batch_device_inputs", False):
            return
        # Device-group pass: single-row parts are what concurrent perf
        # clients send.  The rows are committed to the device explicitly —
        # TPU-shm region arrays arrive committed, and committedness is part
        # of the jit cache key: an uncommitted warmup would leave every
        # serving-time signature cold (retrace + executable reload).
        dev = jax.devices()[0]
        row = {
            name: jax.device_put(np.zeros([1] + dims, dtype=np_dtype), dev)
            for name, (dims, np_dtype) in shapes.items()
        }
        if self._use_fused():
            # one compile per (arity, part-rows): groups of k single-row
            # requests (the concurrency-sweep shape) plus k-part groups of
            # the batched-client row sizes (reference perf_analyzer -b
            # 8/32).  Larger rows cap arity at max_batch//rows, so the
            # extra row sizes add only a handful of executables.
            for rows in (1, 8, 32):
                if rows > self.max_batch:
                    continue
                part = {
                    name: jax.device_put(
                        np.zeros([rows] + dims, dtype=np_dtype), dev
                    )
                    for name, (dims, np_dtype) in shapes.items()
                }
                max_k = min(self.max_fused_arity, self.max_batch // rows)
                for k in range(1, max_k + 1):
                    parts = {name: (p,) * k for name, p in part.items()}
                    out = self._fused_jit()(parts)
                    jax.block_until_ready(out)
            return
        # eager assembly path: per bucket warm (zeros-buffer + one-row
        # dynamic_update_slice) assembly, the forward on an assembled
        # buffer, and the one-row output split.
        for b in buckets:
            batched = {
                name: _device_batch([part], b) for name, part in row.items()
            }
            result = self.model.fn(batched, {}, None)
            for arr in result.values():
                if _is_device_array(arr) and arr.shape and arr.shape[0] == b:
                    _device_split(arr, 0, 1).block_until_ready()

    # -- request side -----------------------------------------------------

    def queue_depth(self):
        """Requests currently waiting in the queue (/metrics gauge)."""
        with self._cond:
            return len(self._queue)

    def queue_depths_by_tenant(self):
        """{tenant: queued count} (/metrics per-tenant queue gauge)."""
        with self._cond:
            return self._queue.depths()

    def submit(self, inputs, trace=None, tenant="", weight=1.0):
        """Block until the batched execution finishes; return this request's
        slice of the outputs — host numpy arrays for wire groups, live device
        slices for device (TPU-shm) groups.  ``tenant``/``weight`` select
        and weight the fair-queue lane this request waits in."""
        rows = _leading_rows(inputs)
        # Device-resident requests batch with the jnp path (concat + split on
        # device, no transfers) and must never mix with host groups — the
        # signature's device flag keeps the populations apart.
        device = all(_is_device_array(a) for a in inputs.values())
        signature = (device,) + tuple(
            (name, arr.dtype.str, tuple(arr.shape[1:]))
            for name, arr in sorted(inputs.items())
        )
        if device and self._use_fused():
            # fused jit retraces per (arity, row-split): mixing row counts in
            # one group would hit signatures warmup never compiled (seconds
            # of cold XLA compile on the request path) — groups stay
            # row-uniform so every composition is a warmed executable
            signature += (rows,)
        pending = _Pending(inputs, rows, signature, trace, tenant=tenant,
                           weight=weight)
        with self._cond:
            if self._closed:
                raise InferenceServerException(
                    f"model '{self.model.name}' is shutting down", status="500"
                )
            if (
                self.max_queue_depth is not None
                and len(self._queue) >= self.max_queue_depth
            ):
                # Retryable overload: the client's retry policy backs off
                # and re-submits once the queue drains (503 == UNAVAILABLE
                # on the gRPC frontend).
                # one consistent label set across the family: the _admit
                # sheds (overload/draining) carry only {reason}, so no
                # model label here either — a by-model aggregation would
                # silently split the family otherwise
                if self._registry is not None:
                    self._registry.inc(
                        "ctpu_requests_shed_total",
                        {"reason": "queue_full"},
                        help_="Requests shed with a retryable 503",
                    )
                raise InferenceServerException(
                    f"model '{self.model.name}' queue is full "
                    f"({len(self._queue)} >= {self.max_queue_depth} queued); "
                    "retry after backoff",
                    status="503",
                )
            self._queue.push(pending)
            self._cond.notify()
        pending.event.wait()
        if pending.error is not None:
            raise pending.error
        return pending.result

    def close(self, shutdown_timeout_s=30.0):
        # One deadline budget shared across every shutdown phase (batcher
        # join, host-completion drain, observer close) — three independent
        # 30s waits made worst-case close() take 90s; the caller's budget
        # now bounds the whole shutdown.
        deadline = time.monotonic() + shutdown_timeout_s
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=max(deadline - time.monotonic(), 0.0))
        # Host completion tasks for batches already dispatched should finish
        # before leftovers are failed — their requests are _active, not
        # queued.  Bounded: a task wedged on a stalled device must not hang
        # close() (the workers are daemon threads; queued requests still get
        # their shutdown error below).
        with self._host_cv:
            self._host_closed = True
            self._host_cv.notify_all()
            while self._host_outstanding or self._host_q:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._host_cv.wait(timeout=remaining)
        self._observer.close(timeout=max(deadline - time.monotonic(), 0.0))
        # Fail anything still queued.  Drained under the lock so a batcher
        # thread that outlived the join timeout (e.g. blocked in a cold
        # compile) cannot race the queue; items it already popped are its to
        # complete, items still queued are ours to fail.
        with self._cond:
            leftovers = self._queue.drain()
        for p in leftovers:
            p.error = InferenceServerException("server shutdown", status="500")
            p.event.set()

    # -- batcher thread ----------------------------------------------------

    def _loop(self):
        try:
            self._run()
        except BaseException:  # noqa: BLE001 - a dead batcher must not strand waiters
            with self._cond:
                self._closed = True
                leftovers = self._queue.drain() + [
                    p for p in self._active if not p.event.is_set()
                ]
                self._active.clear()
            err = InferenceServerException(
                f"model '{self.model.name}' batcher thread died", status="500"
            )
            for p in leftovers:
                p.error = err
                p.event.set()
            raise

    def _run(self):
        # Pipelined dispatch: the batcher thread gathers and issues batches;
        # completion waits run elsewhere (pool for host groups, FIFO watcher
        # for device groups), so the H2D stream keeps flowing while earlier
        # batches' completions are in flight.
        while True:
            with self._span("batch.gather"):
                group = self._gather()
            if group is None:
                return
            device = group[0].signature[0]
            sem = self._sem_device if device else self._sem
            # Backpressure: block while the pipeline is full.  The queue
            # keeps filling meanwhile, and _topup folds those arrivals into
            # this batch — depth and batch size grow together under load.
            sem.acquire()
            with self._span("batch.dispatch"):
                self._topup(group)
                dispatched = self._dispatch(group)
            if dispatched is None:
                sem.release()
                continue
            with self._cond:
                self._inflight += 1
            if device:
                with self._span("batch.handoff"):
                    arrays = self._handoff_device(*dispatched)
                if arrays is None:  # handoff failed; group already notified
                    if self._busy is not None:
                        self._busy.end()
                    self._finish_one(sem)
                else:
                    acked, _, rows, t0, t_in = dispatched  # acked above
                    self._observer.watch(
                        arrays,
                        functools.partial(
                            self._device_done, sem, rows, t0, t_in
                        ),
                        on_error=lambda exc, n=len(acked): (
                            self.stats.record_device_failure(n)
                        ),
                        t_dispatch_ns=t_in,
                    )
            else:
                self._submit_host(dispatched)

    def _device_done(self, sem, rows, t0, t_in, _t_done, device_ns,
                     queue_ns):
        """Observer callback: a device batch's results actually landed.
        Its device time is known only now (serve/_completion.py), so this
        is where compute_infer_ns and the profiler's tick are recorded;
        the counts went in at hand-off, with the ack."""
        if self._busy is not None:
            self._busy.end()
        self.stats.record_device_time(device_ns or 0)
        self._prof_commit(rows, t0, t_in, device_ns or 0, 0,
                          queue_ns=queue_ns or 0)
        self._finish_one(sem)

    def _finish_one(self, sem):
        with self._cond:
            self._inflight -= 1
            # wake a _gather waiting out its peer-delay: with nothing in
            # flight the delay no longer buys anything
            self._cond.notify_all()
        sem.release()

    # -- host-group completion workers --------------------------------------

    def _submit_host(self, dispatched):
        with self._host_cv:
            self._host_q.append(dispatched)
            self._host_threads = [
                t for t in self._host_threads if t.is_alive()
            ]
            if len(self._host_threads) < self.pipeline_depth:
                t = threading.Thread(
                    target=self._host_loop,
                    name=f"batcher-{self.model.name}-done",
                    daemon=True,
                )
                self._host_threads.append(t)
                t.start()
            self._host_cv.notify()

    def _host_loop(self):
        # one guard per pass (the BG-THREAD-CRASH shape): an escaped
        # exception would kill this completion worker silently and
        # strand every group queued behind it
        while True:
            try:
                if not self._host_once():
                    return
            except Exception:
                pass

    def _host_once(self):
        """Complete one dispatched host group; False once closed and
        drained (the outstanding/semaphore accounting is exception-safe
        either way)."""
        with self._host_cv:
            while not self._host_q and not self._host_closed:
                self._host_cv.wait()
            if not self._host_q:
                self._host_cv.notify_all()  # wake the close() waiter
                return False
            dispatched = self._host_q.popleft()
            self._host_outstanding += 1
        try:
            self._complete_host(*dispatched)
        finally:
            with self._host_cv:
                self._host_outstanding -= 1
                self._host_cv.notify_all()
            self._finish_one(self._sem)
        return True

    def _drain_compatible_locked(self, group, first, rows, max_arity):
        """Fold queued signature-compatible requests into *group* (no wait),
        taken in fair-queue order so the fold-in cannot become a side door
        around the weighted-fair schedule.  Caller holds self._cond.
        Returns the updated row count."""
        while rows < self.max_batch and len(group) < max_arity:
            taken = self._queue.take_first(
                lambda p, rows=rows: (
                    p.signature == first.signature
                    and rows + p.rows <= self.max_batch
                )
            )
            if taken is None:
                break
            self._active.add(taken)
            group.append(taken)
            rows += taken.rows
        return rows

    def _max_arity(self, first):
        # Fused device groups cap the part count so the (arity,
        # row-split)-keyed executable set stays small and warmable.
        return (
            self.max_fused_arity
            if first.signature[0] and self._use_fused()
            else self.max_batch
        )

    def _gather(self):
        """Take the oldest request and fold in signature-compatible peers.

        Batch-while-busy: the timed max_queue_delay wait for peers only
        happens while at least one batch is dispatched-but-incomplete — an
        idle pipeline dispatches immediately, so low-concurrency requests pay
        zero artificial queue delay (the reference's fixed-delay scheduler
        charges it unconditionally; this is the latency/throughput-optimal
        variant: delay only when the delay is hidden by in-flight work)."""
        with self._cond:
            while not self._queue:
                if self._closed:
                    return None
                self._cond.wait()
            first = self._queue.pop()
            self._active.add(first)
            group = [first]
            max_arity = self._max_arity(first)
            rows = self._drain_compatible_locked(
                group, first, first.rows, max_arity
            )
            deadline = time.monotonic() + self.max_queue_delay_s
            while (
                rows < self.max_batch
                and len(group) < max_arity
                and self._inflight > 0
                and not self._closed
            ):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cond.wait(timeout=remaining)
                rows = self._drain_compatible_locked(
                    group, first, rows, max_arity
                )
            return group

    def _topup(self, group):
        """Last-moment fold-in of arrivals that queued while the pipeline
        semaphore blocked (or between gather and dispatch)."""
        with self._cond:
            first = group[0]
            rows = sum(p.rows for p in group)
            self._drain_compatible_locked(
                group, first, rows, self._max_arity(first)
            )

    def _prof_commit(self, rows, t0, t_in, infer_ns, output_ns, queue_ns=0):
        """Fold one completed group into the engine's continuous
        profiler (serve/prof.py) as a "batch" tick, reusing the
        timestamps the statistics already took.  ``compute`` is the time
        the device worked on the group and ``device_queue`` (device groups
        only) the time it waited on the device behind the groups
        dispatched before it.  The batcher's own queue wait is omitted:
        it overlaps other groups' device time, so summing it would
        double-count the wall."""
        prof = self.prof
        if prof is None:
            return
        phases = {
            "host": (t_in - t0) / 1e9,
            "compute": infer_ns / 1e9,
            "render": output_ns / 1e9,
        }
        if queue_ns:
            phases["device_queue"] = queue_ns / 1e9
        prof.commit(
            "batch",
            (t_in - t0 + queue_ns + infer_ns + output_ns) / 1e9,
            phases=phases,
            model=self.model.name,
            items=rows,
            flops_per_item=self.model.flops_per_item,
        )

    def _dispatch(self, group):
        """Host-concat the group, pad to a power-of-two bucket, and issue the
        (asynchronous) forward.  Returns state for _complete, or None if the
        dispatch failed (the group is already notified).

        The engine duty-cycle span opens here and closes in _complete/_fail:
        the device is considered busy from issue until results land."""
        t0 = time.monotonic_ns()
        w_dispatch = time.time_ns()
        for p in group:
            if p.trace is not None:
                p.trace.event("QUEUE_END", w_dispatch)
                p.trace.event("COMPUTE_START", w_dispatch)
        if self._busy is not None:
            self._busy.begin()
        try:
            device = group[0].signature[0]
            # per-input entries only (a fused-device signature carries a
            # trailing row-count scalar for group row-uniformity)
            names = [
                e[0] for e in group[0].signature[1:] if isinstance(e, tuple)
            ]
            rows = sum(p.rows for p in group)
            if device and self._use_fused():
                parts = {
                    name: tuple(p.inputs[name] for p in group)
                    for name in names
                }
                result = self._fused_jit()(parts)
                return group, ("fused", result), rows, t0, time.monotonic_ns()
            # rows <= max_batch by construction, so padded >= rows always.
            padded = _bucket(rows, cap=self.max_batch)
            batched = {}
            for name in names:
                parts = [p.inputs[name] for p in group]
                if device:
                    # TPU-shm path: assembly stays on device and the forward
                    # runs at batch=`padded` on the MXU instead of
                    # `len(group)` batch-1 dispatches.  A lone full-bucket
                    # part skips assembly entirely (zero-copy).
                    if len(parts) == 1 and parts[0].shape[0] == padded:
                        batched[name] = parts[0]
                    else:
                        batched[name] = _device_batch(parts, padded)
                else:
                    if padded > rows:
                        pad = np.zeros(
                            (padded - rows,) + tuple(parts[0].shape[1:]),
                            dtype=parts[0].dtype,
                        )
                        parts = parts + [pad]
                    batched[name] = (
                        np.concatenate(parts, axis=0)
                        if len(parts) > 1
                        else parts[0]
                    )
            t_in = time.monotonic_ns()
            result = self.model.fn(batched, {}, None)
            return group, result, rows, t0, t_in
        except Exception as e:  # noqa: BLE001 - failure propagates per-request
            if self._busy is not None:
                self._busy.end()
            self._fail(group, e)
            return None

    def _handoff_device(self, group, result, rows, t0, t_in):
        """Hand a device group's results to its waiters at DISPATCH time
        (ack == dispatch, the TPU-shm contract) — splitting is lazy device
        ops, no transfer.  Returns the arrays the watcher should observe for
        completion (busy span, semaphore and device time close there), or
        None on failure (the group is already notified)."""
        try:
            w_done = time.time_ns()
            if isinstance(result, tuple) and result[0] == "fused":
                # per-part output arrays came straight out of the jitted
                # dispatch — hand them over, nothing left to do on host
                per_part = result[1]
                for i, p in enumerate(group):
                    p.result = {
                        name: parts[i] for name, parts in per_part.items()
                    }
                    # trace events land BEFORE the waiter wakes: the request
                    # thread completes/exports the trace as soon as it runs
                    if p.trace is not None:
                        p.trace.event("COMPUTE_END", w_done)
                    p.event.set()
                watch = per_part
            else:
                # batch-wide response parameters replicate, never slice
                # (reserved "__parameters__" result key)
                extra_params = (
                    result.pop("__parameters__", None)
                    if isinstance(result, dict)
                    else None
                )
                offset = 0
                for p in group:
                    # whole-buffer pass-through when one request fills the
                    # bucket; dynamic_slice otherwise (bounded executables)
                    p.result = {
                        name: arr
                        if offset == 0 and p.rows == arr.shape[0]
                        else _device_split(arr, offset, p.rows)
                        for name, arr in result.items()
                    }
                    if extra_params is not None:
                        p.result["__parameters__"] = extra_params
                    offset += p.rows
                    if p.trace is not None:
                        p.trace.event("COMPUTE_END", w_done)
                    p.event.set()
                watch = result
            with self._cond:
                self._active.difference_update(group)
            # counts and queue time with the ack; compute_infer_ns follows
            # at completion (_device_done): here the device has hardly begun
            self.stats.record_batched(
                rows=rows,
                infer_ns=0,
                input_ns=t_in - t0,
                output_ns=0,
                queue_ns=sum(t_in - p.t_enq for p in group),
                queue_ns_each=[t_in - p.t_enq for p in group],
            )
            return watch
        except Exception as e:  # noqa: BLE001 - failure propagates per-request
            self._fail(group, e)
            return None

    def _complete_host(self, group, result, rows, t0, t_in):
        """Wire-group completion (runs on the completion pool): one
        batch-wide D2H, then split host rows back to requests.  The busy
        span closes when results land host-side — real completion."""
        busy_open = self._busy is not None
        try:
            import jax

            host = jax.device_get(result)
            if busy_open:
                self._busy.end()  # wire results landed host-side
                busy_open = False
            t_inf = time.monotonic_ns()
            # response-level parameters (reserved "__parameters__" result
            # key) are batch-wide, not row-sliceable: replicate them onto
            # every request's split instead of slicing a dict
            extra_params = host.pop("__parameters__", None)
            w_done = time.time_ns()
            offset = 0
            for p in group:
                p.result = {
                    name: arr[offset : offset + p.rows]
                    for name, arr in host.items()
                }
                if extra_params is not None:
                    p.result["__parameters__"] = extra_params
                offset += p.rows
                if p.trace is not None:
                    p.trace.event("COMPUTE_END", w_done)
                p.event.set()
            with self._cond:
                self._active.difference_update(group)
            t1 = time.monotonic_ns()
            queue_ns = sum(t_in - p.t_enq for p in group)
            self.stats.record_batched(
                rows=rows,
                infer_ns=t_inf - t_in,
                input_ns=t_in - t0,
                output_ns=t1 - t_inf,
                queue_ns=queue_ns,
                queue_ns_each=[t_in - p.t_enq for p in group],
            )
            self._prof_commit(rows, t0, t_in, t_inf - t_in, t1 - t_inf)
        except Exception as e:  # noqa: BLE001 - failure propagates per-request
            if busy_open:
                self._busy.end()  # device_get raised before the span closed
            self._fail(group, e)

    def _fail(self, group, e):
        err = (
            e
            if isinstance(e, InferenceServerException)
            else InferenceServerException(
                f"{self.model.name}: batched execution failed: {e}",
                status="500",
                debug_details=e,
            )
        )
        for p in group:
            p.error = err
            p.event.set()
        with self._cond:
            self._active.difference_update(group)


def _leading_rows(inputs):
    for arr in inputs.values():
        if arr.ndim == 0:
            raise InferenceServerException(
                "batchable model input must have a leading batch dimension",
                status="400",
            )
        return int(arr.shape[0])
    raise InferenceServerException("request has no inputs", status="400")


def batchable_request(model, inputs, params, context, request):
    """Whether this request may take the dynamic-batching path."""
    if not model.dynamic_batching or model.decoupled or model.stateful:
        return False
    if context is not None or params.get("sequence_id"):
        return False
    # Request parameters beyond rendering hints reach model.fn on the direct
    # path; the batcher calls fn once for many requests and cannot honor
    # per-request parameters, so any such request keeps the direct path.
    if any(k not in ("binary_data_output",) for k in params):
        return False
    if model.max_batch_size <= 1:
        return False
    device = bool(inputs) and all(
        _is_device_array(a) for a in inputs.values()
    )
    if device and not getattr(model, "batch_device_inputs", False):
        # Device-resident (TPU-shm) inputs skip batching by default: the
        # forward dispatches on them directly (zero-copy, one async op),
        # while fusing adds assemble/split device ops per request — pure
        # overhead on a path that pays no H2D either way.  Batching exists
        # to amortize host<->device transfers; device arrays already did.
        # Opt in per model (`batch_device_inputs=True`) where per-dispatch
        # latency is negligible and MXU utilization dominates (chip-local
        # serving of compute-heavy models).
        return False
    if not device:
        for out in request.get("outputs") or []:
            # shm outputs of HOST groups stay on the direct path: host-mode
            # batching materializes outputs host-side, which would cost the
            # shm path its zero-copy write.  Device groups render outputs as
            # live device slices, so shm outputs batch fine there.
            if "shared_memory_region" in (out.get("parameters") or {}):
                return False
    rows = None
    for arr in inputs.values():
        if isinstance(arr, np.ndarray):
            if arr.dtype == np.object_:
                return False  # BYTES inputs: direct path
        elif not _is_device_array(arr):
            return False
        if arr.ndim == 0:
            return False
        if rows is None:
            rows = arr.shape[0]
        elif arr.shape[0] != rows:
            return False
    # mixed host/device inputs in one request keep the direct path (a device
    # concat would silently D2H the host parts or vice versa)
    if not device and any(_is_device_array(a) for a in inputs.values()):
        return False
    return rows is not None and rows <= model.max_batch_size
