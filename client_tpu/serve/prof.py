"""Continuous low-overhead phase profiler: where serving time goes.

PR 13's tracing answers *what happened* per request; this module is the
always-on layer that answers *why it was slow*: every engine keeps a
:class:`PhaseProfiler` — a bounded ring of per-tick phase timings
(sibling of ``serve/flight.py``'s FlightRecorder) fed continuously by
the hot paths and rolled up on demand:

- the LM engine's scheduler loop brackets each iteration
  (lock/schedule, prefill/decode dispatch, device wait, token delivery,
  idle wait) with ``perf_counter`` spans,
- the unary engine's execute path folds its existing monotonic
  timestamps (input gather / model fn / render) into ``unary`` ticks at
  zero added timing cost,
- the HTTP/gRPC frontends and the perf client backends commit
  wire-path ticks (deserialize / execute-wait / serialize / send).

Rollups attribute windowed wall time into per-phase shares, and the
measured device time + per-model FLOP figures produce compute-share and
MFU series (``ctpu_prof_*`` gauges/counters in serve/metrics.py's
catalog).  :func:`device_peak_tflops` supplies the MFU denominator: the
published bf16 peak of the TPU this process runs on.  Off-TPU there is
no MFU — a host ratio under a device metric's name is how a run with no
chip attached once passed for a benchmark.

Surfaces: ``GET /v2/debug/prof`` (rollup JSON),
``python -m client_tpu.profview`` (attribution tables) and
flight-recorder dumps (the last N tick profiles ride along).

Bracket discipline: a handle acquired with ``start_tick`` MUST reach
``finish`` on every exit path (``with`` handle, or ``try/finally``) —
the SPAN-LEAK lint rule enforces this shape (analysis/resources.py
registers ``start_tick`` in the span vocabulary).  An unfinished tick
never reaches the ring, so the rollup under-attributes exactly when a
failure makes the timeline interesting.

The stall's witness (PR 37).  One daemon thread a process, ``prof-pulse``,
shared by every armed profiler, sleeps ``PULSE_S`` and reads the clock.
A wake-up that comes ``PAUSE_S`` late is a **host pause**: the
interpreter was held (a C call that kept the GIL, a collection) or the
process had no CPU; :meth:`PhaseProfiler.pauses` gives the paused seconds
inside an interval, which is what tells a tick whose completion was
*stamped* late from one the device finished late.  The pulse also looks
at the phase each profiler has open: one open ``STALL_S`` or longer, and
no wait by design, has every thread's innermost frames sampled once.
:func:`stall_cause` is the one rule that names what held a tick or a
phase (``upload``, ``call``, ``compile``, ``host_pause``, ``device``,
``host``); a stall becomes ONE record in the flight ring, the rollup's
``stalls``, ``ctpu_prof_stalls_total{cause}`` and one WARNING line of
this module's logger.  All of it is armed with the profiler and none of
it without.

Everything here must stay cheap enough to leave armed in production:
one clock pair per phase, one deque append per tick, no allocation
beyond the record dict; the pulse wakes fifty times a second for a few
microseconds.  The measured budget (tests/test_prof.py) is <= 2% on the
in-process headline path.  Instants are ``time.monotonic()``'s, the clock
of ``LmEngine.tick_trace()``.
"""

import atexit
import collections
import gc
import json
import logging
import statistics
import sys
import threading
import time
import weakref

from client_tpu.analysis.witness import witness_shared

_log = logging.getLogger(__name__)

__all__ = [
    "PhaseProfiler",
    "NULL_TICK",
    "NULL_PHASE",
    "ATTRIBUTION_GROUPS",
    "device_peak_tflops",
    "attribute_phases",
    "annotation",
    "stall_cause",
    "PULSE_S",
    "PAUSE_S",
    "STALL_S",
]

# The pulse sleeps this long between two looks at the clock.  Fifty
# wake-ups a second of some 10 us are 0.05% of a core, and a pause is
# timed to within one of them.
PULSE_S = 0.02
# A wake-up this late is a host pause.  A thread that waits for the
# interpreter is given it within the switch interval (5 ms), so a busy
# pure-Python process delays the pulse by a few ms and never by ten
# times that; the pauses PERF.md knows of (the allocator inside a
# dispatch, with the GIL held) are 65-140 ms.
PAUSE_S = 0.05
# A span of the host (an upload, a program's call, any phase that is no
# wait by design) this long, or device time this long and four times its
# kind's median, is a stall.  The cells' ticks take 12-16 ms and their
# chunks 16-40 ms; their dispatches take well under a millisecond.
STALL_S = 0.1
# how many of a kind's device times the running median is taken over,
# how many of them it needs before it judges, and the factor over it
_MEDIAN_OVER, _MEDIAN_NEEDS, _MEDIAN_TIMES = 64, 8, 4.0

# Published dense bf16 peak per chip in TFLOP/s, keyed by the exact
# ``device_kind`` JAX reports (the MFU denominator; the table's one
# home in the program).  A TPU kind that is not listed is an
# error, never a neighbour's peak: add the row, with its source, the
# first time the program runs on that chip.
_TPU_PEAK_BF16_TFLOPS = {
    # TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16
    "TPU v5 lite": 197.0,
}

# Phase -> attribution bucket for the compute/dispatch/device_wait/host/
# idle split (the rollup's attribution, profview's summary row).  ``compute``
# is device time as the completion observer measured it
# (serve/_completion.py), or a host model's own run time; the
# dispatch-site phases are launch overhead; ``device_wait`` (the host
# blocked on a read-back) and ``device_queue`` (a dispatched step behind
# the steps before it) are waits, and no one's work.
ATTRIBUTION_GROUPS = {
    "compute": ("compute",),
    "dispatch": ("schedule", "preempt", "resume", "execute",
                 "decode_dispatch", "prefill_dispatch", "verify_dispatch",
                 "build", "upload", "record"),
    "device_wait": ("device_wait", "device_queue"),
    "host": ("host", "render", "deliver", "serialize", "deserialize",
             "send", "wait", "draft"),
    "idle": ("idle",),
}


def device_peak_tflops():
    """``(peak_tflops, device_kind)`` of the accelerator this process
    already holds.

    ``(None, None)`` while the process has initialised no JAX backend —
    asking must never be the call that opens the chip (see
    :func:`metrics.initialized_devices`) — and ``(None, kind)`` on any
    platform but a TPU, so off-TPU every MFU figure is absent rather
    than a host ratio.  A TPU whose ``device_kind`` the table does not
    list raises."""
    from client_tpu.serve.metrics import initialized_devices

    devices = initialized_devices()
    if not devices:
        return None, None
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        return None, kind
    if kind not in _TPU_PEAK_BF16_TFLOPS:
        raise ValueError(
            f"no published bf16 peak for TPU device_kind {kind!r}: add "
            "it, with its source, to serve/prof.py:_TPU_PEAK_BF16_TFLOPS"
        )
    return _TPU_PEAK_BF16_TFLOPS[kind], kind


def annotation(name):
    """A ``jax.profiler.TraceAnnotation`` named *name*: a span on the
    host plane of the profiler's trace, on the same clock as the
    device's events, so that an idle gap of the device can be named by
    the phase the host was in.  Inert while no profiler session runs,
    and a no-op in a process that has not imported jax (asking must not
    be what imports it) or in which another thread is still importing
    it."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    make = getattr(profiler, "TraceAnnotation", None)
    return NULL_PHASE if make is None else make(name)


def attribute_phases(phases, wall_s=None):
    """Fold a {phase: seconds} dict into the compute/dispatch/
    device_wait/host/idle share split (percentages summing to ~100).

    *wall_s* is the window the phases were measured over; time it
    covers beyond the summed phases counts as idle.  Concurrent
    execution can sum past the wall — shares then normalize over the
    summed total (idle 0)."""
    groups = dict.fromkeys(ATTRIBUTION_GROUPS, 0.0)
    for name, seconds in (phases or {}).items():
        for group, members in ATTRIBUTION_GROUPS.items():
            if name in members:
                groups[group] += seconds
                break
        else:
            groups["host"] += seconds  # unmapped phases are host work
    covered = sum(groups.values())
    if wall_s is not None and wall_s > covered:
        groups["idle"] += wall_s - covered
    total = sum(groups.values())
    if total <= 0.0:
        return None
    return {
        f"{group}_pct": round(100.0 * seconds / total, 2)
        for group, seconds in groups.items()
    }


# Phases that wait by design, for the device, for work or for another's
# answer: however long one is open, nothing is held.  ``gather`` is the
# batcher's wait for requests; ``wait`` is a frontend's or a client's wait
# for the engine's or the server's answer, which holds their work.
_WAITS = frozenset(
    ATTRIBUTION_GROUPS["idle"] + ATTRIBUTION_GROUPS["device_wait"]
    + ("gather", "wait")
)

# What may rename the cause a host span gives: a compile inside a
# program's call or a phase says so (``jax.monitoring``'s events), and a
# phase with no name of its own among the causes is ``host_pause`` where
# the interpreter was held under it.  An ``upload`` stays an upload: that
# it held the interpreter is in ``host_pause_s`` beside it.
_RENAMES = {"upload": (), "call": ("compile",),
            "host": ("compile", "host_pause")}


def stall_cause(spans, device_s=None, median_s=None, host_pause_s=0.0,
                compile_s=0.0):
    """THE rule: ``(cause, seconds)`` of what held a tick or a phase, or
    None where nothing did.

    *spans* are the host's spans in order, ``(name, seconds)`` with
    *name* one of ``upload``, ``call`` (a tick's two) and ``host`` (a
    phase that belongs to no tick): the first that took ``STALL_S`` names
    the cause and its length is the stall's.  Else *device_s* at or over
    ``max(STALL_S, 4 x median_s)`` is a stall of its excess over the
    median: ``host_pause`` where *host_pause_s*, the seconds the
    interpreter was held while the device time ran, cover half the excess
    or more (the completion was stamped late, not reached late), else
    ``device``.  Without a median (a kind's first entries) device time
    is not judged."""
    for name, seconds in spans:
        if seconds is None or seconds < STALL_S:
            continue
        covers = {"compile": compile_s, "host_pause": host_pause_s}
        for rename in _RENAMES[name]:
            if covers[rename] >= seconds / 2:
                return rename, seconds
        return name, seconds
    if (device_s is None or median_s is None
            or device_s < max(STALL_S, _MEDIAN_TIMES * median_s)):
        return None
    excess = device_s - median_s
    return ("host_pause" if host_pause_s >= excess / 2 else "device"), excess


def _overlap(spans, t_a, t_b):
    """Seconds of ``(t_begin, seconds)`` *spans* inside ``[t_a, t_b]``.
    They were appended as they ended: the newest first, until one ended
    before the interval."""
    total = 0.0
    for t, seconds in reversed(spans):
        if t + seconds < t_a:
            break
        total += max(min(t + seconds, t_b) - max(t, t_a), 0.0)
    return total


def _frames(depth=8):
    """Every other thread's name and innermost *depth* frames, as
    ``file:line function`` (``sys._current_frames()``, once)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    me = threading.get_ident()
    out = []
    for ident, frame in sys._current_frames().items():
        if ident == me:
            continue
        rows = []
        while frame is not None and len(rows) < depth:
            code = frame.f_code
            where = "/".join(code.co_filename.split("/")[-2:])
            rows.append(f"{where}:{frame.f_lineno} {code.co_name}")
            frame = frame.f_back
        out.append({"thread": names.get(ident, str(ident)), "frames": rows})
    return out


class _Pulse:
    """The process's one pulse: times the pauses of the interpreter,
    samples the frames under a phase that stays open, and keeps beside
    them the full collections (a ``gc.callbacks`` hook) and the compiles
    (``jax.monitoring``'s events, once the process has imported jax), all
    as ``(t_begin, seconds)`` in bounded deques.  Runs while a profiler
    is armed; with none it records nothing."""

    KEPT = 512  # spans a deque: hours of a sound run, minutes of a bad one

    def __init__(self):
        self._lock = threading.Lock()
        self._profilers = weakref.WeakSet()
        self._thread = None
        self._compiles_heard = False
        self._gc_t0 = None
        self._logged_at = float("-inf")
        self._not_logged = 0   # stalls since the last line, and the
        self._longest = None   # longest of them: said with the next
        self.live = False  # a profiler is armed (as of the last beat)
        self.due = None    # when the sleeping pulse means to wake
        self.pauses = collections.deque(maxlen=self.KEPT)
        self.collections = collections.deque(maxlen=self.KEPT)
        self.compiles = collections.deque(maxlen=self.KEPT)
        self.paused_s = 0.0  # lifetime, with ``n_pauses``
        self.n_pauses = 0

    def join(self, prof, on=True):
        """*prof* armed (the first starts the thread) or disarmed."""
        first = None
        with self._lock:
            if not on:
                self._profilers.discard(prof)
                return
            self._profilers.add(prof)
            self.live = True
            if self._thread is None:
                first = self._thread = threading.Thread(
                    target=self._run, name="prof-pulse", daemon=True)
        if first is not None:
            gc.callbacks.append(self._collected)
            atexit.register(self.last_words)
            first.start()

    def _run(self):
        while True:
            try:
                self._beat()
            except Exception:  # noqa: BLE001 - the witness must outlive
                pass           # whatever one beat met (BG-THREAD-CRASH)

    def _beat(self):
        with self._lock:
            armed = list(self._profilers)
            self.live = bool(armed)
            due = self.due = time.monotonic() + PULSE_S if armed else None
        time.sleep(PULSE_S)
        if not armed:
            return
        now = time.monotonic()
        if now - due >= PAUSE_S:
            self.pauses.append((due, now - due))
            self.paused_s += now - due
            self.n_pauses += 1
            self._count_pause(armed, now - due)
        if not self._compiles_heard:
            self._hear_compiles()
        for prof in armed:
            phase = prof._watch.open
            if (phase is not None and phase.frames is None
                    and now - phase.t0 >= STALL_S
                    and phase.name not in _WAITS):
                phase.frames = _frames()

    @staticmethod
    def _count_pause(armed, seconds):
        from client_tpu.serve.metrics import PROF_HELP

        registries = {}
        for prof in armed:
            with prof._lock:
                registry = prof.registry
            if registry is not None:
                registries[id(registry)] = registry
        for registry in registries.values():
            for series, value in (
                    ("ctpu_prof_host_pause_seconds_total", seconds),
                    ("ctpu_prof_host_pauses_total", 1)):
                registry.inc(series, None, value=value,
                             help_=PROF_HELP[series])

    def paused(self, t_a, t_b):
        """Seconds of host pause inside ``[t_a, t_b]``, the pause the
        pulse is still held in included: a thread that is given the
        interpreter before the pulse is, when a pause ends, must not find
        it missing."""
        with self._lock:
            due = self.due
        spans = list(self.pauses)
        total = _overlap(spans, t_a, t_b)
        now = time.monotonic()
        if (due is not None and now - due >= PAUSE_S
                and not (spans and spans[-1][0] == due)):
            total += _overlap(((due, now - due),), t_a, t_b)
        return total

    def compiled(self, t_a, t_b):
        """Seconds of compiling inside ``[t_a, t_b]``."""
        return _overlap(list(self.compiles), t_a, t_b)

    def collected(self, t_a, t_b):
        """Seconds of full collections inside ``[t_a, t_b]``."""
        return _overlap(list(self.collections), t_a, t_b)

    def _collected(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t0 = time.monotonic()
        elif self._gc_t0 is not None:
            t0, self._gc_t0 = self._gc_t0, None
            if self.live:
                self.collections.append((t0, time.monotonic() - t0))

    def _hear_compiles(self):
        """Not before the process has imported jax on its own: asking
        must not be what imports it."""
        monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
        register = getattr(
            monitoring, "register_event_duration_secs_listener", None)
        if register is not None:
            register(self._compiled)
            self._compiles_heard = True

    def _compiled(self, event, seconds, **_):
        if self.live and event.startswith("/jax/core/compile/"):
            self.compiles.append((time.monotonic() - seconds, seconds))

    def log(self, record):
        """One WARNING line a stall, and at most one a second.  A compile
        is said at INFO: the first call of every shape compiles, by
        design, and a warm-up is no wall of warnings."""
        if record["cause"] == "compile":
            _log.info("stall: %s", json.dumps(record, default=str))
            return
        now = time.monotonic()
        with self._lock:
            if now - self._logged_at < 1.0:
                self._not_logged += 1
                if (self._longest is None
                        or record["seconds"] > self._longest["seconds"]):
                    self._longest = record
                return
            self._logged_at = now
        _log.warning("stall: %s%s", json.dumps(record, default=str),
                     self._held_back())

    def _held_back(self):
        """What the limit kept from the log since the last line: how
        many, and the longest of them whole."""
        with self._lock:
            held, self._not_logged = self._not_logged, 0
            longest, self._longest = self._longest, None
        if not held:
            return ""
        return (f" (and {held} before it, not logged; the longest: "
                f"{json.dumps(longest, default=str)})")

    def last_words(self):
        """At exit: the stalls the limit held back after the last line
        are not lost with the process (the longest may be among them)."""
        held = self._held_back()
        if held:
            _log.warning("stall:%s", held)


_PULSE = _Pulse()


class _Watch:
    """Where a profiler's phases note the one that is open, for the
    pulse to look at: a holder of its own, so that the store costs an
    attribute and is no field of the witnessed profiler.  One phase a
    profiler: exact where one thread drives it (an LM scheduler, a
    batcher), the last to open elsewhere."""

    __slots__ = ("open",)

    def __init__(self):
        self.open = None


class _Phase:
    """One ``with tick.phase(name):`` bracket — accumulates elapsed
    seconds into the owning tick's phase dict on exit, and spans the
    same interval on the profiler's trace as ``<profiler>.<phase>``
    (:func:`annotation`).  While it is open the pulse can see it; closed,
    it keeps its ``t0``, its ``seconds`` and the ``frames`` the pulse
    sampled under it, and where it took ``STALL_S`` it is a stall of its
    own, unless it is *held* for a tick's entry to report."""

    __slots__ = ("_prof", "_tick", "name", "_label", "_held", "_span",
                 "t0", "seconds", "frames")

    def __init__(self, prof, tick, name, label, held=False):
        self._prof = prof
        self._tick = tick
        self.name = name
        self._label = label
        self._held = held
        self.seconds = self.frames = None

    def __enter__(self):
        self._span = annotation(self._label)
        self._span.__enter__()
        self.t0 = time.monotonic()
        self._prof._watch.open = self
        return self

    def __exit__(self, *exc):
        self.seconds = seconds = time.monotonic() - self.t0
        self._prof._watch.open = None
        if self._tick is not None:
            self._tick.add(self.name, seconds)
        self._span.__exit__(*exc)
        if (seconds >= STALL_S and not self._held
                and self.name not in _WAITS):
            self._prof.phase_stalled(self)
        return False


class _NullPhase:
    __slots__ = ()
    name = t0 = seconds = frames = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_PHASE = _NullPhase()


class _Tick:
    """One in-progress tick: phase durations + what it computed,
    committed to the profiler's ring by ``finish`` (or ``close`` /
    ``with``)."""

    __slots__ = ("prof", "kind", "t0", "phases", "_items",
                 "_flops", "_model", "_device_s")

    def __init__(self, prof, kind):
        self.prof = prof
        self.kind = kind
        self.phases = {}
        self._items = 0
        self._flops = 0.0
        self._model = None
        self._device_s = None
        self.t0 = time.monotonic()

    def phase(self, name, held=False):
        """*held*: a tick's entry will report this span (``LmEngine``'s
        ``upload_s`` and ``call_s``), so it is no stall of its own."""
        return _Phase(self.prof, self, name, f"{self.prof.name}.{name}",
                      held)

    def relabel(self, kind):
        """Retag the tick once the iteration knows what it did (a
        scheduler tick starts as "sched" and becomes decode/prefill/
        idle)."""
        self.kind = kind

    def add(self, name, seconds):
        """Fold a pre-measured duration into phase *name* (the unary
        path reuses its existing monotonic timestamps this way)."""
        self.phases[name] = self.phases.get(name, 0.0) + seconds

    def compute(self, model, items, flops_per_item=None, device_s=None):
        """Count device work delivered this tick (MFU numerator).  The
        device seconds are the tick's ``compute`` phase, unless the
        caller has them from the completion observer and passes
        *device_s*: the LM scheduler's passes overlap the device's work,
        so there device time is no phase of the pass."""
        self._model = model
        self._items += int(items)
        if flops_per_item:
            self._flops += float(flops_per_item) * int(items)
        if device_s is not None:
            self._device_s = (self._device_s or 0.0) + device_s

    def close(self):
        self.prof.finish(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.prof.finish(self)
        return False


class _NullTick:
    """Disarmed profiler's handle: every bracket is a no-op."""

    __slots__ = ()
    kind = None

    def phase(self, name, held=False):
        return NULL_PHASE

    def relabel(self, kind):
        pass

    def add(self, name, seconds):
        pass

    def compute(self, model, items, flops_per_item=None, device_s=None):
        pass

    def close(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_TICK = _NullTick()


@witness_shared("_lock")
class PhaseProfiler:
    """Bounded ring of per-tick phase timings with windowed rollups.

    Always-on and cheap: ``start_tick``/``finish`` bracket one scheduler
    iteration / request / RPC; ``commit`` folds pre-measured durations
    in one call (the unary hot path).  Consecutive ``idle`` ticks
    coalesce in place so a quiet engine doesn't churn the ring.

    ``registry`` (late-bindable) receives the ``ctpu_prof_*`` series;
    per-model FLOP counts committed via ``_Tick.compute`` update the
    MFU and compute-share gauges using :func:`device_peak_tflops`.
    """

    def __init__(self, name="", capacity=4096, registry=None,
                 window_s=60.0, flush_interval_s=0.25):
        self.name = str(name)
        self.capacity = int(capacity)
        self.window_s = float(window_s)
        self.flush_interval_s = float(flush_interval_s)
        self._lock = threading.Lock()
        self._ring = collections.deque(maxlen=self.capacity)
        self._totals = {}        # phase -> cumulative seconds
        self._kinds = {}         # tick kind -> count
        self._wall_s = 0.0       # cumulative tick wall seconds
        self._models = {}        # model -> [device_s, items, flops]
        self._children = []      # adopted engine profilers (LM scheds)
        self._armed = True
        self.registry = registry
        self.ticks_noted = 0
        # metric deltas batched between registry flushes: exporting on
        # every commit costs several label-formatted registry ops per
        # tick, which alone would blow the <=2% overhead budget on a
        # cheap unary path.
        self._pending_ticks = {}   # kind -> count since last flush
        self._pending_phases = {}  # phase -> seconds since last flush
        self._last_flush = 0.0
        # the stall's witness: the phase that is open (for the pulse), the
        # device times the rule's median is taken over, and what it marked
        self._watch = _Watch()
        self._device_hist = {}   # (kind, width) -> deque of device_s
        self._stall_totals = {}  # cause -> [count, seconds]
        self._stalls = collections.deque(maxlen=8)  # the last records
        # flight recorder (late-bound, as the registry is; an adopted
        # child takes its parent's): a stall is noted in its ring
        self.flight = None
        _PULSE.join(self)

    # -- arming ------------------------------------------------------------

    @property
    def armed(self):
        return self._armed

    def arm(self, on=True):
        """Toggle recording (the overhead-measurement hook; the profiler
        is armed by default).  Disarmed, ``start_tick`` hands out the
        shared no-op tick and ``commit`` returns immediately."""
        with self._lock:
            self._armed = bool(on)
        _PULSE.join(self, bool(on))

    def set_registry(self, registry):
        with self._lock:
            self.registry = registry

    def adopt(self, child):
        """Register a per-engine child profiler (the LM scheduler's) so
        reports and flight dumps cover every engine in the server."""
        if child is None or child is self:
            return
        with self._lock:
            if child not in self._children:
                self._children.append(child)
            flight = self.flight
        if flight is not None and child.flight is None:
            child.flight = flight

    # -- recording ---------------------------------------------------------

    def start_tick(self, kind):
        """A new tick handle (or the no-op handle when disarmed).  The
        caller MUST finish it on every exit path: ``with`` the handle,
        or ``finish``/``close`` inside a ``finally`` — the SPAN-LEAK
        lint shape."""
        if not self._armed:
            return NULL_TICK
        return _Tick(self, kind)

    def span(self, label):
        """A phase that belongs to no tick (the batcher's thread brackets
        its loop so): *label* whole is its name on the profiler's trace,
        its last word the phase's name; the pulse watches it while it is
        open, and closed over ``STALL_S`` it is a stall.  No seconds are
        folded into any tick.  Disarmed, the bare :func:`annotation`."""
        if not self._armed:
            return annotation(label)
        return _Phase(self, None, label.rpartition(".")[2], label)

    def finish(self, tick, kind=None):
        """Commit one tick handle to the ring (idempotent for the no-op
        handle)."""
        if tick is NULL_TICK or tick is None:
            return
        t1 = time.monotonic()
        self.commit(
            kind if kind is not None else tick.kind,
            t1 - tick.t0,
            phases=tick.phases,
            model=tick._model,
            items=tick._items,
            flops=tick._flops,
            device_s=tick._device_s,
        )

    def commit(self, kind, dur_s, phases=None, model=None, items=0,
               flops=0.0, flops_per_item=None, device_s=None):
        """Fold one pre-measured tick into the ring and rollup state —
        the zero-extra-clock path the unary engine and frontends use.
        ``flops_per_item`` is a convenience for callers that count items
        but carry per-item FLOP figures.  *device_s* is the tick's
        device time where it is no phase of the tick (see
        ``_Tick.compute``); left out, it is the ``compute`` phase."""
        if not self._armed:
            return
        phases = phases or {}
        if flops_per_item and items:
            flops = float(flops) + float(flops_per_item) * int(items)
        if device_s is None:
            device_s = phases.get("compute", 0.0)
        record = {
            "ts": time.time(),
            "kind": str(kind),
            "dur_s": dur_s,
            "phases": phases,
        }
        if model is not None:
            record["model"] = str(model)
        if items:
            record["items"] = int(items)
        if device_s:
            record["device_s"] = device_s
        flush = None
        with self._lock:
            ring = self._ring
            if (kind == "idle" and ring
                    and ring[-1]["kind"] == "idle"):
                # coalesce idle runs: a quiet engine must not wash real
                # ticks out of the bounded ring
                last = ring[-1]
                last["dur_s"] += dur_s
                last["ticks"] = last.get("ticks", 1) + 1
                for name, seconds in phases.items():
                    last["phases"][name] = (
                        last["phases"].get(name, 0.0) + seconds
                    )
            else:
                ring.append(record)
            self.ticks_noted += 1
            self._wall_s += dur_s
            self._kinds[kind] = self._kinds.get(kind, 0) + 1
            totals = self._totals
            pending = self._pending_phases
            for name, seconds in phases.items():
                totals[name] = totals.get(name, 0.0) + seconds
                pending[name] = pending.get(name, 0.0) + seconds
            self._pending_ticks[kind] = (
                self._pending_ticks.get(kind, 0) + 1
            )
            if model is not None and (device_s or items):
                entry = self._models.setdefault(model, [0.0, 0, 0.0])
                entry[0] += device_s
                entry[1] += int(items)
                entry[2] += float(flops)
            if (self.registry is not None
                    and record["ts"] - self._last_flush
                    >= self.flush_interval_s):
                flush = self._drain_pending_locked(record["ts"])
        if flush is not None:
            self._export(*flush)

    def _drain_pending_locked(self, now):
        """Grab-and-reset the batched metric deltas (caller holds the
        ring lock); returns the _export argument tuple."""
        ticks, self._pending_ticks = self._pending_ticks, {}
        phases, self._pending_phases = self._pending_phases, {}
        models = {m: list(v) for m, v in self._models.items()}
        self._last_flush = now
        return self.registry, ticks, phases, models

    def flush_metrics(self):
        """Force the batched ctpu_prof_* deltas out to the registry now
        (reports and tests; the commit path flushes on its own interval)."""
        with self._lock:
            if self.registry is None:
                return
            flush = self._drain_pending_locked(time.time())
        self._export(*flush)

    def _export(self, registry, ticks, phases, models):
        """Push one batch of metric deltas to the registry (outside the
        ring lock; the registry has its own)."""
        from client_tpu.serve.metrics import PROF_HELP

        engine = self.name
        for kind, count in ticks.items():
            registry.inc(
                "ctpu_prof_ticks_total", {"engine": engine, "kind": kind},
                value=count,
                help_=PROF_HELP["ctpu_prof_ticks_total"],
            )
        for name, seconds in phases.items():
            registry.inc(
                "ctpu_prof_phase_seconds_total",
                {"engine": engine, "phase": name}, value=seconds,
                help_=PROF_HELP["ctpu_prof_phase_seconds_total"],
            )
        total_device = sum(v[0] for v in models.values())
        peak = device_peak_tflops()[0]
        for model, (dev, _items, total_flops) in models.items():
            if total_device > 0.0:
                registry.set(
                    "ctpu_prof_compute_share_pct",
                    {"engine": engine, "model": model},
                    round(100.0 * dev / total_device, 3),
                    help_=PROF_HELP["ctpu_prof_compute_share_pct"],
                )
            if peak and total_flops and dev > 0.0:
                registry.set(
                    "ctpu_prof_mfu_pct",
                    {"engine": engine, "model": model},
                    round(100.0 * total_flops / (dev * peak * 1e12), 4),
                    help_=PROF_HELP["ctpu_prof_mfu_pct"],
                )

    # -- the stall's witness -----------------------------------------------

    def pauses(self, t_a, t_b):
        """Seconds inside ``[t_a, t_b]`` (``time.monotonic()``) in which
        the interpreter was held or the process had no CPU, as the
        process's pulse timed them."""
        return _PULSE.paused(t_a, t_b)

    def settle(self, entry, t_done, device_s, t_prev, upload, call,
               inflight=None):
        """A tick's device work has completed: the fields the rule gives
        its ``tick_trace()`` *entry* (``kind``, ``t0``, ``lanes``, and
        ``width`` or ``n_lanes`` are read from it), ``{}`` disarmed.

        ``host_pause_s`` always: the paused seconds while the tick's
        device time ran, from its dispatch or the completion before it
        (*t_prev*) to *t_done*.  ``stall`` and ``stall_s`` where
        :func:`stall_cause` marks it, from the tick's two closed spans
        (*upload*, *call*: phases opened ``held``) and *device_s* against
        the running median of its kind and width; the marked tick is
        recorded (:meth:`_stalled`)."""
        if not self._armed:
            return {}
        begin = max(entry["t0"], t_prev or 0.0)
        host_pause_s = self.pauses(begin, t_done)
        fields = {"host_pause_s": host_pause_s}
        key = (entry["kind"], entry.get("width", entry.get("n_lanes")))
        median_s = None
        with self._lock:
            hist = self._device_hist.get(key)
            if hist is None:
                hist = self._device_hist[key] = collections.deque(
                    maxlen=_MEDIAN_OVER)
            if device_s >= STALL_S and len(hist) >= _MEDIAN_NEEDS:
                median_s = statistics.median(hist)
            hist.append(device_s)
        compile_s = 0.0
        if call.seconds is not None and call.seconds >= STALL_S:
            compile_s = _PULSE.compiled(call.t0, call.t0 + call.seconds)
        verdict = stall_cause(
            (("upload", upload.seconds), ("call", call.seconds)),
            device_s, median_s, host_pause_s, compile_s)
        if verdict is None:
            return fields
        cause, seconds = verdict
        fields["stall"], fields["stall_s"] = cause, seconds
        phase = {"upload": upload, "call": call, "compile": call}.get(cause)
        if phase is None:  # the device's time: from ``begin`` to t_done
            t0, span_s, name = begin, t_done - begin, None
        else:  # a span's own pauses, which may lie before ``begin``
            t0, span_s, name = phase.t0, seconds, phase.name
            host_pause_s = self.pauses(t0, t0 + span_s)
        self._stalled(
            cause, t0, span_s, seconds, name,
            {"kind": entry["kind"], "width": key[1],
             "lanes": len(entry["lanes"]), "start": entry.get("start")},
            host_pause_s, upload.frames or call.frames, inflight)
        return fields

    def phase_stalled(self, phase):
        """A phase of no tick closed after ``STALL_S`` or longer (the
        phase calls this itself): a record, by the one rule."""
        t_end = phase.t0 + phase.seconds
        host_pause_s = self.pauses(phase.t0, t_end)
        cause, seconds = stall_cause(
            (("host", phase.seconds),), host_pause_s=host_pause_s,
            compile_s=_PULSE.compiled(phase.t0, t_end))
        self._stalled(cause, phase.t0, seconds, seconds, phase.name, None,
                      host_pause_s, phase.frames, None)

    def _stalled(self, cause, t0, span_s, seconds, phase, tick,
                 host_pause_s, frames, inflight):
        """ONE record a stall, to every surface: the flight ring, the
        rollup's ``stalls``, ``ctpu_prof_stalls_total{cause}`` and the
        log.  ``seconds`` is the stall's (a span's length, or device
        time's excess over its median); ``gc_s`` the seconds of full
        collections inside ``[t0, t0 + span_s]``, which are no cause of
        their own: one that holds the interpreter shows as a pause."""
        fields = {
            "cause": cause, "engine": self.name, "t0": t0,
            "seconds": seconds, "phase": phase, "tick": tick,
            "host_pause_s": host_pause_s,
            "gc_s": _PULSE.collected(t0, t0 + span_s),
            "inflight": inflight, "frames": frames,
        }
        record = {"kind": "stall", **fields}
        with self._lock:
            total = self._stall_totals.setdefault(cause, [0, 0.0])
            total[0] += 1
            total[1] += seconds
            self._stalls.append(record)
            registry, flight = self.registry, self.flight
        if flight is not None:
            flight.note("stall", **fields)
        if registry is not None:
            from client_tpu.serve.metrics import PROF_HELP

            registry.inc(
                "ctpu_prof_stalls_total",
                {"engine": self.name, "cause": cause},
                help_=PROF_HELP["ctpu_prof_stalls_total"],
            )
        _PULSE.log(record)

    # -- reading -----------------------------------------------------------

    def snapshot(self, last=None):
        """The ring's records, oldest first (the last *last* when set)."""
        with self._lock:
            records = list(self._ring)
        if last is not None:
            records = records[-int(last):]
        return records

    def recent(self, last=16):
        """The last *last* tick records of this profiler AND every
        adopted child, each tagged with its engine name — what flight
        dumps carry."""
        with self._lock:
            children = list(self._children)
        out = []
        for prof in [self] + children:
            for record in prof.snapshot(last=last):
                tagged = dict(record)
                tagged["engine"] = prof.name
                out.append(tagged)
        out.sort(key=lambda r: r.get("ts", 0.0))
        return out

    def rollup(self, window_s=None, kinds=None):
        """Windowed attribution summary of this profiler's ring.

        *window_s* bounds the records considered (None = the profiler's
        default window; 0/negative = everything in the ring); *kinds*
        optionally filters tick kinds.  Returns phase totals with
        percentages, tick counts by kind, per-model device share / MFU,
        and the compute/dispatch/device_wait/host/idle split."""
        if window_s is None:
            window_s = self.window_s
        cutoff = time.time() - window_s if window_s > 0 else None
        records = self.snapshot()
        if cutoff is not None:
            records = [r for r in records if r["ts"] >= cutoff]
        if kinds is not None:
            allowed = set(kinds)
            records = [r for r in records if r["kind"] in allowed]
        phases = {}
        kind_counts = {}
        models = {}
        wall = 0.0
        ticks = 0
        for record in records:
            n = record.get("ticks", 1)
            ticks += n
            wall += record["dur_s"]
            kind_counts[record["kind"]] = (
                kind_counts.get(record["kind"], 0) + n
            )
            for name, seconds in record["phases"].items():
                phases[name] = phases.get(name, 0.0) + seconds
            model = record.get("model")
            if model is not None:
                entry = models.setdefault(model, [0.0, 0])
                entry[0] += record.get("device_s", 0.0)
                entry[1] += record.get("items", 0)
        covered = sum(phases.values())
        phase_rows = {
            name: {
                "s": round(seconds, 6),
                "pct": round(100.0 * seconds / covered, 2) if covered
                else 0.0,
            }
            for name, seconds in sorted(
                phases.items(), key=lambda kv: -kv[1]
            )
        }
        peak, device_kind = device_peak_tflops()
        total_device = sum(v[0] for v in models.values())
        with self._lock:
            flops_by_model = {
                m: v[2] for m, v in self._models.items()
            }
            stalls = {
                "by_cause": {
                    cause: {"count": n, "seconds": round(seconds, 6)}
                    for cause, (n, seconds)
                    in sorted(self._stall_totals.items())
                },
                "last": list(self._stalls),
            }
        model_rows = {}
        for model, (device_s, items) in sorted(models.items()):
            row = {
                "device_s": round(device_s, 6),
                "items": items,
                "compute_share_pct": (
                    round(100.0 * device_s / total_device, 2)
                    if total_device else 0.0
                ),
            }
            flops = flops_by_model.get(model)
            if peak and flops and device_s > 0.0:
                # lifetime FLOP/s over lifetime device time: the ring
                # window carries items but not flops per record
                with self._lock:
                    life = self._models.get(model)
                if life and life[0] > 0.0:
                    row["mfu_pct"] = round(
                        100.0 * life[2] / (life[0] * peak * 1e12), 4
                    )
            model_rows[model] = row
        return {
            "engine": self.name,
            "window_s": window_s,
            "ticks": ticks,
            "wall_s": round(wall, 6),
            "covered_s": round(covered, 6),
            "kinds": kind_counts,
            "phases": phase_rows,
            "models": model_rows,
            "attribution": attribute_phases(phases, wall_s=wall),
            "stalls": stalls,
            "peak_tflops": peak,
            "device_kind": device_kind,
        }

    def report(self, window_s=None):
        """This profiler's rollup plus every adopted child's — the
        ``/v2/debug/prof`` payload and profview's input."""
        with self._lock:
            children = list(self._children)
        for prof in [self] + children:
            prof.flush_metrics()
        return {
            "kind": "prof_report",
            "ts": time.time(),
            "host_pauses": {"count": _PULSE.n_pauses,
                            "seconds": round(_PULSE.paused_s, 6)},
            "engines": [
                prof.rollup(window_s=window_s)
                for prof in [self] + children
            ],
        }
