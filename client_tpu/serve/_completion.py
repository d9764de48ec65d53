"""Shared device-completion observer for async-dispatch bookkeeping.

A single daemon thread per observer waits on watched device results and runs
a per-item callback at completion — the mechanism the engine's duty-cycle
metric (BusyTracker spans) and the dynamic batcher's pipeline backpressure
both close through.  Host-only results complete immediately on the caller
thread.

It is also where device time comes from.  Items settle one after the other
in the order they were watched (a batcher and an ``LmEngine`` each dispatch
from one thread to one stream, which runs them in that order), and the
instant after each wait is that item's completion.  From two neighbouring
instants: the device worked on an item from its dispatch, or from the
completion of the item before it if that came later, until its own
completion (``device_ns``); until then it waited behind the items dispatched
before it (``device_queue_ns``).  ``compute_infer_ns``, the profiler's
``compute`` phase and ``LmEngine.tick_trace()``'s ``device_s`` are all this
number.

It is also the only place a device-side failure AFTER the response can be
seen: TPU-shm requests are acknowledged at dispatch, so when the device
work later fails nobody is left waiting on it.  The observer logs the error
and hands it to the watch's ``on_error`` (the model's failure statistics)
before running the completion callback, which still always runs.
"""

import logging
import threading
import time

_log = logging.getLogger(__name__)


def _completion_arrays(result, out=None):
    """Arrays worth waiting on from a result pytree (nested dict/list/tuple
    of arrays — e.g. a fused batch's per-part output dict of tuples)."""
    if out is None:
        out = []
    if isinstance(result, dict):
        for v in result.values():
            _completion_arrays(v, out)
    elif isinstance(result, (list, tuple)):
        for v in result:
            _completion_arrays(v, out)
    elif hasattr(result, "block_until_ready"):
        out.append(result)
    return out


class CompletionObserver:
    def __init__(self, name="completion-observer"):
        self._name = name
        self._cv = threading.Condition()
        self._backlog = []  # (arrays, callback, on_error, t_dispatch_ns)
        self._closed = False
        self._thread = None
        self._t_prev = 0  # completion instant of the item settled last

    def watch(self, result, callback, on_error=None, t_dispatch_ns=None):
        """Run ``callback(t_done_ns, device_ns, device_queue_ns)`` once
        every device array in *result* has completed — or failed: then the
        error is logged and *on_error(exc)* runs first.

        *t_dispatch_ns* is the ``time.monotonic_ns()`` instant at which the
        work was dispatched (now, if not given); ``t_done_ns`` is on the
        same clock.  Host results (nothing to wait on) run the callback
        inline with ``device_ns`` and ``device_queue_ns`` None: no device
        work was observed.  Watches arriving after close() — e.g. a batcher
        thread that outlived its bounded shutdown join — block inline on
        the caller thread and still run the callback, so no
        span/semaphore/counter ever leaks.
        """
        arrays = _completion_arrays(result)
        if not arrays:
            callback(time.monotonic_ns(), None, None)
            return
        if t_dispatch_ns is None:
            t_dispatch_ns = time.monotonic_ns()
        with self._cv:
            if not self._closed:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._loop, name=self._name, daemon=True
                    )
                    self._thread.start()
                self._backlog.append(
                    (arrays, callback, on_error, t_dispatch_ns)
                )
                self._cv.notify()
                return
        self._finish(arrays, callback, on_error, t_dispatch_ns)

    def _finish(self, arrays, callback, on_error, t_dispatch_ns):
        """Wait for one item, stamp its completion, deliver it."""
        exc = self._settle(arrays)
        t_done = time.monotonic_ns()
        with self._cv:
            t_prev, self._t_prev = self._t_prev, t_done
        self._report(exc, on_error)
        callback(
            t_done,
            t_done - max(t_dispatch_ns, t_prev),
            max(t_prev - t_dispatch_ns, 0),
        )

    @staticmethod
    def _settle(arrays):
        """Wait for *arrays*; the exception their device work raised, or
        None (failed results still complete)."""
        try:
            import jax

            jax.block_until_ready(arrays)
        except Exception as exc:  # noqa: BLE001 - reported by the caller
            return exc
        return None

    def _report(self, exc, on_error):
        if exc is None:
            return
        _log.error("%s: device work failed after dispatch: %r",
                   self._name, exc)
        if on_error is not None:
            try:
                on_error(exc)
            except Exception:  # noqa: BLE001 - the callback must still run
                _log.exception("%s: on_error raised", self._name)

    def _loop(self):
        # one guard per pass (the BG-THREAD-CRASH shape): a raising
        # completion callback must not kill the observer thread — every
        # later watch would leak its span/semaphore/counter silently
        while True:
            try:
                if not self._drain_once():
                    return
            except Exception:
                pass

    def _drain_once(self):
        """Settle and deliver one backlog batch, item by item in watch
        order; False once closed and drained.  Each item is guarded
        individually so one bad callback cannot skip its batch siblings."""
        with self._cv:
            while not self._backlog and not self._closed:
                self._cv.wait()
            if not self._backlog:
                return False
            batch, self._backlog = self._backlog, []
        for item in batch:
            try:
                self._finish(*item)
            except Exception:  # noqa: BLE001 - siblings must still run
                pass
        return True

    def close(self, timeout=30):
        with self._cv:
            self._closed = True
            self._cv.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join(timeout=timeout)
