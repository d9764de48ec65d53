"""A named program's share of its roofline in the traced interval: the least
time the chip could take for the work its calls did there, over the device
time of the program's events in the trace.  For a program that jit names (a
``def``), so the trace's events need no laying along the engine's ticks as
``trace_module.align`` does for ``jit__unknown``.

Parameters: ``program`` (the module's name in the trace, without its
fingerprint), ``kind`` (the ``LmEngine.tick_trace()`` kind that dispatches
it), ``work_module`` and ``work_fn`` (a function of that module of
``benchmark``), ``bound`` (the bound it is expected to take; the other is
reported on standard error if it takes over).

The work is counted by the engine itself: the ticks of that kind dispatched
between the two host-clock instants just inside the trace carry their lanes,
``context_tokens`` and ``window_tokens`` (a decode tick) or ``start`` and
``tokens`` (a prefill chunk).  The trace spans a little more than those
instants, so the work of those ticks is brought to the number of events the
trace holds.  Ticks without the fields (a program from before they were
written) give nothing to read: None.
"""

import importlib
import sys

from benchmark import work


def _decode_counts(ticks):
    return {"calls": len(ticks),
            "lane_steps": sum(len(t["lanes"]) for t in ticks),
            "context_sum": sum(t["context_tokens"] for t in ticks),
            "window_sum": sum(t["window_tokens"] for t in ticks)}


def _chunk_counts(ticks):
    return {"chunks": [(t["start"], t["tokens"]) for t in ticks]}


COUNTS = {"decode": ("context_tokens", _decode_counts),
          "prefill_chunk": ("tokens", _chunk_counts)}


def read(params, ctx):
    trace, window = ctx.get("trace"), ctx["window"]
    span = window.get("traced_span")
    if trace is None or span is None:
        return None
    calls, seconds = trace["modules"].get(params["program"], (0, 0.0))
    field, count = COUNTS[params["kind"]]
    ticks = [t for t in window.get("ticks", ())
             if t["kind"] == params["kind"] and span[0] <= t["t0"] < span[1]
             and field in t]
    if not calls or seconds <= 0.0 or not ticks:
        return None
    fn = getattr(importlib.import_module(
        f"benchmark.{params['work_module']}"), params["work_fn"])
    need = fn(ctx["config"], count(ticks))
    per_call = calls / len(ticks)
    need = {"flops": need["flops"] * per_call, "bytes": need["bytes"] * per_call}
    if need["flops"] <= 0 and need["bytes"] <= 0:
        return None
    least, bound = work.roofline_seconds(need, ctx["device_kind"])
    if bound != params["bound"]:
        print(f"trace_program: {params['program']} took the {bound} bound, "
              f"not {params['bound']}", file=sys.stderr)
    return 100.0 * least / seconds
