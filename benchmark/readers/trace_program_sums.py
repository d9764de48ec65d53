"""``trace_program``: a named program's share of its roofline in the traced
interval, for a program whose work also depends on what it counted on the
device.  Parameters: ``trace_program``'s (``program``, ``kind``,
``work_module``, ``work_fn``, ``bound``) and ``sums``, the names of the
fields of a ``LmEngine.tick_trace()`` entry that are added up over the
traced ticks and handed to the work function beside ``trace_program``'s
counts (``expert_rows``, ``experts_hit``, ``kv_positions_live``).  A tick
that lacks one of them is left out; with none left (a program that writes no
such field) there is nothing to read: None.

ROADMAP S7 retires this copy into one ``trace_program`` with ``sums``.
"""

import importlib
import sys

from benchmark import work
from benchmark.readers.trace_program import COUNTS


def read(params, ctx):
    trace, window = ctx.get("trace"), ctx["window"]
    span = window.get("traced_span")
    if trace is None or span is None:
        return None
    calls, seconds = trace["modules"].get(params["program"], (0, 0.0))
    field, count = COUNTS[params["kind"]]
    needed = (field,) + tuple(params["sums"])
    ticks = [t for t in window.get("ticks", ())
             if t["kind"] == params["kind"] and span[0] <= t["t0"] < span[1]
             and all(name in t for name in needed)]
    if not calls or seconds <= 0.0 or not ticks:
        return None
    counts = count(ticks)
    for name in params["sums"]:
        counts[name] = sum(t[name] for t in ticks)
    fn = getattr(importlib.import_module(
        f"benchmark.{params['work_module']}"), params["work_fn"])
    need = fn(ctx["config"], counts)
    per_call = calls / len(ticks)
    need = {"flops": need["flops"] * per_call, "bytes": need["bytes"] * per_call}
    if need["flops"] <= 0 and need["bytes"] <= 0:
        return None
    least, bound = work.roofline_seconds(need, ctx["device_kind"])
    if bound != params["bound"]:
        print(f"trace_program_sums: {params['program']} took the {bound} "
              f"bound, not {params['bound']}", file=sys.stderr)
    return 100.0 * least / seconds
