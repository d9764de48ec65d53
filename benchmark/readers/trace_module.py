"""An XLA module's share of its roofline in the traced interval: the least
time the chip could take for the work the module did there, over the device
time of the module's events.  Parameters: ``module`` (the module's name in
the trace, without its fingerprint), ``work`` (a function of ``work.py``),
``bound`` (the bound it is expected to take; the other is reported on
standard error if it takes over), and optionally ``ticks``.

Without ``ticks`` the module is one program: the driver counts its work
between the two host-clock instants just inside the trace (``traced_counts``,
``traced_seconds``) and the device time is read in the trace's own window, so
both are brought to rates before they are divided.

With ``ticks`` the name is shared by several programs, as ``jit__unknown`` is
by ``LmEngine``'s decode tick and its prefill chunks (jit names a
``functools.partial`` so).  The engine dispatches one such program a tick and
the device runs them in that order, so the trace's events of that name are
laid along ``LmEngine.tick_trace()`` at the offset where each fingerprint
falls on one kind of tick, and the events on ticks of kind ``ticks`` are the
module.
"""

import sys

from benchmark import trace as trace_reader
from benchmark import work


def align(events, ticks, near, slack_s=2.0):
    """The offset ``o`` at which events[i] ran ticks[o + i]: every
    fingerprint should meet one kind of tick only.  None where fewer than
    nine in ten do."""
    best, best_score = None, (-1, 0.0)
    for off in range(len(ticks) - len(events) + 1):
        away = abs(ticks[off]["t0"] - near)
        if away > slack_s:
            continue
        seen = {}
        for (name, _, _), tick in zip(events, ticks[off:]):
            kinds = seen.setdefault(name, {})
            kinds[tick["kind"]] = kinds.get(tick["kind"], 0) + 1
        score = (sum(max(kinds.values()) for kinds in seen.values()), -away)
        if score > best_score:
            best, best_score = off, score
    if best is None or best_score[0] < 0.9 * len(events):
        return None
    return best


def read(params, ctx):
    trace, window = ctx.get("trace"), ctx["window"]
    counts = window.get("traced_counts")
    if trace is None or not counts:
        return None
    if "ticks" in params:
        found = _on_ticks(params, trace, window, counts)
        if found is None:
            return None
        counts, seconds, per_s, host_s = found
    else:
        calls, seconds = trace["modules"].get(params["module"], (0, 0.0))
        per_s = 1.0 / trace["window_s"]
        host_s = window["traced_seconds"]
        counts = dict(counts, calls=calls * per_s * host_s)
    if counts["calls"] == 0 or seconds <= 0.0:
        return None
    need = getattr(work, params["work"])(ctx["config"], counts)
    if need["flops"] <= 0 and need["bytes"] <= 0:
        return None
    least, bound = work.roofline_seconds(need, ctx["device_kind"])
    if bound != params["bound"]:
        print(f"trace_module: {params['module']} took the {bound} bound, "
              f"not {params['bound']}", file=sys.stderr)
    return 100.0 * (least / host_s) / (seconds * per_s)


def _on_ticks(params, trace, window, counts):
    """(counts, device seconds, 1, 1) of the events that ran ticks of the
    kind: calls and lanes from the ticks themselves, context lengths and
    chunk sizes from the clients' records, scaled to those calls."""
    events = [e for e in trace["module_events"]
              if trace_reader.module_name(e[0]) == params["module"]]
    ticks = sorted(window.get("ticks", ()), key=lambda t: t["t0"])
    if not events or len(ticks) < len(events):
        return None
    off = align(events, ticks, window["traced_span"][0])
    if off is None:
        print(f"trace_module: {len(events)} events of {params['module']} "
              "do not fall on the engine's ticks", file=sys.stderr)
        return None
    mine = [(e, t) for e, t in zip(events, ticks[off:])
            if t["kind"] == params["ticks"]]
    calls = len(mine)
    seconds = sum(e[2] for e, _ in mine) / 1e9
    out = {"calls": calls}
    if params["ticks"] == "decode":
        if not counts["lane_steps"]:
            return None
        out["lane_steps"] = sum(len(t["lanes"]) for _, t in mine)
        out["context_sum"] = (counts["decode_context_sum"]
                              / counts["lane_steps"] * out["lane_steps"])
    else:
        if not counts["chunks"]:
            return None
        out["chunks"] = counts["chunks"]
        out["scale"] = calls / len(counts["chunks"])
    return out, seconds, 1.0, 1.0
