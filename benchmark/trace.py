"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy union
and idle share, device time by XLA module, the operations that took most
time, and the longest idle gaps with what the host was doing in each.

Reads the file with ``jax.profiler.ProfileData`` alone.  A TPU's plane is
named ``/device:TPU:<n>``; its ``XLA Modules`` line has one event per
executed program (``jit_<function>(<fingerprint>)``), its ``XLA Ops`` line
one per operation.  Host threads are lines of the ``/host:CPU`` plane.
"""

import glob
import os
import re


def newest_xplane(log_dir):
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def module_name(event_name):
    """``jit__decode_tick(1234)`` -> ``jit__decode_tick``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def op_name(event_name):
    """An operation's kind and result type, without its number and without
    the operands that the trace spells out after them, so that the 32
    ``%broadcast.<n>`` of 16 layers count as one line:
    ``%broadcast f32[16,2048,8,4,128]``."""
    head, _, rest = event_name.partition(" = ")
    kind = re.sub(r"\.\d+$", "", head.strip())
    return (kind + " " + rest.split("{")[0].split(" ")[0]).strip()[:96]


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _union(intervals):
    """Merged (start, end) intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _totals(events, rename=lambda n: n):
    by_name = {}
    for start, end, name in events:
        entry = by_name.setdefault(rename(name), [0, 0.0])
        entry[0] += 1
        entry[1] += (end - start) / 1e9
    return by_name


def read(path, device_prefix="/device:TPU:", top=10):
    """Summary of one trace: ``busy_s`` and ``window_s`` (averaged over the
    device planes; the window runs from a plane's first event to its last),
    ``modules`` {name: [calls, seconds]}, ``module_events`` ([name with its
    fingerprint, start ns, duration ns] in the order they ran),
    ``device_ops`` and ``idle_gaps`` (lists of [name, seconds], longest
    first)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    devices = [p for p in planes if p.name.startswith(device_prefix)]
    if not devices:
        raise ValueError(f"{path}: no plane named {device_prefix}*: "
                         f"{[p.name for p in planes]}")
    busy = window = 0.0
    modules, ops, gaps, module_events = {}, {}, [], []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        op_events = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
        mod_events = (_events(lines["XLA Modules"])
                      if "XLA Modules" in lines else [])
        spans = _union((s, e) for s, e, _ in (op_events or mod_events))
        if not spans:
            continue
        busy += sum(e - s for s, e in spans) / 1e9
        window += (spans[-1][1] - spans[0][0]) / 1e9
        module_events += [[name, start, end - start]
                          for start, end, name in sorted(mod_events)]
        for name, (calls, secs) in _totals(mod_events, module_name).items():
            entry = modules.setdefault(name, [0, 0.0])
            entry[0] += calls
            entry[1] += secs
        for name, (calls, secs) in _totals(op_events, op_name).items():
            ops[name] = ops.get(name, 0.0) + secs
        gaps += [(b[0] - a[1], a[1], b[0]) for a, b in zip(spans, spans[1:])]
    if window <= 0.0:
        raise ValueError(f"{path}: no operation ran on a device")
    n = len(devices)
    gaps = sorted(gaps, reverse=True)[:top]
    host = _host_events(planes, gaps)
    return {
        "busy_s": busy / n,
        "window_s": window / n,
        "modules": modules,
        "module_events": module_events,
        "device_ops": [[k, v / n] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_doing(host, start, end), length / 1e9]
                      for length, start, end in gaps],
    }


def _host_events(planes, gaps):
    """Host events that overlap any of the gaps: (start, end, thread: name)."""
    if not gaps:
        return []
    lo = min(start for _, start, _ in gaps)
    hi = max(end for _, _, end in gaps)
    found = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                start, end = e.start_ns, e.start_ns + e.duration_ns
                if end > lo and start < hi:
                    found.append((start, end, f"{line.name}: {e.name}"))
    return found


def _doing(host, start, end):
    """The host event that covers most of the gap [start, end)."""
    best, best_overlap = "no host event", 0
    for s, e, name in host:
        overlap = min(e, end) - max(s, start)
        # prefer the innermost (shortest) event among those covering as much
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best[:120]


def describe(path):
    """Planes, lines and a few event names: for reading a trace by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events[:2000]})[:6]
            out.append(f"  line {line.name!r}: {len(events)} events, e.g. "
                       f"{names}")
    return "\n".join(out)
