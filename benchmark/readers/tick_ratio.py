"""The sum of one field over the sum of another, over the engine's ticks of
one kind in the window (``LmEngine.tick_trace()``).  Parameters: ``kind``,
``num`` and ``den`` (fields of a tick; ``lanes`` counts the tick's lanes).
Ticks that lack either field are left out, and with none left the metric is
not reported: an earlier program wrote no such field."""


def _value(tick, field):
    value = tick.get(field)
    return len(value) if field == "lanes" and value is not None else value


def read(params, ctx):
    num = den = 0
    for tick in ctx["window"].get("ticks", ()):
        if tick["kind"] != params["kind"]:
            continue
        a, b = _value(tick, params["num"]), _value(tick, params["den"])
        if a is not None and b is not None:
            num, den = num + a, den + b
    return num / den if den else None
