"""A statistic of a field of the engine's ticks of one kind, from
``LmEngine.tick_trace()`` over the window.  Parameters: ``kind``; ``field``,
or ``from`` and ``to`` for the difference of two fields; ``stat`` (``p50`` or
``mean``); ``scale``.  A tick that lacks a field is left out: the program
fills the fields in when it knows them, and an earlier program had none."""

from benchmark import traffic


def read(params, ctx):
    values = []
    for tick in ctx["window"].get("ticks", ()):
        if tick["kind"] != params["kind"]:
            continue
        if "field" in params:
            value = tick.get(params["field"])
        else:
            a, b = tick.get(params["from"]), tick.get(params["to"])
            value = None if a is None or b is None else b - a
        if value is not None:
            values.append(value)
    if not values:
        return None
    stat = {"mean": lambda v: sum(v) / len(v),
            "p50": lambda v: traffic.percentile(v, 50)}[params["stat"]]
    return params.get("scale", 1.0) * stat(values)
