"""The mean number of lanes in the engine's ticks of one kind, from
``LmEngine.tick_trace()`` over the window.  Parameters: ``kind``."""


def read(params, ctx):
    lanes = [len(t["lanes"]) for t in ctx["window"].get("ticks", ())
             if t["kind"] == params["kind"]]
    if not lanes:
        return None
    return sum(lanes) / len(lanes)
