"""The sum of a field over the engine's ticks of some kinds, from
``LmEngine.tick_trace()`` over the window.  Parameters: ``kinds``;
``field``; ``scale``.  A tick that lacks the field is left out (the program
writes ``stall_s`` on a marked tick alone), and where no tick of those kinds
has it the metric is not reported: an earlier program wrote no such field,
and nor does one whose profiler is disarmed."""


def read(params, ctx):
    values = [tick[params["field"]] for tick in ctx["window"].get("ticks", ())
              if tick["kind"] in params["kinds"] and params["field"] in tick]
    if not values:
        return None
    return params.get("scale", 1.0) * sum(values)
