"""One of the server's counters over another, as the statistics endpoint
moved between the window's open and close.  Parameters: ``num``, ``den``
(keys of the window's ``stats``), ``scale``."""


def read(params, ctx):
    stats = ctx["window"].get("stats") or {}
    num, den = stats.get(params["num"]), stats.get(params["den"])
    if num is None or not den:
        return None
    return params.get("scale", 1.0) * num / den
