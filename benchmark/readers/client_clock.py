"""A percentile of a series that the cell's clients timed on the host's
clock.  Parameters: ``series`` (a key of the window's ``series``), ``q``."""

from benchmark import traffic


def read(params, ctx):
    values = ctx["window"]["series"].get(params["series"])
    if not values:
        return None
    return traffic.percentile(values, params["q"])
