"""The share of the traced window in which no operation ran on the device."""


def read(params, ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
