"""The whole step's share of the chip's peak: the operations that the work
completed in the window needs (``work.<work>`` of the window's ``counts``),
over the window and the peak FLOP/s.  Parameters: ``work``."""

from benchmark import work


def read(params, ctx):
    counts = ctx["window"].get("counts")
    if not counts or ctx["window"]["seconds"] <= 0:
        return None
    need = getattr(work, params["work"])(ctx["config"], counts)
    peak = work.peaks(ctx["device_kind"])["flops_per_s"] * ctx["chips"]
    return 100.0 * need["flops"] / ctx["window"]["seconds"] / peak
