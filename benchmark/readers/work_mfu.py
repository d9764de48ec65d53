"""The whole step's share of the chip's peak, as ``mfu.py`` reads it, with
the work function looked up by name: the operations that the work completed
in the window needs (``<work_module>.<work_fn>`` of the window's ``counts``),
over the window and the peak FLOP/s.  Parameters: ``work_module`` (a module
of ``benchmark``), ``work_fn``."""

import importlib

from benchmark import work


def read(params, ctx):
    counts = ctx["window"].get("counts")
    if not counts or ctx["window"]["seconds"] <= 0:
        return None
    fn = getattr(importlib.import_module(
        f"benchmark.{params['work_module']}"), params["work_fn"])
    need = fn(ctx["config"], counts)
    peak = work.peaks(ctx["device_kind"])["flops_per_s"] * ctx["chips"]
    return 100.0 * need["flops"] / ctx["window"]["seconds"] / peak
